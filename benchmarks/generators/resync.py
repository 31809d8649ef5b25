"""Traffic generator ``resync``: a reconnect storm, the read half of the
protocol.

Every connection of every resident room comes back at once, as when the
proxy in front of a live provider is redeployed or a partition heals:
each session sends sync step 1 with the state vector it holds and is
owed step 2, the diff.  The configuration states the connections
(``sessions`` a room by kind); a session is a y-websocket client that
keeps its document and its client id over the reconnect.

Set-up gives the hot rooms a recent past a session can have missed:
``hot_rooms`` distinct rooms (``hot_rooms // hot_traces`` rooms of each of
the first ``hot_traces`` documents, as the flood picks them, then one
room of each following document) take ``history_keystrokes`` keystrokes
each from a resident plain typist (``benchmarks/plain_client.py``;
``history_typing_run`` characters then ``history_erasing_run`` backspaces,
rooms at different points of that cycle), sent in units of
``history_unit`` with one ``flush()`` each.

A storm is the timed interval.  All sessions send step 1 once, dealt to
ticks of ``tick_frames`` so that every tick holds an equal share of each
class below.  A tick is a unit: ``flush()``;
``handle_sync_step1_batch`` of the tick's frames; ``sync_step1`` for each
of its rooms (the server's own step 1); then, through ``cell.send_all``,
the step 2 of those of its sessions that typed while away.  The storm
ends with a ``flush()`` and the fence.  Storms run back to back.

Who sends what is the same count in every seed; the seed decides which
session, and which of the rooms that hold a document:

- ``reload_share`` of each kind's sessions (rounded down, one at least;
  of the distinct ones the hot and the other rooms' sessions each give
  their share) come with an empty state vector, a page load without a
  local copy, and are owed the whole room.  They are taken by document,
  in turn from storm to storm, so every seed reloads the same documents.
- ``stale_share`` of the hot rooms' sessions, drawn among those that do
  not reload, missed the last j entries of their room's history, j dealt
  evenly over 1..``history_keystrokes``: a state vector that is a prefix
  of what the room was sent, so closed.
- ``offline_share`` of those, no two in one room, bring back 1..
  ``offline_chars_max`` characters (dealt evenly) typed from that stale
  state at a place the missed entries did not touch, as one update.
- every other session is current and is owed the delete set alone.

Between storms, untimed: the last storm's answers are held to their
gaps, the next storm is drawn, its frames written, the offline typing
done on the benchmark's own clients, and the Python heap collected
(what was resident when the rehearsal ended is frozen, as the flood
freezes its typists, so that collection walks a storm's garbage only).

Work is elements, from state vectors alone: for every answer the sum
over clients of what the room holds past what the session holds, plus
the elements the sessions brought back.

What decides ``correct`` besides ``oracle.check`` (every hot room against
``views()``, offline typing included): every answer of every storm must
carry exactly its gap: per client, structs from the session's clock to
the room's, read by a parser of this file's own (a whole room's answer is
parsed once per distinct answer, after the window); every server step 1
must state the room's state vector; and for a seeded sample of rooms
(``sample_rooms``: hot, other distinct, storm; and every reload of a
``b4`` or ``prepend`` room) each session is a CPU ``Y.Doc`` that holds
what the session held, applies its answer and the broadcasts since, and
must end where the oracle's replay of the room does (for a room that
took nothing, at its entry in ``oracle.BASE_STATES``).  A handshake with
no answer, an answer that is not its gap or a sampled session left
behind goes to ``cell.refused`` (limit 0); an answered handshake counts
as acknowledged.

Parameters (``benchmarks/traffic/<name>.json``): those named above,
``rehearsal_storms_min``/``_max`` (storms go on until one meets no new
program), ``trace_units``.  Where the provider keeps ``last_sync_metrics``
its counts are summed into ``cell.counts`` (``sync_requests``,
``sync_reply_bytes``, ``sync_encode_buffer_bytes``) for the readers.
"""

from __future__ import annotations

import gc
import hashlib
import random

from benchmarks.deployment import BenchError, pick_rooms
from benchmarks.oracle import BASE_STATES, items_of, text_digest
from benchmarks.plain_client import PlainText, Typist, insert_update, varuint

_LETTERS = "etaoinshrdlucmfwypvbgkqjxz"
_CLOCK_BITS = 32
_TYPIST = 1_000_000   # resident typists' client ids start here
_SESSION = 2_000_000  # sessions' client ids start here
_PARSE_NOW = 4096     # answers longer than this are parsed after the window
KINDS = ("distinct", "storm", "b4", "prepend")
BIG = ("b4", "prepend")  # every reload of one is replayed on a Y.Doc


def _char(rng: random.Random) -> str:
    return " " if rng.random() < 0.18 else rng.choice(_LETTERS)


def step1_frame(sv: dict[int, int]) -> bytes:
    """Sync step 1 as a y-websocket client writes it: message type 0 and
    the encoded state vector as a byte array."""
    body = varuint(len(sv)) + b"".join(
        varuint(c) + varuint(n) for c, n in sv.items()
    )
    return b"\x00" + varuint(len(body)) + body


def _reader(data: bytes, pos: int = 0):
    def rd() -> int:
        nonlocal pos
        n = shift = 0
        while True:
            b = data[pos]
            pos += 1
            n |= (b & 0x7F) << shift
            if b < 0x80:
                return n
            shift += 7

    def skip(n: int) -> int:
        nonlocal pos
        pos += n
        if pos > len(data):
            raise IndexError("past the end")
        return pos

    def byte() -> int:
        return data[skip(1) - 1]

    return rd, skip, byte


def frame_payload(frame: bytes, kind: int) -> bytes:
    """The byte array of a sync frame of message type ``kind``."""
    rd, skip, _byte = _reader(frame)
    if rd() != kind:
        raise ValueError(f"not a sync frame of type {kind}")
    n = rd()
    end = skip(n)
    if end != len(frame):
        raise ValueError("bytes after the frame's payload")
    return frame[end - n : end]


def state_vector_of(frame: bytes) -> dict[int, int]:
    """The state vector a step-1 frame carries."""
    rd, _skip, _byte = _reader(frame_payload(frame, 0))
    return {rd(): rd() for _ in range(rd())}


def gap_of(update: bytes) -> dict[int, tuple[int, int]]:
    """Per client of a v1 update of a plain text: the clock its structs
    start at and the elements they hold (UTF-16 units, deleted ones
    counted).  Reads strings, deleted runs and GC; anything else raises."""
    rd, skip, byte = _reader(update)
    out: dict[int, tuple[int, int]] = {}
    for _ in range(rd()):
        structs, client, clock = rd(), rd(), rd()
        if client in out:
            raise ValueError(f"client {client} twice")
        elements = 0
        for _ in range(structs):
            info = byte()
            ref = info & 31
            if ref == 0 and info == 0:  # GC
                elements += rd()
                continue
            if info & 0x80:
                rd(), rd()
            if info & 0x40:
                rd(), rd()
            if not info & 0xC0:
                if rd():
                    skip(rd())
                else:
                    rd(), rd()
                if info & 0x20:
                    skip(rd())
            if ref == 4:
                n = rd()
                end = skip(n)
                s = update[end - n : end].decode("utf-8")
                elements += n if len(s) == n else len(s.encode("utf-16-le")) // 2
            elif ref == 1:
                elements += rd()
            else:
                raise ValueError(f"content {ref} in a plain text room")
        out[client] = (clock, elements)
    return out


def expected_gap(room_sv: dict, session_sv: dict) -> dict[int, tuple[int, int]]:
    """What an answer must carry, per client: from the session's clock,
    as many elements as the room holds past it."""
    return {
        c: (session_sv.get(c, 0), n - session_sv.get(c, 0))
        for c, n in room_sv.items() if n > session_sv.get(c, 0)
    }


class HotRoom:
    """One hot room: the text as its clients hold it, its resident
    typist, and per entry of its history past the trace (what
    ``cell.history`` holds of it) the client, the clock before it and its
    elements."""

    def __init__(self, index: int, room, text: PlainText):
        self.index, self.room, self.text = index, room, text
        self.typist = Typist(text, _TYPIST + index)
        self.entries: list[tuple[int, int, int]] = []

    def keystroke(self, erase: bool, rng) -> bytes:
        t = self.typist
        update = t.erase() if erase else None
        if update is not None:
            self.entries.append((t.client, t.clock, 0))
            return update
        self.entries.append((t.client, t.clock, 1))
        return t.type(_char(rng))

    def type_offline(self, client: int, clock: int, n: int, rng) -> bytes:
        """``n`` characters from a session that holds a stale prefix, as
        one struct between two neighbours of the room's trace that are
        still neighbours: nothing the session missed lies between them,
        so YATA has no conflict to settle and the characters go there."""
        ids = self.text.ids
        p = rng.randint(1, len(ids) - 1)
        for _ in range(len(ids)):
            if max(ids[p - 1], ids[p]) >> _CLOCK_BITS < _TYPIST:
                break
            p = p % (len(ids) - 1) + 1
        else:
            raise BenchError(f"{self.room.guid}: no two neighbours of the trace left")
        s = "".join(_char(rng) for _ in range(n))
        update = insert_update(client, clock, ids[p - 1], ids[p], s)
        packed = (client << _CLOCK_BITS) | clock
        for k, ch in enumerate(s):
            self.text.place(p + k, packed + k, ch)
        self.entries.append((client, clock, n))
        return update


class Tick:
    __slots__ = ("sessions", "frames", "rooms", "updates", "replies", "step1s")

    def __init__(self):
        self.sessions: list[int] = []
        self.frames: list[tuple[str, bytes]] = []
        self.rooms: list[str] = []
        self.updates: list[tuple[str, bytes]] = []
        self.replies = self.step1s = None


class Storm:
    """One storm as drawn: its ticks, what each answer must carry, what
    each room's server step 1 must state, its work."""

    def __init__(self):
        self.ticks: list[Tick] = []
        self.want: dict[int, dict] = {}        # session -> expected gap
        self.room_sv: dict[tuple[int, str], dict] = {}  # (tick, guid) -> sv
        self.sampled: dict[int, dict] = {}     # session -> what it held
        self.counts: dict[str, int] = {}
        self.work = 0
        self.in_window = False


class Generator:
    def __init__(self, params: dict, cell):
        self.p = p = params
        self.cell = cell
        self.rng = rng = random.Random(f"resync:{cell.seed}")
        cfg = cell.cfg
        per_room = cfg["sessions"]
        # the sessions, in slot order: a session's number is its identity
        self.room_of = [
            room for room in cell.plan for _ in range(int(per_room[room.kind]))
        ]
        by_kind = {k: [r for r in cell.plan if r.kind == k] for k in KINDS}
        self.sessions_in = {}
        for s, room in enumerate(self.room_of):
            self.sessions_in.setdefault(room.guid, []).append(s)
        n_hot, n_traces = int(p["hot_rooms"]), int(p["hot_traces"])
        first = n_hot // n_traces * n_traces
        hot = pick_rooms(cell.plan, cfg, "distinct", first, rng, n_traces=n_traces)
        docs = sorted({r.trace for r in by_kind["distinct"]})
        later = set(docs[n_traces : n_traces + n_hot - first])
        hot += pick_rooms(
            [r for r in by_kind["distinct"] if r.trace in later], cfg,
            "distinct", n_hot - first, rng,
        )
        self.hot_specs = hot
        hot_docs = {r.trace for r in hot}
        # reloads are taken by document: the rooms that hold each
        self.holders = {
            "distinct": self._holders(
                r for r in by_kind["distinct"] if r.trace not in hot_docs
            ),
            **{k: self._holders(by_kind[k]) for k in KINDS[1:]},
        }
        share = float(p["reload_share"])
        n_sessions = {k: len(by_kind[k]) * int(per_room[k]) for k in KINDS}
        self.n_reload = {
            k: max(1, int(share * n_sessions[k])) for k in KINDS if n_sessions[k]
        }
        hot_sessions = n_hot * int(per_room["distinct"])
        self.n_hot_reload = int(share * hot_sessions)
        self.n_reload["distinct"] -= self.n_hot_reload
        self.n_stale = int(float(p["stale_share"]) * hot_sessions)
        self.n_offline = int(float(p["offline_share"]) * self.n_stale)
        for kind, n in self.n_reload.items():
            if n > len(self.holders[kind]):
                raise BenchError(
                    f"resync: {n} {kind} reloads a storm and "
                    f"{len(self.holders[kind])} documents to take them from"
                )
        if self.n_stale > hot_sessions - self.n_hot_reload or (
            2 * self.n_offline > self.n_stale
        ):
            raise BenchError("resync: the shares do not fit the hot sessions")
        self.clock = [0] * len(self.room_of)  # a session's own clock
        self.storm: Storm | None = None
        self.pending: Storm | None = None
        self.storms = 0
        self.window_work = 0
        self.window_rates: list[float] = []
        self.storms_drawn = 0
        self.last_counts: dict = {}
        # whole rooms' answers, parsed once each after the window, and
        # the window's sampled sessions: (session, what it held, answer)
        self.big: dict[bytes, tuple[str, bytes, dict]] = {}
        self.sessions_held: list[tuple[int, dict, bytes]] = []

    @staticmethod
    def _holders(rooms) -> list[list]:
        """Rooms by the document they hold, documents in order."""
        by_doc: dict[int, list] = {}
        for room in rooms:
            by_doc.setdefault(room.trace, []).append(room)
        return [by_doc[d] for d in sorted(by_doc)]

    # -- set-up ------------------------------------------------------------

    def prepare(self) -> None:
        cell, p, rng = self.cell, self.p, self.rng
        t = cell.clock()
        bases: dict[tuple[str, int], PlainText] = {}
        self.hot = []
        for i, room in enumerate(self.hot_specs):
            key = (room.kind, room.trace)
            if key not in bases:
                bases[key] = PlainText.of_items(
                    items_of(cell.oracle.state(room, [room.base]).doc)
                )
            self.hot.append(HotRoom(i, room, bases[key].copy()))
        self.hot_of = {h.room.guid: h for h in self.hot}
        # what a room that took nothing holds: the table, or for the big
        # rooms the oracle's replay of the trace (one a kind)
        self.base_sv = {}
        for room in cell.plan:
            if room.guid in self.hot_of:
                continue
            if room.kind in BASE_STATES:
                self.base_sv[room.guid] = BASE_STATES[room.kind][room.trace][0]
            else:
                self.base_sv[room.guid] = cell.oracle.state(room, [room.base]).sv
        t_clients = cell.clock() - t
        # the rooms' recent past
        t = cell.clock()
        run_t, run_e = int(p["history_typing_run"]), int(p["history_erasing_run"])
        unit = int(p["history_unit"])
        for lap in range(int(p["history_keystrokes"])):
            order = list(self.hot)
            rng.shuffle(order)
            for at in range(0, len(order), unit):
                with cell.unit():
                    cell.send_all([
                        (h.room.guid, h.keystroke(
                            (h.index + lap) % (run_t + run_e) >= run_t, rng
                        ))
                        for h in order[at : at + unit]
                    ])
                    cell.flush()
        cell.fence()
        picks = random.Random(f"resync-sample:{cell.seed}")
        want = p["sample_rooms"]
        cold = [r for docs in self.holders["distinct"] for r in docs]
        storm = [r for docs in self.holders["storm"] for r in docs]
        self.sample = {
            r.guid for rooms, n in (
                (self.hot_specs, want["hot"]), (cold, want["cold"]),
                (storm, want["storm"]),
            ) for r in picks.sample(rooms, min(int(n), len(rooms)))
        }
        cell.log(
            f"resync: {len(self.room_of)} sessions in {len(cell.plan)} rooms, "
            f"{len(self.hot)} hot rooms over {len(bases)} documents (clients "
            f"built in {t_clients:.3f} s), {p['history_keystrokes']} "
            f"keystrokes of history each in {cell.clock() - t:.3f} s; a storm: "
            f"{self.n_reload} + {self.n_hot_reload} hot reloads, "
            f"{self.n_stale} stale, {self.n_offline} of them typed offline"
        )

    def rehearse(self) -> None:
        cell, p = self.cell, self.p
        for lap in range(int(p["rehearsal_storms_max"])):
            self.untimed(lap)
            before = cell.compiles.programs
            t = cell.clock()
            self.timed(lap)
            seconds = cell.clock() - t
            met = cell.compiles.programs - before
            cell.log(
                f"rehearsal storm {lap}: {met} programs first met, "
                f"{seconds:.3f} s, {self.pending.work / seconds:.0f} elements/s"
            )
            if met == 0 and lap + 1 >= int(p["rehearsal_storms_min"]):
                break
        # what is resident now (the rooms, their typists, the sessions)
        # stays: the collection between storms walks a storm's own
        # garbage and not 4096 rooms, so a window holds more storms
        gc.collect()
        gc.freeze()

    # -- the draw ----------------------------------------------------------

    def _draw(self, number: int) -> Storm:
        p, rng, cell = self.p, self.rng, self.cell
        storm = Storm()
        storm.in_window = cell.in_window
        classes: dict[str, list[int]] = {
            "reload": [], "offline": [], "stale": [], "current": [],
        }
        # reloads, by document, in turn from storm to storm
        for kind, n in self.n_reload.items():
            docs = self.holders[kind]
            for m in range(n):
                room = rng.choice(docs[(number * n + m) % len(docs)])
                classes["reload"].append(rng.choice(self.sessions_in[room.guid]))
        for m in range(self.n_hot_reload):
            h = self.hot[(number * self.n_hot_reload + m) % len(self.hot)]
            classes["reload"].append(rng.choice(self.sessions_in[h.room.guid]))
        reloads = set(classes["reload"])
        hot_sessions = [
            s for h in self.hot for s in self.sessions_in[h.room.guid]
            if s not in reloads
        ]
        stale = rng.sample(hot_sessions, self.n_stale)
        depth = int(p["history_keystrokes"])
        missed = dict(zip(stale, self._dealt(self.n_stale, depth)))
        typed: dict[int, int] = {}
        rooms_typed: set[str] = set()
        chars = self._dealt(self.n_offline, int(p["offline_chars_max"]))
        for s in stale:
            guid = self.room_of[s].guid
            if len(typed) < self.n_offline and guid not in rooms_typed:
                rooms_typed.add(guid)
                typed[s] = chars[len(typed)]
        if len(typed) < self.n_offline:
            raise BenchError("resync: too few rooms with a stale session")
        classes["offline"] = list(typed)
        classes["stale"] = [s for s in stale if s not in typed]
        taken = reloads | set(stale)
        classes["current"] = [
            s for s in range(len(self.room_of)) if s not in taken
        ]
        # an equal share of every class to each tick
        keyed = sorted(
            ((k + rng.random()) / len(members), s)
            for members in classes.values() for k, s in enumerate(members)
        )
        size = int(p["tick_frames"])
        order = [s for _key, s in keyed]
        tick_of = {s: k // size for k, s in enumerate(order)}
        storm.ticks = [Tick() for _ in range(-(-len(order) // size))]
        # what each hot room holds as the storm begins, and once its
        # session's offline typing has been sent and flushed
        start = {h.room.guid: dict(h.text.sv) for h in self.hot}
        entries = {h.room.guid: list(h.entries) for h in self.hot}
        after: dict[str, tuple[int, dict]] = {}
        history = {g: len(cell.history[g]) for g in self.sample}
        brought = 0
        for s, n in typed.items():
            h = self.hot_of[self.room_of[s].guid]
            client = _SESSION + s
            update = h.type_offline(client, self.clock[s], n, rng)
            self.clock[s] += n
            brought += n
            storm.ticks[tick_of[s]].updates.append((h.room.guid, update))
            after[h.room.guid] = (
                tick_of[s], {**start[h.room.guid], client: self.clock[s]}
            )
            if h.room.guid in self.sample:
                storm.sampled[s] = {"typed": update}
        gaps = 0
        for s in order:
            room, k = self.room_of[s], tick_of[s]
            tick = storm.ticks[k]
            guid = room.guid
            if guid in start:
                sent_at, sv_after = after.get(guid, (len(storm.ticks), None))
                room_sv = sv_after if sent_at < k else start[guid]
            else:
                room_sv = self.base_sv[guid]
            if s in reloads:
                sv = {}
            elif s in missed:
                sv = self._rolled_back(
                    entries[guid], start[guid], missed[s], _SESSION + s
                )
                if s in typed:
                    sv[_SESSION + s] = self.clock[s]
            else:
                sv = start.get(guid, room_sv)
            storm.want[s] = want = expected_gap(room_sv, sv)
            gaps += sum(n for _from, n in want.values())
            tick.sessions.append(s)
            tick.frames.append((guid, step1_frame(sv)))
            if (k, guid) not in storm.room_sv:
                storm.room_sv[(k, guid)] = room_sv
                tick.rooms.append(guid)
            if guid in self.sample or (s in reloads and room.kind in BIG):
                storm.sampled.setdefault(s, {}).update(
                    history=history.get(guid, 1), missed=missed.get(s, 0),
                    reload=s in reloads,
                )
        storm.work = gaps + brought
        storm.counts = {
            **{k: len(v) for k, v in classes.items()},
            "gap_elements": gaps, "brought_back": brought,
        }
        return storm

    def _dealt(self, n: int, upto: int) -> list[int]:
        """``n`` numbers of 1..``upto``, each as often as the next, in a
        seeded order."""
        out = [k % upto + 1 for k in range(n)]
        self.rng.shuffle(out)
        return out

    @staticmethod
    def _rolled_back(entries: list, sv: dict, missed: int, own: int) -> dict:
        """``sv`` less the last ``missed`` of ``entries`` (its own kept: a
        session cannot have missed what it typed)."""
        sv = dict(sv)
        for client, clock, _n in reversed(entries[-missed:]):
            if client != own:
                if clock:
                    sv[client] = clock
                else:
                    sv.pop(client, None)
        return sv

    # -- a storm -----------------------------------------------------------

    def untimed(self, i: int) -> None:
        t = self.cell.clock()
        self._settle()
        self.storm = self._draw(self.storms_drawn)
        self.storms_drawn += 1
        gc.collect()
        self.cell.note("untimed_ms_a_storm", (self.cell.clock() - t) * 1e3)

    def timed(self, i: int) -> None:
        cell, prov = self.cell, self.cell.prov
        storm, counts = self.storm, self.cell.counts
        t = cell.clock()
        for tick in storm.ticks:
            with cell.unit():
                cell.flush()
                tick.replies = prov.handle_sync_step1_batch(tick.frames)
                tick.step1s = [prov.sync_step1(guid) for guid in tick.rooms]
                cell.send_all(tick.updates)
                m = getattr(prov, "last_sync_metrics", None)
                if m is not None and cell.in_window:
                    for name, key in (
                        ("sync_requests", "n_requests"),
                        ("sync_reply_bytes", "reply_bytes"),
                        ("sync_encode_buffer_bytes", "encode_buffer_bytes"),
                    ):
                        counts[name] = counts.get(name, 0) + m[key]
                for s in tick.sessions:
                    if s in storm.sampled:
                        storm.sampled[s]["heard"] = len(
                            cell.broadcasts.get(self.room_of[s].guid, ())
                        )
        cell.flush()
        cell.fence()
        self.storm, self.pending = None, storm
        if cell.in_window:
            self.storms += 1
            self.window_work += storm.work
            self.window_rates.append(storm.work / (cell.clock() - t))

    # -- the comparison ----------------------------------------------------

    def _settle(self) -> None:
        """Hold the last storm's answers to their gaps: the short ones
        now, a whole room's once per distinct answer after the window."""
        storm, self.pending = self.pending, None
        if storm is None:
            return
        cell = self.cell
        for k, tick in enumerate(storm.ticks):
            replies = tick.replies or [None] * len(tick.sessions)
            for s, reply in zip(tick.sessions, replies):
                guid = self.room_of[s].guid
                try:
                    update = frame_payload(reply, 1)
                    if len(update) > _PARSE_NOW:
                        # rooms of one document give one answer
                        key = hashlib.blake2b(update, digest_size=16).digest()
                        held = self.big.setdefault(key, (guid, update, storm.want[s]))
                        if held[2] != storm.want[s]:
                            raise ValueError("one answer to two gaps")
                    elif gap_of(update) != storm.want[s]:
                        raise ValueError("not the session's gap")
                except (ValueError, IndexError, TypeError, UnicodeError):
                    cell.refused.append(guid)
                    continue
                cell.acknowledged += 1
                if s in storm.sampled and storm.in_window:
                    self.sessions_held.append(
                        (s, storm.sampled[s], update)
                    )
            for guid, frame in zip(tick.rooms, tick.step1s or ()):
                try:
                    if state_vector_of(frame) != storm.room_sv[(k, guid)]:
                        raise ValueError("not the room's state vector")
                except (ValueError, IndexError, TypeError):
                    cell.refused.append(guid)
            tick.replies = tick.step1s = None
        self.last_counts = storm.counts

    def finish(self) -> None:
        cell = self.cell
        gc.unfreeze()
        self._settle()
        t = cell.clock()
        for guid, update, want in self.big.values():
            try:
                if gap_of(update) != want:
                    raise ValueError("not the session's gap")
            except (ValueError, IndexError, UnicodeError):
                cell.refused.append(guid)
        t_big = cell.clock() - t
        t = cell.clock()
        behind = self._sessions_behind()
        cell.log(
            "storm rates in the window, elements/s: "
            + " ".join(f"{r:.0f}" for r in self.window_rates)
        )
        cell.log(
            f"resync: {len(self.big)} distinct long answers parsed in "
            f"{t_big:.3f} s; {len(self.sessions_held)} sampled sessions "
            f"replayed on a Y.Doc in {cell.clock() - t:.3f} s, {behind} "
            f"left behind"
        )

    def _sessions_behind(self) -> int:
        """The sampled sessions as CPU ``Y.Doc``s: what each held, its
        answer, the broadcasts since; each must end where the room's
        replay does."""
        import yjs_tpu as Y

        cell = self.cell
        by_guid = {r.guid: r for r in cell.plan}
        wants: dict[str, tuple[dict, str]] = {}
        seen: set[tuple] = set()
        behind = 0
        for s, held, answer in self.sessions_held:
            room = self.room_of[s]
            guid = room.guid
            history = cell.history[guid]
            if guid not in self.hot_of:
                # a room that took nothing gives a session of one kind
                # one answer, storm after storm: replayed once
                key = (
                    guid, held["reload"],
                    hashlib.blake2b(answer, digest_size=16).digest(),
                )
                if key in seen:
                    continue
                seen.add(key)
            if guid not in wants:
                w = cell.oracle.state(by_guid[guid], history)
                wants[guid] = (w.sv, text_digest(w.text))
                if len(history) == 1 and room.kind in BASE_STATES and (
                    wants[guid] != BASE_STATES[room.kind][room.trace]
                ):
                    behind += 1
            doc = Y.Doc(gc=False)
            if not held["reload"]:
                n, own = held["history"], _SESSION + s
                keep = n - held["missed"]
                entries = self.hot_of[guid].entries if guid in self.hot_of else ()
                for at, update in enumerate(history[:n]):
                    if at < keep or entries[at - 1][0] == own:
                        Y.apply_update(doc, update)
            if "typed" in held:
                Y.apply_update(doc, held["typed"])
            Y.apply_update(doc, answer)
            for update in cell.broadcasts.get(guid, [])[held["heard"] :]:
                Y.apply_update(doc, update)
            have = (
                Y.decode_state_vector(Y.encode_state_vector(doc)),
                text_digest(doc.get_text("text").to_string()),
            )
            if have != wants[guid]:
                behind += 1
                cell.refused.append(guid)
        return behind

    def work(self) -> int:
        self.cell.log(
            f"{self.storms} storms in the window, {self.window_work} elements; "
            f"the last storm: {self.last_counts}"
        )
        return self.window_work

    def views(self) -> dict[str, tuple[dict, str]]:
        """What every hot room's clients hold, offline typing included."""
        return {h.room.guid: (h.text.sv, h.text.text()) for h in self.hot}
