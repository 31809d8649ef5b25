"""Traffic generator ``offline``: sessions that come back from working
offline, each with one large update, several to a room.

The deployment's configuration (``yws-offline``) states the clients:
``y-indexeddb`` keeps a client's document while it has no connection, so
a session goes on editing and, once ``y-websocket`` reconnects, answers
the server's step 1 with ONE update holding everything it did while
away.  What it did is one of crdt-benchmarks' B2 shapes
(``offline_operations`` operations at random positions of what the
session sees, ``benchmarks/plain_offline.py``): ``b2.2`` characters,
``b2.3`` words, ``b2.4`` words and deletes in a text room with
``offline_writers.text`` writers, or single numbers into a ``Y.Array``
room with ``offline_writers.array`` writers (``BASELINE.json`` config
4).  Every writer of a room left from the room's resident state and
nobody typed online meanwhile, so the updates of a room are mutually
concurrent: the server merges them into rows it holds, through the
element lanes (``apply_plan2``), never as a whole-room row load.

The harness deals every room one root ``Y.Text``; at set-up, untimed,
this generator homes a ``Y.Array`` document in each of ``array_rooms``
rooms (release, ``receive_update``, ``flush()``, as ``prosemirror`` homes
its trees): room k of the pick holds base array k mod
``array_documents``, each what one client leaves after
``array_base_inserts`` inserts of one number at random positions, made
here by the plain client from the document's number alone.  The text
rooms of the waves are the rooms picked after them, so every seed merges
into the same documents: the seed draws which room holds which, the
positions and the characters.

A wave is ``wave_shapes`` rooms of each shape (``wave_rooms`` in all),
one update a session, and is the timed interval.  It is three ticks,
each a unit.  Tick 1: ``flush()``; ``handle_sync_step1_batch`` of every
session's step 1 (each states the room as the session left it plus its
own clock, so each answer's gap is empty); ``sync_step1`` for each room
(the server's own step 1, which a client answers with its update); then,
through ``cell.send_all``, the updates that arrive at once.  In
``together_share`` of each shape's rooms that is every writer's (one
flush plans 2-3 mutually concurrent updates of a room); in the others
writer k's update arrives in tick k (ticks 2 and 3 begin with the
``flush()`` that integrates the tick before, so the second writer merges
into rows that already hold the first's).  The wave ends with a
``flush()`` and the fence.  ``waves`` disjoint sets of rooms take turns.

Between waves, untimed (with the window's counters off: they are the
waves'): the next wave's rooms are released and loaded again from their
base (``receive_update`` + ``flush()``: the row load, and one ``flush()``
more, so that whatever a reload leaves to the flush after it is untimed
and tick 1's ``flush()`` finds nothing to do), the plan cache is emptied
as a reload's is, the next wave's sessions are typed on the
benchmark's own writers (``writer_processes`` processes of
``plain_offline.py``, asleep while a wave is timed; client ids from
``first_client`` up, a new one for every session of the run, so no two
updates of a run are byte-equal and the plan cache serves none), the
last wave is settled and the heap collected (what set-up left resident is
frozen before the rehearsal).

Work is elements: the clocks the sessions bring back, every element
they inserted, deleted since or not.

What decides ``correct`` besides ``oracle.check`` (which replays every
room that holds a merge at the end on a CPU ``Y.Doc``, in arrival
order): every answer of every wave must be a step 2 with no struct,
every server step 1 must state the room's base; every merged room, when
it is released or at the end, must hold the state vector its writers'
clocks give, and its rows on the device must be the host mirror's; of
EVERY wave, rehearsed or timed, a seeded sample of rooms
(``sample_rooms_a_wave``, the shapes and arrival classes taking turns
from wave to wave) is kept when the wave is settled: the room's encoded
state, its text or JSON, what it was sent and what it broadcast.  After
the window a ``Y.Doc`` fed base + updates in arrival order AND one fed
them in reverse must both give the kept canonical state and text or JSON
(and, where the room still holds that merge, what is walked out of the
device's rows); one session of each kept room, as a ``Y.Doc`` holding the
base and its own history, applies the broadcasts it heard and must end
where the room did.  A difference goes to ``cell.refused`` (limit 0).

Into ``cell.counts``, summed over the waves' flushes, what the cell's
readers read from ``last_flush_metrics`` (``SUMMED``), and
``offline_waves``, ``offline_structs``.

A program whose ``last_flush_metrics`` lacks one of ``REQUIRED`` (which
write path a flush's links took, the lanes of the keys it dispatched,
the conflict scan's steps) is refused at set-up, before this generator
touches a room or starts a process: the command ends with exit code 1
and no result.  The cell is
for merges that go through the element lanes, and only the program can
say that they did; and the programs from before those counts (PR 46)
keep a bulk merge's exact lane key, so that a wave of the window
compiles one (``compiles_in_window`` 1-2 and ``correct`` false in every
run of theirs on the chip, ``PERF.md`` §6): they do not support this
deployment.

Parameters (``benchmarks/traffic/<name>.json``): those named above,
``lengths`` (``word``, ``delete``: least and most), ``rehearsal_waves_max``
(waves go on until every set's last wave met no new program),
``trace_units``.
"""

from __future__ import annotations

import gc
import random
from array import array

from benchmarks.deployment import BenchError, pick_rooms
from benchmarks.generators.resync import (
    frame_payload, state_vector_of, step1_frame,
)
from benchmarks.oracle import BASE_STATES, device_rows_differ, items_of
from benchmarks.plain_client import PlainText
from benchmarks.plain_offline import SHAPES, Pool
from yjs_tpu.ops import plan_cache

REQUIRED = ("lane_links", "row_links", "lanes_dispatched", "conflict_steps")
SUMMED = REQUIRED + ("rows_planned", "emit_batched", "emit_fallback")
_ARRAY_AUTHOR = 2_500_000  # the base arrays' authors' client ids


def _no_structs(update: bytes) -> bool:
    """A v1 update that holds no struct (its delete set is not read)."""
    return update[:1] == b"\x00"


class Room:
    """One room of a wave: its base as the writers left with it."""

    __slots__ = ("spec", "shape", "together", "kind", "root", "base",
                 "base_sv", "ids", "dead")

    def __init__(self, spec, shape, together, base, base_sv, ids, dead, root):
        self.spec, self.shape, self.together = spec, shape, together
        self.kind = "array" if shape == "array" else "text"
        self.root, self.base, self.base_sv = root, base, base_sv
        self.ids, self.dead = ids, dead


class Wave:
    """One wave as drawn: who sends what in which tick."""

    def __init__(self, number: int, rooms: list[Room]):
        self.number, self.rooms = number, rooms
        self.frames: list[tuple[str, bytes]] = []   # every session's step 1
        self.sessions: list[tuple[Room, int, int]] = []  # room, client, tick
        self.ticks: list[list[tuple[str, bytes]]] = [[], [], []]
        self.updates: dict[int, bytes] = {}         # client -> its update
        self.want_sv: dict[str, dict] = {}          # guid -> sv once merged
        self.replies = self.step1s = None
        self.heard: dict[str, int] = {}   # broadcasts a room had at step 1
        self.work = self.structs = 0


class Kept:
    """One sampled room of a wave as it stood when the wave was settled,
    for the replays after the window."""

    __slots__ = ("wave", "room", "history", "state", "held", "want_sv",
                 "update", "heard")

    def __init__(self, wave, room, history, state, held, update, heard):
        self.wave, self.room, self.history = wave.number, room, history
        self.state, self.held = state, held
        self.want_sv = wave.want_sv[room.spec.guid]
        self.update, self.heard = update, heard  # one session's


class Generator:
    def __init__(self, params: dict, cell):
        self.p = p = params
        self.cell = cell
        cfg = cell.cfg
        self.rng = rng = random.Random(f"offline:{cell.seed}")
        self.root = cfg["array_root"]
        self.n_sets = int(p["waves"])
        shapes = {s: int(p["wave_shapes"][s]) for s in SHAPES}
        n_text = sum(n for s, n in shapes.items() if s != "array")
        n_array, n_docs = int(cfg["array_rooms"]), int(cfg["array_documents"])
        if (
            sum(shapes.values()) != int(p["wave_rooms"])
            or self.n_sets * shapes["array"] > min(n_array, n_docs)
            or n_array % n_docs
        ):
            raise BenchError(
                f"offline: waves of {shapes} do not fit {p['wave_rooms']} "
                f"rooms a wave, {n_array} array rooms and {n_docs} base arrays"
            )
        picked = pick_rooms(
            cell.plan, cfg, "distinct", n_array + self.n_sets * n_text, rng
        )
        self.array_specs = picked[:n_array]
        # guid -> the number of the base array the room holds
        self.home = {r.guid: k % n_docs for k, r in enumerate(self.array_specs)}
        text_specs = picked[n_array:]
        # per set: (room, shape, arrives together), the same documents,
        # shapes and arrival classes in every seed
        share = float(p["together_share"])
        self.sets: list[list[tuple]] = []
        for w in range(self.n_sets):
            rooms, at = [], w * n_text
            for shape in SHAPES:
                n = shapes[shape]
                if shape == "array":
                    specs = self.array_specs[w * n : (w + 1) * n]
                else:
                    specs, at = text_specs[at : at + n], at + n
                rooms += [
                    (spec, shape, k < int(share * n))
                    for k, spec in enumerate(specs)
                ]
            self.sets.append(rooms)
        self.writers = {
            "text": int(cfg["offline_writers"]["text"]),
            "array": int(cfg["offline_writers"]["array"]),
        }
        self.operations = int(cfg["offline_operations"])
        self.next_client = int(p["first_client"])
        self.rooms: list[list[Room]] = []
        self.wave: Wave | None = None
        self.pending: Wave | None = None
        self.last_of_set: dict[int, Wave] = {}
        self.kept: list[Kept] = []  # the sampled rooms of every wave
        self.merged: dict[str, dict] = {}  # guid -> the sv its merge gives
        self.waves_drawn = self.waves_timed = 0
        self.window_work = self.window_structs = 0
        self.window_rates: list[float] = []
        self.pool: Pool | None = None
        # seconds of the sampled rooms' comparison, by part
        self.took = dict.fromkeys(("replay", "canonical", "read", "session"), 0.0)

    # -- set-up ------------------------------------------------------------

    def _array_task(self, number: int, sequence: bool) -> dict:
        return {
            "client": _ARRAY_AUTHOR + number, "ids": array("q"), "dead": b"",
            "root": self.root, "kind": "array", "shape": "array",
            "operations": int(self.cell.cfg["array_base_inserts"]),
            "seed": f"array-document:{number}", "sequence": sequence,
        }

    def prepare(self) -> None:
        cell, cfg = self.cell, self.cell.cfg
        t = cell.clock()
        # the cold load's flush left every count the program keeps
        kept = cell.prov.engine.last_flush_metrics or {}
        lacks = [key for key in REQUIRED if key not in kept]
        if lacks:
            raise BenchError(
                f"this program keeps no {', '.join(lacks)} in "
                "last_flush_metrics: it cannot say which write path a "
                "merge's links took, and it is from before the support for "
                "this deployment (PR 46): a bulk merge's lane key is exact "
                "there, and a wave of the window compiles one"
            )
        try:  # a program that reads a room's one root name cannot serve these
            cell.prov.to_json(self.array_specs[0].guid, self.root)
        except TypeError as e:
            raise BenchError(
                f"this provider reads no root by its name ({e}): it cannot "
                f"serve a room whose document is the array {self.root!r}"
            ) from e
        self.pool = Pool(int(self.p["writer_processes"]))
        n_docs = int(cfg["array_documents"])
        in_waves = {
            self.home[spec.guid]
            for rooms in self.sets for spec, shape, _t in rooms
            if shape == "array"
        }
        arrays = self.pool.map([
            self._array_task(k, k in in_waves) for k in range(n_docs)
        ])
        t_arrays = cell.clock() - t
        t = cell.clock()
        homing = [
            (r.guid, arrays[self.home[r.guid]]["update"])
            for r in self.array_specs
        ]
        for guid, _update in reversed(homing):
            cell.release(guid)
        cell.send_all(homing)
        cell.flush()
        # the first client back brings what the server already holds: the
        # idle rooms are sent their document once more (no news), so
        # that ``oracle.check`` replays the two updates of their history
        # and does not hold them to the trace the harness dealt them
        busy = {
            spec.guid for rooms in self.sets for spec, _s, _t in rooms
        }
        idle = [pair for pair in homing if pair[0] not in busy]
        cell.send_all(idle, news=False)
        cell.flush()
        cell.fence()
        cell.touched.difference_update(guid for guid, _u in idle)
        t_homed = cell.clock() - t
        t = cell.clock()
        texts: dict[tuple, tuple] = {}
        for rooms in self.sets:
            out = []
            for spec, shape, together in rooms:
                if shape == "array":
                    doc = arrays[self.home[spec.guid]]
                    out.append(Room(
                        spec, shape, together, doc["update"],
                        {_ARRAY_AUTHOR + self.home[spec.guid]: doc["clock"]},
                        array("q", doc["ids"]), bytes(doc["dead"]), self.root,
                    ))
                    continue
                key = (spec.kind, spec.trace)
                if key not in texts:
                    text = PlainText.of_items(
                        items_of(cell.oracle.state(spec, [spec.base]).doc)
                    )
                    texts[key] = (array("q", text.ids), bytes(text.dead))
                out.append(Room(
                    spec, shape, together, spec.base,
                    BASE_STATES[spec.kind][spec.trace][0], *texts[key], "text",
                ))
            self.rooms.append(out)
        cell.log(
            f"offline: {n_docs} base arrays typed in {t_arrays:.3f} s, "
            f"{len(homing)} array rooms homed in {t_homed:.3f} s; "
            f"{self.n_sets} sets of {len(self.rooms[0])} rooms "
            f"({len(texts)} text documents read in {cell.clock() - t:.3f} s), "
            f"{self.operations} operations a session, "
            f"{len(self.pool.workers)} writer processes"
        )

    def rehearse(self) -> None:
        cell, p = self.cell, self.p
        # what is resident now (the rooms, their bases) stays: the
        # collection between waves walks a wave's own garbage.  Frozen
        # before the rehearsal and not after it: the collector counts
        # what survives from here on, and until that count has grown it
        # runs a full collection every few thousand allocations, which
        # made the first two waves after a freeze three times as long as
        # the rest (CPU count, PR 46); those are now rehearsal waves
        gc.collect()
        gc.freeze()
        clean = 0  # waves in a row that met no new program
        for lap in range(int(p["rehearsal_waves_max"])):
            self.untimed(lap)
            before = cell.compiles.programs
            t = cell.clock()
            self.timed(lap)
            seconds = cell.clock() - t
            met = cell.compiles.programs - before
            cell.log(
                f"rehearsal wave {lap}: {met} programs first met, "
                f"{seconds:.3f} s, {self.pending.work / seconds:.0f} elements/s"
            )
            clean = clean + 1 if met == 0 else 0
            if clean >= self.n_sets:  # every set's last wave met none
                break

    # -- the draw ----------------------------------------------------------

    def _draw(self, number: int) -> Wave:
        rooms = self.rooms[number % self.n_sets]
        wave = Wave(number, rooms)
        tasks = []
        for room in rooms:
            n = self.writers[room.kind]
            clients = list(range(self.next_client, self.next_client + n))
            self.next_client += n
            for k, client in enumerate(clients):
                wave.sessions.append((room, client, 0 if room.together else k))
                tasks.append({
                    "client": client, "ids": room.ids, "dead": room.dead,
                    "root": room.root, "kind": room.kind, "shape": room.shape,
                    "operations": self.operations,
                    "seed": f"offline:{self.cell.seed}:{client}",
                    "lengths": self.p["lengths"],
                })
        done = self.pool.map(tasks)
        order = list(range(len(tasks)))
        self.rng.shuffle(order)  # arrival order within a tick
        for j in order:
            room, client, tick = wave.sessions[j]
            guid, d = room.spec.guid, done[j]
            wave.updates[client] = d["update"]
            wave.ticks[tick].append((guid, d["update"]))
            wave.frames.append(
                (guid, step1_frame({**room.base_sv, client: d["clock"]}))
            )
            sv = wave.want_sv.setdefault(guid, dict(room.base_sv))
            sv[client] = d["clock"]
            wave.work += d["clock"]
            wave.structs += d["structs"]
        return wave

    # -- a wave ------------------------------------------------------------

    def _reset(self, rooms: list[Room]) -> None:
        """The rooms made what they were before a wave: released (a
        room that held a merge is held to its writers' clocks as it
        goes), loaded again from their base as rows, and flushed once
        more."""
        cell = self.cell
        for room in reversed(rooms):
            guid = room.spec.guid
            cell.release(guid)
            want = self.merged.pop(guid, None)
            if want is not None:
                # the state vector this life ended with, held here to
                # the clocks: ``oracle.check`` would replay every such
                # life on a Y.Doc (0.4 s a room a wave)
                if cell.left[guid].pop() != want:
                    cell.refused.append(guid)
        cell.send_all([(room.spec.guid, room.base) for room in rooms])
        cell.flush()
        # whatever the reload leaves to the flush after it is the
        # reset's too (today nothing: a row load writes compact rows; it
        # is the load's own flush that compacts, the rooms the last
        # wave's closing flush planned).  The harness has then seen that
        # compaction: ``Cell.flush`` counts one it has not seen as the
        # window's
        cell.flush()
        cell._last_compaction = cell.prov.engine.last_compaction

    def _flush(self, pending: bool = True) -> None:
        cell = self.cell
        if not pending:
            # tick 1 begins with a flush that has nothing to take (the
            # reload was flushed twice, untimed) and records nothing:
            # ``last_flush_metrics`` stays the reload's, which
            # ``Cell.flush`` would add to the window's counts again
            cell.prov.flush()
            return
        cell.flush()
        if not cell.in_window:
            return
        m = cell.prov.engine.last_flush_metrics
        for key in SUMMED:
            cell.counts[key] = cell.counts.get(key, 0) + m[key]

    def untimed(self, i: int) -> None:
        cell = self.cell
        t = cell.clock()
        self._settle()
        number = self.waves_drawn
        self.waves_drawn += 1
        # the window's counters are its waves': the reload between them
        # is the benchmark making room, as a release is
        in_window, cell.in_window = cell.in_window, False
        self._reset(self.rooms[number % self.n_sets])
        cell.in_window = in_window
        plan_cache.reset_cache()
        self.wave = self._draw(number)
        gc.collect()
        cell.fence()
        cell.note("untimed_ms_a_wave", (cell.clock() - t) * 1e3)

    def timed(self, i: int) -> None:
        cell, prov, wave = self.cell, self.cell.prov, self.wave
        t = cell.clock()
        for k, updates in enumerate(wave.ticks):
            with cell.unit():
                self._flush(pending=k > 0)
                if k == 0:
                    wave.replies = prov.handle_sync_step1_batch(wave.frames)
                    wave.step1s = [
                        prov.sync_step1(room.spec.guid) for room in wave.rooms
                    ]
                    wave.heard = {
                        room.spec.guid: len(
                            cell.broadcasts.get(room.spec.guid, ())
                        )
                        for room in wave.rooms
                    }
                cell.send_all(updates)
        self._flush()
        cell.fence()
        self.merged.update(wave.want_sv)
        self.wave, self.pending = None, wave
        self.last_of_set[wave.number % self.n_sets] = wave
        if cell.in_window:
            self.waves_timed += 1
            self.window_work += wave.work
            self.window_structs += wave.structs
            self.window_rates.append(wave.work / (cell.clock() - t))

    # -- the comparison ----------------------------------------------------

    def _settle(self) -> None:
        """Hold the last wave's handshakes to what they must carry, and
        its rooms' rows on the device to the host mirror's."""
        wave, self.pending = self.pending, None
        if wave is None:
            return
        cell = self.cell
        replies = wave.replies or [None] * len(wave.frames)
        for (guid, _frame), reply in zip(wave.frames, replies):
            try:
                if not _no_structs(frame_payload(reply, 1)):
                    raise ValueError("an answer with structs to a session "
                                     "that holds all the room does")
            except (ValueError, IndexError, TypeError):
                cell.refused.append(guid)
                continue
            cell.acknowledged += 1
        for room, frame in zip(wave.rooms, wave.step1s or [None] * len(wave.rooms)):
            try:
                if state_vector_of(frame) != room.base_sv:
                    raise ValueError("not the room's state vector")
            except (ValueError, IndexError, TypeError):
                cell.refused.append(room.spec.guid)
        wave.replies = wave.step1s = None
        guids = [room.spec.guid for room in wave.rooms]
        differ = device_rows_differ(cell.prov, guids)
        if differ:
            cell.refused.extend(guids[:differ])
        for room in self._sample(wave):
            guid = room.spec.guid
            rng = random.Random(f"offline-session:{cell.seed}:{guid}")
            client = rng.choice([c for r, c, _t in wave.sessions if r is room])
            self.kept.append(Kept(
                wave, room, list(cell.history[guid]),
                cell.prov.encode_state_as_update(guid), self._read(guid, room),
                wave.updates[client],
                cell.broadcasts.get(guid, [])[wave.heard[guid]:],
            ))

    def finish(self) -> None:
        cell = self.cell
        gc.unfreeze()
        if self.pool is not None:
            self.pool.close()
        self._settle()
        cell.counts["offline_waves"] = self.waves_timed
        cell.counts["offline_structs"] = self.window_structs
        cell.log(
            "wave rates in the window, elements/s: "
            + " ".join(f"{r:.0f}" for r in self.window_rates)
        )
        t = cell.clock()
        differ = 0
        for guid, want in self.merged.items():
            if not cell.prov.has_doc(guid) or (
                cell.prov.state_vector(guid) != want
            ):
                differ += 1
                cell.refused.append(guid)
        t_sv = cell.clock() - t
        t = cell.clock()
        resident = {wave.number for wave in self.last_of_set.values()}
        behind = sum(self._replayed(k, k.wave in resident) for k in self.kept)
        cell.log(
            f"offline: {len(self.merged)} merged rooms held to their "
            f"writers' clocks in {t_sv:.3f} s, {differ} differ; "
            f"{len(self.kept)} sampled rooms of "
            f"{len({k.wave for k in self.kept})} waves replayed forwards, "
            f"in reverse and as a session in {cell.clock() - t:.3f} s "
            f"{ {k: round(v, 2) for k, v in self.took.items()} }, "
            f"{behind} differ; in the window "
            + " ".join(f"{k} {cell.counts.get(k, 0)}" for k in SUMMED)
        )

    def _sample(self, wave: Wave) -> list[Room]:
        """``sample_rooms_a_wave`` rooms drawn by the seed, the shapes
        and arrival classes taking turns from one wave of a set to its
        next."""
        rng = random.Random(f"offline-sample:{self.cell.seed}:{wave.number}")
        classes: dict[tuple, list[Room]] = {}
        for room in wave.rooms:
            classes.setdefault((room.shape, room.together), []).append(room)
        groups = list(classes.values())
        n = int(self.p["sample_rooms_a_wave"])
        first = wave.number // self.n_sets * n
        share = [0] * len(groups)
        for j in range(n):
            share[(first + j) % len(groups)] += 1
        return [
            room for rooms, k in zip(groups, share)
            for room in rng.sample(rooms, min(k, len(rooms)))
        ]

    def _read(self, guid: str, room: Room):
        prov = self.cell.prov
        if room.kind == "array":
            return prov.to_json(guid, room.root)
        return prov.text(guid)

    def _replayed(self, kept: Kept, resident: bool) -> int:
        """One kept room against a ``Y.Doc`` fed what it was sent, in
        arrival order and in reverse, and one of its sessions against
        the room; where the room still holds that merge, what the
        device's rows give too.  Returns 1 where anything differs."""
        import yjs_tpu as Y

        cell, eng = self.cell, self.cell.prov.engine
        room, history = kept.room, kept.history
        guid = room.spec.guid

        def held(doc):
            if room.kind == "array":
                return doc.get_array(room.root).to_json()
            return doc.get_text(room.root).to_string()

        took, clock = self.took, cell.clock
        ok = len(history) == 1 + self.writers[room.kind]
        t = clock()
        have = Y.merge_updates([kept.state])
        took["canonical"] += clock() - t
        raw = None
        for updates in (history, history[:1] + history[:0:-1]):
            t = clock()
            doc = cell.oracle.replay(updates)
            took["replay"] += clock() - t
            t = clock()
            state = Y.encode_state_as_update(doc)
            if state != raw:  # the same bytes need no second merge
                raw = state
                ok = ok and Y.merge_updates([state]) == have
            took["canonical"] += clock() - t
            ok = ok and held(doc) == kept.held
        if resident:
            t = clock()
            eng.export_from_device = True
            ok = ok and self._read(guid, room) == kept.held
            eng.export_from_device = False
            took["read"] += clock() - t
        t = clock()
        # one session: the base, its own history, the broadcasts since
        # its step 1 (its answer held no struct)
        session = cell.oracle.replay([room.base, kept.update])
        for update in kept.heard:
            Y.apply_update(session, update)
        ok = ok and held(session) == kept.held and (
            Y.decode_state_vector(Y.encode_state_vector(session))
            == kept.want_sv
        )
        took["session"] += clock() - t
        if not ok:
            cell.refused.append(guid)
        return 0 if ok else 1

    def work(self) -> int:
        self.cell.log(
            f"{self.waves_timed} waves in the window, {self.window_work} "
            f"elements and {self.window_structs} structs brought back"
        )
        return self.window_work

    def views(self) -> dict[str, tuple[dict, str]]:
        """What the array rooms' writers' clocks give, and the root
        ``text`` these rooms do not have."""
        return {
            room.spec.guid: (wave.want_sv[room.spec.guid], "")
            for wave in self.last_of_set.values() for room in wave.rooms
            if room.kind == "array"
        }
