"""Traffic generator ``recover``: the process that owned the chip is
killed, and the same host brings it back from its write-ahead log.

The harness's provider is the *predecessor*.  At set-up, untimed, the
hot rooms' plain typists (``benchmarks/plain_client.py``: the flood's,
``solo_rooms`` with one and ``duet_rooms`` with two that type at once)
type a *tail* into it through ``cell.send_all`` / ``cell.flush``,
``updates_a_flush`` keystrokes a flush: hot room ``i`` (duets first, in
the order ``pick_rooms`` gives, which holds the same documents in every
seed) gets ``(tail_step * i + tail_offset) % (tail_max + 1)``
keystrokes, so the lengths are uniform over ``0..tail_max`` and every
seed does the same work; the seed draws which rooms hold the documents,
the characters, the places jumped to and the order of arrival.  A solo
typist works in the flood's runs (``typing_run`` characters, then
``erasing_run`` backspaces, a jump before one typing run in
``jump_every_runs``).  The log then holds one whole-room record a room
and the tail behind it, and no checkpoint file.

The last ``final_units`` flushes of the tail (``F``) are held back for
``untimed(0)``, after the fault controls arm (``faults.install`` wraps
the harness's provider, and that one is dead once the window runs): a
control then strikes among the last updates before the crash, the ones
a crash is likeliest to lose.

**A crash**: the predecessor takes no further call (no ``close()``, no
``checkpoint()``), its directory is copied, and to the copy's last
segment the first half of a record's bytes is appended: the write a
kill cut short (the record is the first keystroke's, which was never
acknowledged and is sent again to the successor).  Every recovery gets
a copy of its own, because ``recover`` truncates the torn tail and
journals onward.

**A timed interval**, one unit, to a fence: ``TpuProvider.recover(copy,
n_docs=slots, backend="device")`` (construction, log read, one flush),
``on_update`` registered, fence; then the first keystroke to one
typical room that is not hot and ``flush()``, which compacts what the
recovery loaded, fence.  Untimed before it: the previous successor is
held to the predecessor (below), dropped and collected so that its
tables leave the device, the planner's process-wide plan cache is
emptied (a new process has none: ``reload`` does the same), the crashed
directory copied.  Closed loop, one recovery after the other.  Work is
the elements of all recovered rooms (the sum of their state vectors'
clocks, from the plain reference), the same in every seed.

Each successor is a timeline of its own: all get the same first
keystroke.  The one that stands when the window ends becomes
``cell.prov``, its directory ``cell.wal_dir``, and the keystroke is in
its room's ``cell.history`` once, so ``oracle.check`` holds the
survivor's rooms, journal and listeners as it holds any cell's (the
tailed rooms, every long room and ``others_compared`` more are marked
``touched``, so their device rows are compared too).

**Rehearsal** replays the records the window will replay: the
predecessor's directory (which lacks ``F``) is copied, ``F`` is written
to the copy by the benchmark's own record writer (``plain_wal.record``:
the same bytes the predecessor journals later), the torn bytes follow,
and recoveries of copies of that go on until one meets no new program
(``rehearsal_laps_min`` at least).  A log that differed from the
window's by ``F`` could meet an ``apply_plan2`` lane width no rehearsal
met.

**Compared**, each a count with the limit 0, printed, a failure also in
``cell.refused``; for every successor of the window: its state vectors
of all rooms against the predecessor's at its death and against the
plain reference's (``benchmarks/plain_wal.py``: every room of the
crashed copy replayed on a CPU ``Y.Doc``; the survivor's host text
too); ``last_recovery`` as the configuration's third guarantee states
it.  For every successor dropped, the rehearsal's too: device bytes it
still holds after its collection (``memory_stats`` ``bytes_in_use``
against the level before the first recovery;
``held_after_drop_max_bytes``).  Once a run: acknowledged
updates not whole in the crashed copy (the plain reader's count); the
predecessor's state vectors at its death against its typists'.

Into ``cell.counts``, ``cell.phase_s`` and ``cell.flushes``, summed
over the window's recoveries from each successor's
``last_flush_metrics`` (the recovery's own flush and the keystroke's),
what the shared readers read; from ``last_recovery``, where the program
keeps them, ``recover_bytes_read`` and ``recoveries``.

Parameters (``benchmarks/traffic/<name>.json``): ``solo_rooms``,
``duet_rooms``, ``hot_traces``, ``hot_storm_rooms``, ``typing_run``,
``erasing_run``, ``jump_every_runs``, ``tail_max``, ``tail_step``,
``tail_offset``, ``updates_a_flush``, ``final_units``,
``others_compared``, ``held_after_drop_max_bytes``,
``rehearsal_laps_min``/``_max``, ``trace_units``.
"""

from __future__ import annotations

import gc
import random
import shutil
from collections import Counter
from pathlib import Path

from benchmarks import plain_wal
from benchmarks.deployment import BenchError, pick_rooms
from benchmarks.oracle import items_of
from benchmarks.plain_client import PlainText, Typist, type_together
from yjs_tpu.ops import plan_cache

_LETTERS = "etaoinshrdlucmfwypvbgkqjxz"
KEYSTROKE_CLIENT = 999_999  # below every typist's
# of a flush's metrics, into cell.counts under the harness's names
COUNTS = {
    "plan_cache_hits": "plan_cache_hits",
    "plan_cache_misses": "plan_cache_misses",
    "n_sched_entries": "link_writes",
}
# last_recovery as a torn tail and nothing else leaves it
SOUND = {"dead_lettered": 0, "overflowed": 0, "corrupt_records": 0,
         "torn_truncations": 1}


def _char(rng: random.Random) -> str:
    return " " if rng.random() < 0.18 else rng.choice(_LETTERS)


def tail_length(p: dict, index: int) -> int:
    """Keystrokes the ``index``-th hot room is sent before the crash."""
    return (int(p["tail_step"]) * index + int(p["tail_offset"])) % (
        int(p["tail_max"]) + 1
    )


class Successor:
    """One recovered provider and what was read off it."""

    def __init__(self, prov, wal_dir: Path, in_window: bool):
        self.prov, self.wal_dir, self.in_window = prov, wal_dir, in_window
        self.recovery = dict(prov.last_recovery)
        self.svs: dict[str, dict | None] = {}
        self.held_bytes: int | None = None


class Generator:
    def __init__(self, params: dict, cell):
        self.p = p = params
        self.cell = cell
        self.rng = rng = random.Random(f"recover:{cell.seed}")
        n_solo, n_duet = int(p["solo_rooms"]), int(p["duet_rooms"])
        n_storm = int(p["hot_storm_rooms"])
        picked = pick_rooms(
            cell.plan, cell.cfg, "distinct", n_solo + n_duet - n_storm, rng,
            n_traces=int(p["hot_traces"]),
        ) + pick_rooms(cell.plan, cell.cfg, "storm", n_storm, rng)
        # (room, typists): the same documents are duet rooms in every seed
        self.hot_specs = [(r, 2) for r in picked[:n_duet]] + [
            (r, 1) for r in picked[n_duet:]
        ]
        hot = {r.guid for r, _n in self.hot_specs}
        cold = [
            r for r in cell.plan if r.kind == "distinct" and r.guid not in hot
        ]
        if not cold:
            raise BenchError("recover: every typical room is hot")
        self.keystroke_room = cold[0]
        self.pred = cell.prov
        self.pred_dir = Path(cell.wal_dir)
        self.run_dir = self.pred_dir.parent
        self.copies = 0
        self.current: Successor | None = None
        self.window: list[Successor] = []
        self.dropped: list[Successor] = []  # the rehearsal's too
        self.baseline_bytes: int | None = None
        self.at_death: dict[str, dict] = {}
        self.pred_differs = 0
        self.crashed: Path | None = None

    # -- set-up: the typists, the tail ---------------------------------------

    def _type_tail(self, index: int, text: PlainText, typists: int) -> list:
        """Hot room ``index``'s tail, a visit at a time (a duet's visit is
        two updates typed at once from one state)."""
        p, rng = self.p, self.rng
        length = tail_length(p, index)
        first = Typist(text, 1_000_000 + 2 * index)
        if typists == 2:
            second = Typist(text, 1_000_001 + 2 * index)
            second.jump(rng.randint(0, text.live()))
            visits = [
                type_together(first, _char(rng), second, _char(rng))
                for _ in range(length // 2)
            ]
            if length % 2:
                visits.append([first.type(_char(rng))])
            return visits
        run_t, run_e = int(p["typing_run"]), int(p["erasing_run"])
        visits = []
        for k in range(length):
            at, run = (k + index) % (run_t + run_e), (k + index) // (run_t + run_e)
            update = first.erase() if at >= run_t else None
            if update is None:  # a typing visit, or nothing left to erase
                if at == 0 and run % int(p["jump_every_runs"]) == 0:
                    first.jump(rng.randint(0, text.live()))
                update = first.type(_char(rng))
            visits.append([update])
        return visits

    def prepare(self) -> None:
        import yjs_tpu as Y

        cell, p, rng = self.cell, self.p, self.rng
        t = cell.clock()
        bases: dict[tuple[str, int], PlainText] = {}
        self.texts: dict[str, PlainText] = {}
        tails = []
        for index, (room, typists) in enumerate(self.hot_specs):
            key = (room.kind, room.trace)
            if key not in bases:
                bases[key] = PlainText.of_items(
                    items_of(cell.oracle.state(room, [room.base]).doc)
                )
            text = self.texts[room.guid] = bases[key].copy()
            tails.append((room.guid, self._type_tail(index, text, typists)))
        # arrival: the k-th visit of every room that has one, room after
        # room in an order the seed draws anew each round
        flat: list[tuple[str, bytes]] = []
        k = 0
        while tails:
            tails = [(g, v) for g, v in tails if len(v) > k]
            rng.shuffle(tails)
            flat += [(g, u) for g, v in tails for u in v[k]]
            k += 1
        n, held = int(p["updates_a_flush"]), int(p["final_units"])
        if len(flat) < n * (held + 1):
            raise BenchError(f"recover: a tail of {len(flat)} updates in all")
        cut = len(flat) - n * held
        sent, last = flat[:cut], flat[cut:]
        self.final = [last[i : i + n] for i in range(0, len(last), n)]
        units = [sent[i : i + n] for i in range(0, cut, n)]
        t_typed = cell.clock()
        for unit in units:
            with cell.unit():
                cell.send_all(unit)
                cell.flush()
        cell.fence()
        t_sent = cell.clock()
        # the first keystroke after a recovery: one character at the end
        # of a typical room that took no tail
        room = self.keystroke_room
        doc = Y.Doc(gc=False)
        doc.client_id = KEYSTROKE_CLIENT
        Y.apply_update(doc, room.base)
        typed: list[bytes] = []
        doc.on("update", lambda update, _origin, _doc: typed.append(update))
        text = doc.get_text("text")
        text.insert(len(text), "x")
        self.keystroke = (room.guid, typed[0])
        # what the room was acknowledged before any successor's keystroke
        self.history_len = len(cell.history[room.guid])
        torn = plain_wal.record(plain_wal.KIND_UPDATE, *self.keystroke)
        self.torn = torn[: len(torn) // 2]
        cell.log(
            f"recover: {len(self.hot_specs)} hot rooms over {len(bases)} "
            f"documents, a tail of {len(flat)} keystrokes (the longest "
            f"{max(tail_length(p, i) for i in range(len(self.hot_specs)))}) "
            f"typed in {t_typed - t:.3f} s; {cut} of them sent in "
            f"{len(units)} flushes in {t_sent - t_typed:.3f} s "
            f"({cut / (t_sent - t_typed):.1f} updates/s), {len(flat) - cut} "
            f"held back for the window's first step"
        )

    # -- a crash, a recovery --------------------------------------------------

    def _crash(self, name: str, journal: list = ()) -> Path:
        """The predecessor's directory as a kill leaves it: copied,
        ``journal`` written behind it record for record (the rehearsal's
        stand-in for what the predecessor journals before the window's
        crash), and the write the kill cut short."""
        crashed = self.run_dir / name
        shutil.copytree(self.pred_dir, crashed)
        last = sorted(crashed.glob("wal-*.log"))[-1]
        with open(last, "ab") as f:
            for guid, update in journal:
                f.write(plain_wal.record(plain_wal.KIND_UPDATE, guid, update))
            f.write(self.torn)
        return crashed

    def _bytes_in_use(self) -> int | None:
        import jax

        stats = jax.devices()[0].memory_stats() or {}
        return stats.get("bytes_in_use")

    def _read_off(self, s: Successor) -> None:
        prov = s.prov
        s.svs = {
            r.guid: prov.state_vector(r.guid) if prov.has_doc(r.guid) else None
            for r in self.cell.plan
        }

    def _drop(self) -> None:
        """Untimed, between recoveries: the successor that stands is read
        off, dropped and collected; the plan cache is a new process's."""
        s, self.current = self.current, None
        if s is not None:
            if s.in_window:
                self._read_off(s)
            self.cell.prov = self.pred
            s.prov = None
            self.dropped.append(s)
            shutil.rmtree(s.wal_dir, ignore_errors=True)
        plan_cache.reset_cache()
        gc.collect()
        in_use = self._bytes_in_use()
        if self.baseline_bytes is None:
            self.baseline_bytes = in_use
        elif s is not None and in_use is not None:
            s.held_bytes = in_use - self.baseline_bytes

    def _next_copy(self, crashed: Path) -> None:
        self._drop()
        self.copies += 1
        self.copy = self.run_dir / f"recovered-{self.copies}"
        shutil.copytree(crashed, self.copy)

    def _count_flush(self, eng, compacted_before) -> None:
        cell = self.cell
        m = eng.last_flush_metrics
        cell.flushes += 1
        for k in cell.phase_s:
            cell.phase_s[k] += m.get(k, 0.0)
        for key, name in COUNTS.items():
            cell.counts[name] += m.get(key, 0)
        if eng.last_compaction is not compacted_before:
            cell.counts["rows_compacted"] += len(eng.last_compaction)

    def timed(self, i: int) -> None:
        from yjs_tpu.provider import TpuProvider

        cell = self.cell
        guid, update = self.keystroke
        with cell.unit():
            prov = TpuProvider.recover(
                str(self.copy), n_docs=int(cell.cfg["slots"]), backend="device"
            )
            prov.on_update(cell.heard)
            cell.prov = prov
            cell.fence()
            eng = prov.engine
            if cell.in_window:
                self._count_flush(eng, None)
            # every successor is sent the keystroke; a room's history
            # holds it once
            del cell.history[guid][self.history_len :]
            compacted = eng.last_compaction
            # no news: what a flush owes its room's peers is held to the
            # predecessor's flushes, through cell.flush
            cell.send_all([self.keystroke], news=False)
            prov.flush()
            if cell.in_window:
                self._count_flush(eng, compacted)
            cell.fence()
        self.current = Successor(prov, self.copy, cell.in_window)
        if cell.in_window:
            self.window.append(self.current)
            r = self.current.recovery
            cell.counts["recoveries"] = cell.counts.get("recoveries", 0) + 1
            if "bytes_read" in r:
                cell.counts["recover_bytes_read"] = (
                    cell.counts.get("recover_bytes_read", 0) + r["bytes_read"]
                )

    def rehearse(self) -> None:
        cell, p = self.cell, self.p
        rehearsed = self._crash(
            "crashed-rehearsal", [u for unit in self.final for u in unit]
        )
        for lap in range(int(p["rehearsal_laps_max"])):
            before = cell.compiles.programs
            t = cell.clock()
            self._next_copy(rehearsed)
            t_copy = cell.clock() - t
            self.timed(lap)
            met = cell.compiles.programs - before
            r = self.current.recovery
            cell.log(
                f"rehearsal recovery {lap}: {met} programs first met, drop "
                f"and copy {t_copy:.3f} s, recovery and keystroke "
                f"{cell.clock() - t - t_copy:.3f} s; records "
                f"{r['records_applied']}, torn {r['torn_truncations']}"
            )
            if met == 0 and lap + 1 >= int(p["rehearsal_laps_min"]):
                break
        self._drop()
        shutil.rmtree(rehearsed, ignore_errors=True)
        # the typists and the predecessor stay: the collection between
        # recoveries walks a recovery's own garbage
        gc.collect()
        gc.freeze()

    def _die(self) -> None:
        """The predecessor's last flushes, under the fault controls, and
        its death."""
        cell = self.cell
        cell.in_window = False  # the window's units and counts are its recoveries'
        for unit in self.final:
            cell.send_all(unit)
            cell.flush()
        cell.fence()
        cell.in_window = True
        pred = self.pred
        self.at_death = {r.guid: pred.state_vector(r.guid) for r in cell.plan}
        self.pred_differs = sum(
            self.at_death[guid] != text.sv for guid, text in self.texts.items()
        )
        self.crashed = self._crash("crashed")

    def untimed(self, i: int) -> None:
        if self.crashed is None:
            self._die()
        self._next_copy(self.crashed)

    # -- after the window ------------------------------------------------------

    def _expected(self, svs: dict[str, dict]) -> dict[str, dict]:
        """``svs`` with the first keystroke, which every successor took."""
        guid = self.keystroke[0]
        return {**svs, guid: {**svs[guid], KEYSTROKE_CLIENT: 1}}

    def finish(self) -> None:
        cell = self.cell
        gc.unfreeze()
        t = cell.clock()
        survivor = self.current
        self._read_off(survivor)
        keystroke_guid = self.keystroke[0]
        # the plain reference: the crashed copy, a room at a time
        log = plain_wal.read_crashed(self.crashed)
        replayed: dict[tuple, tuple[dict, str]] = {}
        plain: dict[str, tuple[dict, str]] = {}
        for guid, payloads in log.rooms.items():
            key = tuple(payloads)
            if key not in replayed:
                replayed[key] = plain_wal.replay(payloads)
            plain[guid] = replayed[key]
        t_plain = cell.clock() - t
        self.elements = sum(sum(sv.values()) for sv, _text in plain.values())
        n = {}
        n["acknowledged_not_whole_in_crashed_copy"] = sum(
            sum((Counter(
                h[: self.history_len] if guid == keystroke_guid else h
            ) - Counter(log.rooms.get(guid, []))).values())
            for guid, h in cell.history.items()
        )
        n["predecessor_state_vector_differs_from_typists"] = self.pred_differs
        want_pred = self._expected(self.at_death)
        rooms = [r.guid for r in cell.plan]
        want_plain = self._expected({
            guid: plain[guid][0] if guid in plain else {} for guid in rooms
        })
        n["successor_state_vector_differs_from_predecessor"] = sum(
            s.svs[guid] != want_pred[guid] for s in self.window for guid in rooms
        )
        n["successor_state_vector_differs_from_plain_reference"] = sum(
            s.svs[guid] != want_plain[guid] for s in self.window for guid in rooms
        )
        prov = survivor.prov
        prov.engine.export_from_device = False
        n["survivor_text_differs_from_plain_reference"] = sum(
            prov.text(guid) != plain[guid][1] + "x" * (guid == keystroke_guid)
            for guid in rooms if guid in plain and prov.has_doc(guid)
        )
        sound = {**SOUND, "records_applied": log.records}
        n["recoveries_not_as_guaranteed"] = sum(
            any(s.recovery.get(k) != v for k, v in sound.items())
            for s in self.window
        )
        limit = int(self.p["held_after_drop_max_bytes"])
        held = [s.held_bytes for s in self.dropped if s.held_bytes is not None]
        n["dropped_successors_still_on_the_device"] = sum(
            b > limit for b in held
        )
        for name, value in n.items():
            cell.log(
                f"check {name}: {value} (limit 0) "
                f"{'ok' if value == 0 else 'FAILED'}"
            )
            cell.refused += [f"recover:{name}"] * min(int(value), 64)
        # the survivor is the provider the oracle holds
        cell.prov, cell.wal_dir = prov, survivor.wal_dir
        rng = random.Random(f"recover-others:{cell.seed}")
        quiet = [
            r.guid for r in cell.plan
            if r.kind in ("distinct", "storm") and r.guid not in cell.touched
        ]
        cell.touched.update(
            r.guid for r in cell.plan if r.kind in ("b4", "prepend")
        )
        cell.touched.update(
            rng.sample(quiet, min(int(self.p["others_compared"]), len(quiet)))
        )
        r = survivor.recovery
        cell.log(
            f"recover: {len(self.window)} recoveries in the window, each of "
            f"{log.records} records whole in {log.files} files of "
            f"{log.bytes} bytes (torn at byte {log.torn_at[1]} of "
            f"{log.torn_at[0].name}), {len(plain)} rooms, {self.elements} "
            f"elements; the plain reference replayed {len(replayed)} "
            f"distinct logs in {t_plain:.3f} s; all of this in "
            f"{cell.clock() - t:.3f} s"
        )
        cell.log(
            "the survivor's last_recovery: "
            + " ".join(
                f"{k} {r[k]}" for k in (
                    "records_applied", "torn_truncations", "files",
                    "bytes_read", "records_max_a_room", "t_construct_s",
                    "t_read_s", "t_validate_s", "t_queue_s", "t_flush_s",
                    "duration_s",
                ) if k in r
            )
        )
        cell.log(
            "device bytes in use: before the first recovery "
            f"{self.baseline_bytes}; more than that after each drop, the "
            f"rehearsal's first, {held} (limit {limit})"
        )

    def work(self) -> int:
        return self.elements * len(self.window)

    def views(self) -> dict[str, tuple[dict, str]]:
        """What every hot room's typists hold: state vector and text."""
        return {guid: (t.sv, t.text()) for guid, t in self.texts.items()}
