"""Traffic generator ``longtail``: a restart in which long documents
come back beside short ones.

The deployment's rooms have a heavy tail of sizes (its configuration
states ``long_documents`` and the seeds of the committed fixtures under
``benchmarks/longdocs/``).  A restart group holds ``group_rooms`` of
each kind: the typical and storm rooms as ``reload`` picks them (the
kind's first traces, one room each), and of the long kinds the rooms
the seed draws.  At set-up, untimed, each long room of the group is
given a long document of its own (release, ``receive_update``,
``flush()``; ``cell.history`` holds the bytes really sent): the harness
deals every room of a long kind the same trace, and a load whose long
rooms share one update would measure the planner's clone of a plan, not
the planner.  No room of a timed load is a clone of another, and the
plan cache is emptied between loads: every room is planned cold.

Between loads, untimed: the group is released, the plan cache emptied,
the Python heap collected (what was resident when the rehearsal ended
is frozen, as ``typing`` and ``resync`` freeze theirs), the device
fenced.  Timed, to a fence: the group through ``receive_update`` in an
order the seed shuffles, ``flush()``, the first keystroke, ``flush()``,
which compacts the group (rooms of three widths: the engine stages them
in width classes).  Work is the group's elements: the sum of its
documents' state-vector clocks, from ``base_states.json`` and the
fixtures' ``documents.json``, the same in every seed.

The order of arrival is one order a run, and the group is released in
the reverse of it: the provider re-lets freed slots last in, first out,
so every room comes back to the slot it held, every load of a run packs
the same rooms into the same chunks of 256 slots, and meets the
programs the rehearsal met.  A restart is one load, and compiles its
lane widths once whatever they are; with a new order every load the 16
long rooms fall differently over the chunks each time, every load
brings ``apply_plan2`` lane widths no load before it had, and the
window would time the compiler (10-19 s a program at these lane widths
on the chip), not the restart.  One device issues the exact lane key
(``engine._covering_key`` bounds the key space on a mesh only).

Into ``cell.counts``, summed over the window's flushes, what the
per-layer readers of this cell read from ``last_flush_metrics``:
``rows_staged_bytes``, ``rows_held_bytes``, and where the program keeps
them ``rows_staged_blocks``, ``plan_room_max_s``, ``plan_pool_s``;
``plan_threads_host`` is the native pool's width.

After the window every long room of the group is held to its
document's committed entry in ``documents.json``: state vector, the
host's text, and the text walked out of the device's rows; a room that
differs goes to ``cell.refused`` (limit 0).  ``oracle.check`` replays
each of them on a CPU ``Y.Doc`` besides, as it does every room that
does not hold its dealt trace.

Parameters (``benchmarks/traffic/<name>.json``): ``group_rooms`` (by
kind), ``rehearsal_laps_min``/``_max`` (laps go on until one meets no
new program; at least six, by which each planner thread's heap has held
a long room and a load has stopped growing the process), ``trace_units``.
"""

from __future__ import annotations

import gc
import json
import random
import zlib
from pathlib import Path
from typing import NamedTuple

from benchmarks.deployment import BenchError, pick_rooms
from benchmarks.oracle import ELEMENTS, text_digest
from yjs_tpu.ops import plan_cache

LONGDOCS = Path(__file__).resolve().parent.parent / "longdocs"
LONG_KINDS = ("b4", "prepend")
# of a flush's metrics: summed into cell.counts where the program has them
SUMMED = (
    "rows_staged_bytes", "rows_held_bytes", "rows_staged_blocks",
    "plan_room_max_s", "plan_pool_s",
)


class LongDocument(NamedTuple):
    name: str      # <kind>-<seed>, as the fixture's file
    update: bytes  # the document, one update
    entry: dict    # its committed entry: state vector, text digest


def long_documents(cfg: dict) -> dict[str, list[LongDocument]]:
    """Per long kind the documents a group holds."""
    table = json.loads((LONGDOCS / "documents.json").read_text())["documents"]
    out = {}
    for kind in LONG_KINDS:
        seeds = cfg["long_document_seeds"][kind][: cfg["long_documents"][kind]]
        if len(seeds) != cfg["long_documents"][kind]:
            raise BenchError(f"{cfg['name']}: too few {kind} seeds")
        out[kind] = []
        for seed in seeds:
            name = f"{kind}-{seed}"
            update = zlib.decompress((LONGDOCS / f"{name}.bin.z").read_bytes())
            out[kind].append(LongDocument(name, update, table[name]))
    return out


class Generator:
    def __init__(self, params: dict, cell):
        self.p = params
        self.cell = cell
        rng = random.Random(f"longtail:{cell.seed}")
        rooms = params["group_rooms"]
        self.short = [
            room for kind in ("distinct", "storm")
            for room in pick_rooms(cell.plan, cell.cfg, kind, rooms[kind], rng)
        ]
        documents = long_documents(cell.cfg)
        # guid of a long room of the group -> the document it holds
        self.long: dict[str, LongDocument] = {}
        for kind in LONG_KINDS:
            if rooms[kind] != len(documents[kind]):
                raise BenchError(
                    f"a group of {rooms[kind]} {kind} rooms and "
                    f"{len(documents[kind])} distinct {kind} documents"
                )
            picked = pick_rooms(cell.plan, cell.cfg, kind, rooms[kind], rng)
            for room, doc in zip(picked, documents[kind]):
                self.long[room.guid] = doc
        # one order of arrival a run (see the module's docstring)
        self.homing = [(guid, doc.update) for guid, doc in self.long.items()]
        self.load = [(room.guid, room.base) for room in self.short] + self.homing
        rng.shuffle(self.load)
        self.elements = sum(
            ELEMENTS[room.kind][room.trace] for room in self.short
        ) + sum(
            n for doc in self.long.values()
            for _client, n in doc.entry["state_vector"]
        )
        self.loads = 0
        self.per_flush: dict[str, list] = {}

    def prepare(self) -> None:
        """The first keystroke after a load (one typist's one character
        at the end of the group's first room), and each long room of the
        group given its own document."""
        import yjs_tpu as Y
        from yjs_tpu import native

        cell = self.cell
        room = self.short[0]
        doc = Y.Doc(gc=False)
        doc.client_id = 1_000_000
        Y.apply_update(doc, room.base)
        typed: list[bytes] = []
        doc.on("update", lambda update, _origin, _doc: typed.append(update))
        text = doc.get_text("text")
        text.insert(len(text), "x")
        self.keystroke = [(room.guid, typed[0])]
        t = cell.clock()
        for guid, _update in reversed(self.homing):
            cell.release(guid)
        cell.send_all(self.homing)
        cell.flush()
        cell.fence()
        cell.counts["plan_threads_host"] = int(native.load().ymx_plan_threads())
        self.slots = {guid: cell.prov.doc_id(guid) for guid, _u in self.load}
        cell.log(
            f"group of {len(self.load)} rooms, {self.elements} elements; "
            f"{len(self.long)} long documents homed in "
            f"{cell.clock() - t:.3f} s: "
            + " ".join(doc.name for doc in self.long.values())
        )

    def rehearse(self) -> None:
        cell, p = self.cell, self.p
        for lap in range(int(p["rehearsal_laps_max"])):
            before = cell.compiles.programs
            t = cell.clock()
            self.untimed(lap)
            t_release = cell.clock() - t
            self.timed(lap)
            cell.fence()
            met = cell.compiles.programs - before
            cell.log(
                f"rehearsal lap {lap}: {met} programs first met, release "
                f"{t_release:.3f} s, load {cell.clock() - t - t_release:.3f} s"
            )
            if met == 0 and lap + 1 >= int(p["rehearsal_laps_min"]):
                break
        # what is resident now (4096 rooms, the documents) stays: the
        # collection between loads walks a load's own garbage
        gc.collect()
        gc.freeze()

    def untimed(self, i: int) -> None:
        cell = self.cell
        t = cell.clock()
        for guid, _update in reversed(self.load):
            cell.release(guid)
        plan_cache.reset_cache()
        gc.collect()
        cell.fence()
        cell.note("release_ms_a_room", (cell.clock() - t) * 1e3 / len(self.load))

    def _flush(self, which: str) -> None:
        cell = self.cell
        cell.flush()
        if not cell.in_window:
            return
        m = cell.prov.engine.last_flush_metrics
        for key in SUMMED:
            if key in m:
                cell.counts[key] = cell.counts.get(key, 0) + m[key]
        self.per_flush.setdefault(which, []).append((
            m.get("plan_cache_misses", 0), m.get("plan_cache_hits", 0),
            m.get("rows_staged_blocks"),
        ))

    def timed(self, i: int) -> None:
        cell = self.cell
        with cell.unit():
            cell.send_all(self.load)
            self._flush("load")
            cell.send_all(self.keystroke)
            self._flush("keystroke")
            cell.fence()
        if cell.in_window:
            self.loads += 1

    def finish(self) -> None:
        """Every long room of the group against its document's committed
        entry: state vector, host text, text read back from the device."""
        cell, prov = self.cell, self.cell.prov
        gc.unfreeze()
        eng = prov.engine
        t = cell.clock()
        differ = 0
        for guid, doc in self.long.items():
            entry = doc.entry
            sv = {int(c): n for c, n in entry["state_vector"]}
            ok = prov.has_doc(guid) and prov.state_vector(guid) == sv
            for from_device in (False, True):
                eng.export_from_device = from_device
                ok = ok and text_digest(prov.text(guid)) == entry["text_digest"]
            eng.export_from_device = False
            if not ok:
                differ += 1
                cell.refused.append(guid)
        for which, seen in self.per_flush.items():
            cold, served, blocks = (sorted(set(col)) for col in zip(*seen))
            cell.log(
                f"{which} flushes in the window: cold plans {cold}, clones "
                f"and cache hits {served}, rows_staged_blocks {blocks}"
            )
        moved = sum(prov.doc_id(g) != slot for g, slot in self.slots.items())
        cell.log(
            f"longtail: {len(self.long)} long rooms held to documents.json "
            f"(state vector, host text, device text) in "
            f"{cell.clock() - t:.3f} s, {differ} differ; {moved} rooms of "
            f"the group left their slot"
        )

    def work(self) -> int:
        self.cell.log(f"{self.elements} elements a load, {self.loads} loads")
        return self.elements * self.loads
