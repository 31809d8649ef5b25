"""Traffic generator ``reload``: rooms coming back after a restart.

With every room of the deployment resident, one group of rooms is made
new to the process, as a restart makes them: each is released
(``release_doc``) and the planner's process-wide plan cache is emptied
(``plan_cache.reset_cache()``; it would otherwise serve the second load
of the same documents from its snapshots, which no restart can).  That
is the benchmark making room and is not timed.  Then the group is
cold-loaded through ``receive_update`` + one ``flush()``, and the first
keystroke after it arrives: one more update to one of the rooms and a
second ``flush()``, which is where the engine compacts the rooms it has
just loaded (``_maybe_compact`` runs at the head of the next flush).  A
unit, which is also the timed interval, is both flushes to their fence
on the device.  Its work is the group's elements: the sum of the rooms'
state-vector clocks, every character ever typed there, deleted or not.

The group is the same multiset of traces in every seed (the first
``group_rooms - group_storm_rooms`` distinct traces and the first
``group_storm_rooms`` storm traces, one room each, dealt evenly over
the chips' blocks of slots); the seed decides which rooms hold them and
the order in which they arrive.

Parameters (``benchmarks/traffic/<name>.json``): ``group_rooms``,
``group_storm_rooms``, ``rehearsal_laps_min``/``_max`` (laps go on until
one meets no new program), ``trace_units``.
"""

from __future__ import annotations

import random

from benchmarks.deployment import pick_rooms
from benchmarks.oracle import ELEMENTS
from yjs_tpu.ops import plan_cache


class Generator:
    def __init__(self, params: dict, cell):
        self.p = params
        self.cell = cell
        rng = random.Random(f"reload:{cell.seed}")
        n_storm = int(params["group_storm_rooms"])
        group = pick_rooms(
            cell.plan, cell.cfg, "distinct",
            int(params["group_rooms"]) - n_storm, rng,
        ) + pick_rooms(cell.plan, cell.cfg, "storm", n_storm, rng)
        self.group = group
        self.rng = rng
        self.loads = 0

    def prepare(self) -> None:
        """The first keystroke after a load: one typist's one character
        at the end of the group's first room."""
        import yjs_tpu as Y

        room = self.group[0]
        doc = Y.Doc(gc=False)
        doc.client_id = 1_000_000
        Y.apply_update(doc, room.base)
        typed: list[bytes] = []
        doc.on("update", lambda update, _origin, _doc: typed.append(update))
        text = doc.get_text("text")
        text.insert(len(text), "x")
        self.keystroke = [(room.guid, typed[0])]
        self.cell.log(f"group of {len(self.group)} rooms")

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

    def untimed(self, i: int) -> None:
        t = self.cell.clock()
        for room in self.group:
            self.cell.release(room.guid)
        plan_cache.reset_cache()
        self.cell.fence()
        self.cell.note("release_ms_a_room",
                       (self.cell.clock() - t) * 1e3 / len(self.group))

    def timed(self, i: int) -> None:
        cell = self.cell
        order = list(self.group)
        self.rng.shuffle(order)
        with cell.unit():
            cell.send_all((room.guid, room.base) for room in order)
            cell.flush()
            cell.send_all(self.keystroke)
            cell.flush()
            cell.fence()
        if cell.in_window:
            self.loads += 1

    def finish(self) -> None:
        pass

    def work(self) -> int:
        """Elements loaded in the window, from the table of elements a
        trace (which the oracle checks against the rooms it compares)."""
        elements = sum(ELEMENTS[room.kind][room.trace] for room in self.group)
        self.cell.log(f"{elements} elements a load, {self.loads} loads")
        return elements * self.loads
