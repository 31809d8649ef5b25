"""Traffic generator ``typing``: a closed-loop flood of keystrokes.

A hot set of rooms, each with plain typists of the benchmark's own
(``benchmarks/plain_client.py``) that start from the room's trace:
``solo_rooms`` with one typist and ``duet_rooms`` with two.  An update
is one keystroke, as a y-websocket client sends it: one character typed
at the cursor, or one backspace.  A unit is a fixed number of updates,
then one ``flush()`` whose fan-out reaches the room's peers; a circuit
is ``units_per_circuit`` units; the timed interval is one circuit and
the window is whole circuits, so a run's work does not depend on where
the clock cut it.

Every unit has the same make-up, whatever the seed (``unit`` in the
parameters): ``duets`` duet rooms in which both typists type a
character at once from the same state (two updates), then ``typed``
solo rooms whose typist types a character and ``erased`` solo rooms
whose typist backspaces one.  A solo typist works in runs, as the B4
editing trace has them: ``typing_run`` characters, then ``erasing_run``
backspaces, and so on; before one typing run in ``jump_every_runs`` it
moves the cursor to another place first.  Rooms start at different
points of that cycle and every solo room is visited once a lap (a
seeded shuffle of the hot set), so each lap holds the same number of
typed and erased updates and deals them to its units in equal shares;
the parameters must make those shares whole.  The seed draws the rooms'
order, the characters and the places jumped to.

The hot set is wide on purpose: the planner's time for an update grows
with the rows its room holds (PERF.md, Findings), so a narrow hot set
ages within a run.  Its rooms hold the first ``hot_traces`` distinct
documents and the first ``hot_storm_rooms`` storm documents in every
seed.

Rehearsal runs whole circuits until the provider is where a server long
under this load is, and asks the provider: its SLO snapshot
(``slo_snapshot()``, what an operator reads) counts the completed
updates its long and its short window hold, and every flush pays for
walking them (PERF.md, Findings).  Rehearsal ends after the first
circuit, ``rehearsal_circuits_max`` at most, that met no new program, left the
long window no fuller than the circuit before had and the short one
within ``settled_within`` of it.  The host's clock is not asked: on a
shared host single circuits stray by some per cent, and rules on their
times stopped too early and too late (PERF.md, Findings).  Every
circuit's rate is printed, and ``window_trend`` reports what is left.
After the first circuit a ladder sends a few units of other sizes
(``ladder``, as shares of the unit's own): ``apply_plan2`` is one
program for each bucket of lane widths, a unit's count of link writes
strays over a bucket's edge now and then, and the ladder meets the
neighbouring buckets' programs here and not in the window.

A circuit is typed just before it is sent, outside the timed interval,
so what the typists hold is what the provider was sent: after the
window :meth:`Generator.views` states every hot room's state vector and
text for the comparison, beside the ``Y.Doc`` oracle fed the same
updates.
"""

from __future__ import annotations

import gc
import random

from benchmarks.deployment import BenchError, pick_rooms
from benchmarks.oracle import items_of
from benchmarks.plain_client import PlainText, Typist, type_together

_LETTERS = "etaoinshrdlucmfwypvbgkqjxz"


def _char(rng: random.Random) -> str:
    return " " if rng.random() < 0.18 else rng.choice(_LETTERS)


class HotRoom:
    """One hot room's clients: the text as they hold it, their cursors."""

    def __init__(self, index: int, room, text: PlainText, typists: int, rng):
        self.room, self.text = room, text
        self.typists = [
            Typist(text, 1_000_000 + 2 * index + k) for k in range(typists)
        ]
        for other in self.typists[1:]:  # works somewhere else in the text
            other.jump(rng.randint(0, text.live()))


class Generator:
    def __init__(self, params: dict, cell):
        self.p = p = params
        self.cell = cell
        self.rng = rng = random.Random(f"typing:{cell.seed}")
        n_solo, n_duet = int(p["solo_rooms"]), int(p["duet_rooms"])
        n_storm = int(p["hot_storm_rooms"])
        picked = pick_rooms(
            cell.plan, cell.cfg, "distinct", n_solo + n_duet - n_storm, rng,
            n_traces=int(p["hot_traces"]),
        ) + pick_rooms(cell.plan, cell.cfg, "storm", n_storm, rng)
        # the same documents are duet rooms in every seed
        self.duet_specs, self.solo_specs = picked[:n_duet], picked[n_duet:]
        u = p["unit"]
        self.duets = int(u["duets"])
        self.typed, self.erased = int(u["typed"]), int(u["erased"])
        self.units = int(p["units_per_circuit"])
        self.updates_a_unit = 2 * self.duets + self.typed + self.erased
        self.run_t, self.run_e = int(p["typing_run"]), int(p["erasing_run"])
        self.jump_every = int(p["jump_every_runs"])
        period = self.run_t + self.run_e
        lap_units = n_solo // (self.typed + self.erased)
        if (
            n_solo % period
            or n_solo % (self.typed + self.erased)
            or n_solo // period * self.run_t != lap_units * self.typed
        ):
            raise BenchError(
                f"typing: {n_solo} solo rooms in runs of {self.run_t} typed "
                f"and {self.run_e} erased do not deal units of {self.typed} "
                f"and {self.erased}"
            )
        self.lap = 0
        self.solo_units: list[list[tuple[HotRoom, str]]] = []
        self.duet_lap: list[HotRoom] = []
        self.circuit: list[list[tuple[str, bytes]]] = []
        self.window_rates: list[float] = []
        self.window_work = 0

    # -- typing ------------------------------------------------------------

    def prepare(self) -> None:
        """The typists, each from its room's trace: a trace is replayed
        once (by the oracle, which keeps it for the comparison) and read
        off into a plain text that every room holding it copies."""
        cell = self.cell
        t = cell.clock()
        bases: dict[tuple[str, int], PlainText] = {}

        def hot(i, room, typists):
            key = (room.kind, room.trace)
            if key not in bases:
                bases[key] = PlainText.of_items(
                    items_of(cell.oracle.state(room, [room.base]).doc)
                )
            return HotRoom(i, room, bases[key].copy(), typists, self.rng)

        self.duet = [hot(i, r, 2) for i, r in enumerate(self.duet_specs)]
        self.solo = [
            hot(len(self.duet) + i, r, 1) for i, r in enumerate(self.solo_specs)
        ]
        cell.log(
            f"typing: {len(self.solo)} solo and {len(self.duet)} duet rooms "
            f"over {len(bases)} documents (clients built in "
            f"{cell.clock() - t:.3f} s), {self.updates_a_unit} updates a "
            f"unit, {self.units} units a circuit"
        )

    def _deal_lap(self) -> None:
        """One visit to every solo room, dealt to units of the fixed
        make-up: which rooms type and which erase follows from the lap."""
        period, rng = self.run_t + self.run_e, self.rng
        typing, erasing = [], []
        for k, room in enumerate(self.solo):
            at = (k + self.lap) % period
            if at >= self.run_t:
                erasing.append((room, "erased"))
            elif at == 0 and ((k + self.lap) // period) % self.jump_every == 0:
                typing.append((room, "jumped"))
            else:
                typing.append((room, "typed"))
        self.lap += 1
        rng.shuffle(typing)
        rng.shuffle(erasing)
        for u in range(len(self.solo) // (self.typed + self.erased)):
            unit = (
                typing[u * self.typed : (u + 1) * self.typed]
                + erasing[u * self.erased : (u + 1) * self.erased]
            )
            rng.shuffle(unit)
            self.solo_units.append(unit)

    def _type_unit(self) -> list[tuple[str, bytes]]:
        rng, out = self.rng, []
        for _ in range(self.duets):
            if not self.duet_lap:
                self.duet_lap = list(self.duet)
                rng.shuffle(self.duet_lap)
            room = self.duet_lap.pop()
            a, b = room.typists
            out += [
                (room.room.guid, u)
                for u in type_together(a, _char(rng), b, _char(rng))
            ]
        if not self.solo_units:
            self._deal_lap()
        for room, kind in self.solo_units.pop():
            typist = room.typists[0]
            update = typist.erase() if kind == "erased" else None
            if update is None:  # nothing before the cursor: type instead
                if kind == "jumped":
                    typist.jump(rng.randint(0, room.text.live()))
                update = typist.type(_char(rng))
            out.append((room.room.guid, update))
        return out

    def _type_units(self, n: int) -> list[list[tuple[str, bytes]]]:
        return [self._type_unit() for _ in range(n)]

    # -- rehearsal ---------------------------------------------------------

    def _held(self) -> tuple[int, int] | None:
        """Completed updates in the provider's long and short SLO
        windows, or None where it keeps none."""
        try:
            w = self.cell.prov.slo_snapshot()["windows"]
            return int(w["long"]["total"]), int(w["short"]["total"])
        except (AttributeError, KeyError, TypeError):
            return None

    def rehearse(self) -> None:
        cell, p = self.cell, self.p
        within = float(p["settled_within"])
        held, settled = self._held(), False
        for lap in range(int(p["rehearsal_circuits_max"])):
            self.untimed(lap)
            before = cell.compiles.programs
            t = cell.clock()
            work = self.timed(lap, rehearsal=True)
            cell.fence()
            seconds = cell.clock() - t
            met = cell.compiles.programs - before
            was, held = held, self._held()
            settled = held is None or (
                held[0] <= was[0] and abs(held[1] - was[1]) <= within * was[1]
            )
            cell.log(
                f"rehearsal circuit {lap}: {met} programs first met, "
                f"{seconds:.3f} s, {work / seconds:.1f} updates/s, the "
                f"provider's SLO windows hold {held}"
            )
            if lap == 0:
                self._ladder()
            if settled and met == 0:
                break
        if not settled:
            cell.log(
                "rehearsal: the provider's SLO windows were still filling "
                "after the last circuit: the window begins all the same, and "
                "its circuits' rates will show it"
            )
        # the typists are the benchmark's own: keep the collector from
        # walking them inside the window
        gc.collect()
        gc.freeze()

    def _ladder(self) -> None:
        cell = self.cell
        sizes = [
            max(1, round(share * self.updates_a_unit))
            for share in self.p["ladder"]
        ]
        before = cell.compiles.programs
        t = cell.clock()
        flat = [
            u
            for unit in self._type_units(-(-sum(sizes) // self.updates_a_unit))
            for u in unit
        ]
        steps = 0
        while flat:  # everything typed is sent: later keystrokes build on it
            size = sizes[steps % len(sizes)]
            step, flat = flat[:size], flat[size:]
            with cell.unit():
                cell.send_all(step)
                cell.flush()
            steps += 1
        cell.fence()
        cell.log(
            f"ladder of {steps} units of {sizes} updates: "
            f"{cell.compiles.programs - before} programs first met, "
            f"{cell.clock() - t:.3f} s"
        )

    # -- sending -----------------------------------------------------------

    def untimed(self, i: int) -> None:
        """The next circuit, typed."""
        self.circuit = self._type_units(self.units)

    def timed(self, i: int, rehearsal: bool = False) -> int:
        """One circuit.  Returns its updates (the rehearsal's use)."""
        cell = self.cell
        t = cell.clock()
        work = 0
        for unit in self.circuit:
            with cell.unit():
                cell.send_all(unit)
                cell.flush()
            work += len(unit)
        self.circuit = []
        if not rehearsal:
            cell.fence()
            self.window_rates.append(work / (cell.clock() - t))
            self.window_work += work
        return work

    def finish(self) -> None:
        gc.unfreeze()
        self.cell.log(
            "circuit rates in the window, updates/s: "
            + " ".join(f"{r:.1f}" for r in self.window_rates)
        )

    def work(self) -> int:
        return self.window_work

    def views(self) -> dict[str, tuple[dict, str]]:
        """What every hot room's clients hold: state vector and text."""
        return {
            room.room.guid: (room.text.sv, room.text.text())
            for room in self.duet + self.solo
        }
