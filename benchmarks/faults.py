"""Fault controls: this system's "lower precision".

The provider states no numeric precision; its configuration states
guarantees.  Each control breaks one of them underneath the timed path,
once, for one seeded update of the window, and a run with it installed
must come out ``correct: false``:

- ``drop_update``  an update is acknowledged and never journaled or
                   integrated
- ``drop_in_engine``  an update is acknowledged and journaled, and the
                   engine never integrates it: only a room's state can
                   show it
- ``skip_wal``     an update is acknowledged, integrated and broadcast,
                   and never journaled
- ``withhold``     an update is acknowledged, journaled and integrated,
                   and one broadcast that carries a room's edits is
                   never delivered to the room's peers

``benchmarks/run.py --fault <name>`` runs them on the chip at a cell's own
size;
``tests/bench/test_controls.py`` keeps them as tests at a small one.
"""

from __future__ import annotations

import random

FAULTS = ("drop_update", "drop_in_engine", "skip_wal", "withhold")


def install(fault: str, prov, seed: int):
    """Wrap one seam of ``prov``.  Returns ``arm()``: the fault strikes
    the n-th call of that seam after it is armed (n from the seed)."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}: one of {FAULTS}")
    state = {"left": None}
    nth = random.Random(f"fault:{seed}").randint(1, 40)

    def strikes() -> bool:
        if state["left"] is None:
            return False
        state["left"] -= 1
        return state["left"] == 0

    if fault == "drop_update":
        receive = prov.receive_update

        def receive_update(guid, update, *a, **kw):
            if strikes():
                return True  # acknowledged, and lost
            return receive(guid, update, *a, **kw)

        prov.receive_update = receive_update
    elif fault == "drop_in_engine":
        queue = prov.engine.queue_update

        def queue_update(doc, update, *a, **kw):
            if strikes():
                return True  # accepted, and lost
            return queue(doc, update, *a, **kw)

        prov.engine.queue_update = queue_update
    elif fault == "skip_wal":
        append = prov.wal.append

        def wal_append(kind, guid, payload, v2=False):
            if kind == 1 and strikes():  # KIND_UPDATE
                return (None, 0, 0)
            return append(kind, guid, payload, v2=v2)

        prov.wal.append = wal_append
    else:
        on_update = prov.on_update

        def withholding(callback):
            def deliver(guid, update):
                if not strikes():
                    callback(guid, update)

            on_update(deliver)

        prov.on_update = withholding

    def arm() -> None:
        state["left"] = nth

    return arm
