"""Device programs read as milliseconds a timed unit.

``trace_reduce`` gives each jitted program's device seconds inside the
timed intervals, averaged over the chips used; a cell whose unit is one
load reads a kernel's cost as that over the units timed.
"""


def programs_ms_a_unit(trace: dict, counters: dict, kernels) -> float | None:
    """Device time (ms) of the programs whose name holds one of
    ``kernels``, over the units timed; nothing where no such program
    ran or no unit was timed."""
    found = [
        rec["seconds"] for name, rec in trace["programs"].items()
        if any(k in name for k in kernels)
    ]
    if not found or not counters.get("units"):
        return None
    return 1e3 * sum(found) / counters["units"]
