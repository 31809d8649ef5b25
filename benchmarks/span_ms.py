"""Host spans read as milliseconds a timed unit.

``span_sum`` reads spans as a share of the timed intervals; a cell whose
unit is one load reads a phase's cost as its self time over the units
timed, which does not move when another phase shortens the load.
"""


def spans_ms_a_unit(trace: dict, counters: dict, names) -> float | None:
    """Self time (ms) of the spans in ``names`` over the units timed;
    nothing where the trace holds none of them or no unit was timed."""
    found = [trace["spans"][n] for n in names if n in trace["spans"]]
    if not found or not counters.get("units"):
        return None
    return 1e3 * sum(found) / counters["units"]
