"""emit_share (%): emit: encoding each room's incremental update and the `on_update` fan-out.  Self time of the engine's own
`ytpu.emit` span as a share of the timed intervals.  Source:
program_span."""

from benchmarks.trace_reduce import span_share


def read(trace, counters):
    return span_share(trace, "ytpu.emit")
