"""compact_share (%): engine compaction (`_maybe_compact`: mirror rebuilds and the `scatter_rows` dispatch).  Self time of the engine's own
`ytpu.compact` span as a share of the timed intervals.  Source:
program_span."""

from benchmarks.trace_reduce import span_share


def read(trace, counters):
    return span_share(trace, "ytpu.compact")
