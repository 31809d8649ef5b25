"""dispatch_share (%): dispatch: host-to-device transfer of the lanes, the launch of `apply_plan2` and the fence's token.  Self time of the engine's own
`ytpu.dispatch` span as a share of the timed intervals.  Source:
program_span."""

from benchmarks.trace_reduce import span_share


def read(trace, counters):
    return span_share(trace, "ytpu.dispatch")
