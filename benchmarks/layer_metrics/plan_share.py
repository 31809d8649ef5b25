"""plan_share (%): the host planner (`native/plancore.cpp` through `_plan_chunk_native`), the flushing thread's wait for its pool included.  Self time of the engine's own
`ytpu.plan` span as a share of the timed intervals.  Source:
program_span."""

from benchmarks.trace_reduce import span_share


def read(trace, counters):
    return span_share(trace, "ytpu.plan")
