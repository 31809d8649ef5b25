"""pack_share (%): packing the planned lanes into the staging buffers.  Self time of the engine's own
`ytpu.pack` span as a share of the timed intervals.  Source:
program_span."""

from benchmarks.trace_reduce import span_share


def read(trace, counters):
    return span_share(trace, "ytpu.pack")
