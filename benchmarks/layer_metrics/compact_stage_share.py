"""compact_stage_share (%): a compaction's staging: the three `rooms x (cap +
1)` host allocations, their transfer to the device, and the `scatter_rows`
dispatch. Self time of `ytpu.compact.alloc` + `ytpu.compact.put` +
`ytpu.compact.scatter`, as a share of the timed intervals. Source:
program_span."""

from benchmarks.span_sum import spans_share

SPANS = ("ytpu.compact.alloc", "ytpu.compact.put", "ytpu.compact.scatter")


def read(trace, counters):
    return spans_share(trace, SPANS)
