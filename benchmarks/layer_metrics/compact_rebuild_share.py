"""compact_rebuild_share (%): the per-room `rebuild_compacted_self` and the
copy of its rows into the staging arrays. Self time of
`ytpu.compact.rebuild`, as a share of the timed intervals. Source:
program_span."""

from benchmarks.span_sum import spans_share

SPANS = ("ytpu.compact.rebuild",)


def read(trace, counters):
    return spans_share(trace, SPANS)
