"""compact_scan_share (%): `_maybe_compact`'s look at every mirror for one that
has doubled, at every flush whether or not one has. Self time of
`ytpu.compact.scan`, as a share of the timed intervals. Source:
program_span."""

from benchmarks.span_sum import spans_share

SPANS = ("ytpu.compact.scan",)


def read(trace, counters):
    return spans_share(trace, SPANS)
