"""fold_share (%): the update-log fold at the head of the emit phase (a room
whose engine log passed 64 entries re-encodes its whole state). Self time of
`ytpu.emit.fold`, as a share of the timed intervals. Source: program_span."""

from benchmarks.span_sum import spans_share

SPANS = ("ytpu.emit.fold",)


def read(trace, counters):
    return spans_share(trace, SPANS)
