"""wal_share (%): the journal: every `WriteAheadLog.append` (encode, write,
flush, an fsync every 64), which in these cells is the one `receive_update`
makes. Self time of `ytpu.wal.append`, as a share of the timed intervals.
Source: program_span."""

from benchmarks.span_sum import spans_share

SPANS = ("ytpu.wal.append",)


def read(trace, counters):
    return spans_share(trace, SPANS)
