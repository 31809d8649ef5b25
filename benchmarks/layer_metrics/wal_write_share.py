"""wal_write_share (%): the journal's record reaching the operating system:
the file's `write` and `flush` of every `WriteAheadLog.append`, one a record,
no fsync.  Self time of `ytpu.wal.write` (inside `ytpu.wal.append`), as a share
of the timed intervals.  Source: program_span."""

from benchmarks.span_sum import spans_share

SPANS = ("ytpu.wal.write",)


def read(trace, counters):
    return spans_share(trace, SPANS)
