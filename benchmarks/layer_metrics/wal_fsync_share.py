"""wal_fsync_share (%): the journal's durability: the `os.fsync` the policy
asks for (one append in 64 by default) and nothing else.  Self time of
`ytpu.wal.fsync` (inside the `ytpu.wal.append` that paid it), as a share of the
timed intervals.  Source: program_span."""

from benchmarks.span_sum import spans_share

SPANS = ("ytpu.wal.fsync",)


def read(trace, counters):
    return spans_share(trace, SPANS)
