"""recover_read_share (%): the log read: each file's bytes from the page
cache, every record's header, CRC and copy-out (`persistence/recovery.py`
`iter_file_events` -> `records.try_decode_at`), once a file.  Self time of
`ytpu.recover.read` as a share of the timed intervals; nothing where the
program opens no such span.  Source: program_span."""

from benchmarks.span_sum import spans_share

SPANS = ("ytpu.recover.read",)


def read(trace, counters):
    return spans_share(trace, SPANS)
