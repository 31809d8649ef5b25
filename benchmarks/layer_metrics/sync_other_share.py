"""sync_other_share (%): `handle_sync_step1_batch` outside its decode and
its encode: framing each answer as step 2, the counters, `last_sync_metrics`.
Self time of `ytpu.sync.step1_batch` (the whole call after its flush, less
`ytpu.sync.decode` and `ytpu.sync.encode` inside it) as a share of the timed
intervals; nothing where the program opens no such span.  Source:
program_span."""

from benchmarks.span_sum import spans_share

SPANS = ("ytpu.sync.step1_batch",)


def read(trace, counters):
    return spans_share(trace, SPANS)
