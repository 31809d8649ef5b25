"""recover_queue_share (%): the per-record Python loop that hands the log
to the engine in its own order: `doc_id`, `engine.queue_update`, the replay
counters, releases and the other record kinds.  Self time of
`ytpu.recover.queue` (once a file) as a share of the timed intervals;
nothing where the program opens no such span.  Source: program_span."""

from benchmarks.span_sum import spans_share

SPANS = ("ytpu.recover.queue",)


def read(trace, counters):
    return spans_share(trace, SPANS)
