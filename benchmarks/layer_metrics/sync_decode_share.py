"""sync_decode_share (%): reading a batch of sync step 1: each frame's type,
its state vector decoded, `doc_id`, the dead letter of a frame that is not
one.  Self time of `ytpu.sync.decode` as a share of the timed intervals;
nothing where the program opens no such span.  Source: program_span."""

from benchmarks.span_sum import spans_share

SPANS = ("ytpu.sync.decode",)


def read(trace, counters):
    return spans_share(trace, SPANS)
