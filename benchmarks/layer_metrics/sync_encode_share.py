"""sync_encode_share (%): the diffs of a batch of sync step 1: the whole of
`engine.sync_step2_batch`, on the default path one native encode a request
from its room's host mirror, each into a fresh buffer of the whole room's
bound.  Self time of `ytpu.sync.encode` as a share of the timed intervals;
nothing where the program opens no such span.  Source: program_span."""

from benchmarks.span_sum import spans_share

SPANS = ("ytpu.sync.encode",)


def read(trace, counters):
    return spans_share(trace, SPANS)
