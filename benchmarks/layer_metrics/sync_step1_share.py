"""sync_step1_share (%): the server's own sync step 1, one a room a tick:
`TpuProvider.sync_step1`, the room's state vector from the host mirror and
its frame.  Self time of `ytpu.sync.step1` as a share of the timed
intervals; nothing where the program opens no such span.  Source:
program_span."""

from benchmarks.span_sum import spans_share

SPANS = ("ytpu.sync.step1",)


def read(trace, counters):
    return spans_share(trace, SPANS)
