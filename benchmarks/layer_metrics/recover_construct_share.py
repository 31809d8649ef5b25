"""recover_construct_share (%): the new process's provider made: all of
`TpuProvider.__init__` inside `TpuProvider.recover` (the engine and its
mirrors, the admission, SLO and cost holders, the WAL's opening, which
lists the directory).  Self time of `ytpu.recover.construct` as a share of
the timed intervals; nothing where the program opens no such span.  Source:
program_span."""

from benchmarks.span_sum import spans_share

SPANS = ("ytpu.recover.construct",)


def read(trace, counters):
    return spans_share(trace, SPANS)
