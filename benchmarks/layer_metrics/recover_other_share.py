"""recover_other_share (%): `TpuProvider.recover` outside its construction,
its three passes over the files and its closing flush: the directory's
scan, the stats, the tier placements.  Self time of `ytpu.recover` (the
whole call less every span inside it, the flush's own among them) as a
share of the timed intervals; nothing where the program opens no such span.
Source: program_span."""

from benchmarks.span_sum import spans_share

SPANS = ("ytpu.recover",)


def read(trace, counters):
    return spans_share(trace, SPANS)
