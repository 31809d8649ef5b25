"""flush_tick_share (%): what `TpuProvider.flush` does itself around
`engine.flush` and the tracker's passes, and the cost ledger's `on_flush`.
Self time of `ytpu.provider.flush` + `ytpu.cost.on_flush`, as a share of the
timed intervals. Source: program_span."""

from benchmarks.span_sum import spans_share

SPANS = ("ytpu.provider.flush", "ytpu.cost.on_flush")


def read(trace, counters):
    return spans_share(trace, SPANS)
