"""slo_share (%): everything the convergence tracker (`obs/slo.py`) costs: the
stamp at ingest (`update_key`, origin clock, flow start), the visibility
pass after a flush, and the burn-rate pass. Self time of `ytpu.slo.receive`
+ `ytpu.slo.visible` + `ytpu.slo.burn`, as a share of the timed intervals.
Source: program_span."""

from benchmarks.span_sum import spans_share

SPANS = ("ytpu.slo.receive", "ytpu.slo.visible", "ytpu.slo.burn")


def read(trace, counters):
    return spans_share(trace, SPANS)
