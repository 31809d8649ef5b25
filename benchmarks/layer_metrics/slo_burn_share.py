"""slo_burn_share (%): the tracker's burn-rate pass alone
(`ConvergenceTracker._update_state`: a copy of its ring of completions and a
walk of both windows, at every flush). Self time of `ytpu.slo.burn`, as a
share of the timed intervals. Source: program_span."""

from benchmarks.span_sum import spans_share

SPANS = ("ytpu.slo.burn",)


def read(trace, counters):
    return spans_share(trace, SPANS)
