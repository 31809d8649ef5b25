"""plan_native_share (%): the one `ymx_prepare_many` call (C++, pooled) inside
`ytpu.plan`; what is left of `plan_share` is the Python around it. Self time
of `ytpu.plan.native`, as a share of the timed intervals. Source:
program_span."""

from benchmarks.span_sum import spans_share

SPANS = ("ytpu.plan.native",)


def read(trace, counters):
    return spans_share(trace, SPANS)
