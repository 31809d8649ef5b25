"""plan_finish_share (%): the loop after the native call: `_finish_prepare` a
room (its error policy, a second digest of the staged bytes for the frontier),
a leader's member clones, `cache.insert_native`.  Self time of
`ytpu.plan.finish` (inside `ytpu.plan`, once a native call), as a share of the
timed intervals.  Source: program_span."""

from benchmarks.span_sum import spans_share

SPANS = ("ytpu.plan.finish",)


def read(trace, counters):
    return spans_share(trace, SPANS)
