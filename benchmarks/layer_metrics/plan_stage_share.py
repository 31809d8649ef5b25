"""plan_stage_share (%): `prepare_many`'s marshalling ahead of the native call:
handles, one `ymx_add_bufs_many` that registers every staged buffer, the pins,
the output arrays.  Self time of `ytpu.plan.stage` (inside `ytpu.plan`, once a
native call), as a share of the timed intervals.  Source: program_span."""

from benchmarks.span_sum import spans_share

SPANS = ("ytpu.plan.stage",)


def read(trace, counters):
    return spans_share(trace, SPANS)
