"""plan_walk_share (%): the head of a flush's plan phase: the walk over the
rooms that took an update, with a `state_vector()` read for each room that
has listeners.  Self time of `ytpu.plan.walk` (inside `ytpu.plan`, once a
flush), as a share of the timed intervals.  Source: program_span."""

from benchmarks.span_sum import spans_share

SPANS = ("ytpu.plan.walk",)


def read(trace, counters):
    return spans_share(trace, SPANS)
