"""plan_keys_share (%): the plan cache's probes: a `plan_key` (a digest of the
staged bytes folded into the room's frontier) and a `cache.lookup` for every
room of a chunk.  Self time of `ytpu.plan.keys` (inside `ytpu.plan`, once a
chunk; not opened with the cache off), as a share of the timed intervals.
Source: program_span."""

from benchmarks.span_sum import spans_share

SPANS = ("ytpu.plan.keys",)


def read(trace, counters):
    return spans_share(trace, SPANS)
