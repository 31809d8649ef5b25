"""engine_other_share (%): `engine.flush` outside its five phases: deferred
hydrations, `_finish_flush` (the flush ring, the registry, the device-memory
gauges), `health.tick`, the metrics dict. Self time of `ytpu.flush`, as a
share of the timed intervals. Source: program_span."""

from benchmarks.span_sum import spans_share

SPANS = ("ytpu.flush",)


def read(trace, counters):
    return spans_share(trace, SPANS)
