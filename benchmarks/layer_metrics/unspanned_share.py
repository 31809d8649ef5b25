"""unspanned_share (%): the share of the timed intervals inside a unit
that no span of the engine or of the benchmark covers: what
`TpuProvider.flush` does around `engine.flush` (the SLO tracker's
visibility stamps and burn-rate pass, the cost ledger), the engine's
flush outside its five phases, and the benchmark's own bookkeeping.
Self time of `bench.unit` and `bench.timed`.  Source: program_span."""

from benchmarks.trace_reduce import NOT_A_LAYER


def read(trace, counters):
    inside = [trace["spans"][s] for s in NOT_A_LAYER if s in trace["spans"]]
    if not inside:
        return None
    return 100.0 * sum(inside) / trace["window_s"]
