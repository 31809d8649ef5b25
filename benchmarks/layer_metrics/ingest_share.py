"""ingest_share (%): provider ingest, `TpuProvider.receive_update` with
its WAL append, SLO stamp and queueing.  Self time of the benchmark's
own `bench.ingest` span (around its calls into the provider) as a share
of the timed intervals.  Source: program_span."""

from benchmarks.trace_reduce import span_share


def read(trace, counters):
    return span_share(trace, "bench.ingest")
