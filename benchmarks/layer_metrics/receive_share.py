"""receive_share (%): provider ingest timed inside the program: the whole of
`TpuProvider.receive_update` (trace context in use, SLO stamp, WAL append,
engine queue, cost ledger), the benchmark's own loop around it left out.
Self time of `ytpu.provider.receive_update` + `ytpu.wal.append` +
`ytpu.slo.receive`, as a share of the timed intervals. Source: program_span."""

from benchmarks.span_sum import spans_share

SPANS = (
    "ytpu.provider.receive_update", "ytpu.wal.append", "ytpu.slo.receive",
)


def read(trace, counters):
    return spans_share(trace, SPANS)
