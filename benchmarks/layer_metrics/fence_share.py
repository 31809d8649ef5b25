"""fence_share (%): the host's wait for the device at the end of a timed
interval (`jax.block_until_ready` on the resident tables), under the
benchmark's own `bench.fence` span, as a share of the timed intervals:
the device work the host could not hide behind its own.  Source:
program_span."""

from benchmarks.trace_reduce import span_share


def read(trace, counters):
    return span_share(trace, "bench.fence")
