"""apply_ms_a_load (ms): device time of the bulk apply in the timed
intervals (the trace's `programs`, averaged over the chips used) over
the loads timed (`units`): `apply_plan2` on one chip, on a mesh the
program that wraps `local_apply` under `shard_map` (the same body on a
chip's lanes, and a `psum` of two counters).  Nothing where no such
program ran.  Source: device_trace."""

from benchmarks.program_ms import programs_ms_a_unit

KERNELS = ("apply_plan2", "local_apply")


def read(trace, counters):
    return programs_ms_a_unit(trace, counters, KERNELS)
