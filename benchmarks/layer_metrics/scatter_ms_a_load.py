"""scatter_ms_a_load (ms): device time of `scatter_rows` in the timed
intervals (the trace's `programs`, averaged over the chips used) over
the loads timed (`units`): what the row scatter of one load's compaction
costs the device.  On doc-sharded tables the staged block is replicated
and the partitioner splits the scatter, so this is a chip's part.
Nothing where no such program ran.  Source: device_trace."""

from benchmarks.program_ms import programs_ms_a_unit

KERNELS = ("scatter_rows",)


def read(trace, counters):
    return programs_ms_a_unit(trace, counters, KERNELS)
