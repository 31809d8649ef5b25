"""stage_mb_a_load (MB): bytes staged for `scatter_rows` by the window's
compactions (`rows_staged_bytes` of the engine's flush metrics, summed by
the generator) over the loads timed (`units`): what one load's compaction
allocates on the host, sends and has the device write.  Source:
program_counter; nothing where the generator sums no such counter."""


def read(trace, counters):
    if "rows_staged_bytes" not in counters or not counters.get("units"):
        return None
    return counters["rows_staged_bytes"] / counters["units"] / 1e6
