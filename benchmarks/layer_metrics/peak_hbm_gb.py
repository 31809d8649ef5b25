"""peak_hbm_gb (GB): peak bytes in use on the fullest chip since the
process began (`device.memory_stats()["peak_bytes_in_use"]`), set-up
and table growth included.  Source: program_counter; nothing where the
backend keeps no memory statistics."""


def read(trace, counters):
    return counters["memory_peak_bytes"] / 1e9 or None
