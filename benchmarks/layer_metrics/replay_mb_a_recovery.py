"""replay_mb_a_recovery (MB): bytes of log a recovery read: `bytes_read` of
the provider's `last_recovery`, summed by the generator over the window's
recoveries (`recover_bytes_read`, `recoveries`).  Source: program_counter;
nothing where the provider keeps no such counter."""


def read(trace, counters):
    if not counters.get("recoveries") or "recover_bytes_read" not in counters:
        return None
    return counters["recover_bytes_read"] / counters["recoveries"] / 1e6
