"""step2_kb_a_session (KB): bytes of framed sync step 2 a step 1 answered:
`reply_bytes` over `n_requests` of the provider's `last_sync_metrics`, summed
by the generator over the window's batches (`sync_reply_bytes`,
`sync_requests`).  Source: program_counter; nothing where the provider keeps
no such counters."""


def read(trace, counters):
    if not counters.get("sync_requests"):
        return None
    return counters["sync_reply_bytes"] / counters["sync_requests"] / 1e3
