"""Several host spans read as one share.

``trace_reduce.span_share`` reads one span; a layer the program splits
over several (ingest: the call, its WAL append, its SLO stamp) is the
sum of their self times.  Self times never overlap, so the sum counts
every second once.
"""


def spans_share(trace: dict, names) -> float | None:
    """The self time of the spans in ``names`` as a share (%) of the
    timed intervals; nothing where the trace holds none of them (a
    program that opens no such span)."""
    found = [trace["spans"][n] for n in names if n in trace["spans"]]
    if not found:
        return None
    return 100.0 * sum(found) / trace["window_s"]
