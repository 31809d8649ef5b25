"""unit_p95_ms (ms): a unit seen from its clients, its updates in to the
last of them broadcast (`receive_update` x n, then `flush()` returned):
the 95th percentile over the window's units, by the host's clock.  The
start of a record of the tail, not yet a judge of it.  Source:
host_clock; nothing where the window had fewer than 20 units."""


def read(trace, counters):
    if counters["units"] < 20:
        return None
    return counters["unit_p95_ms"]
