"""stage_fill (%): how full the blocks staged for `scatter_rows` are: the
bytes of rebuilt rows the compacted rooms hold over the bytes staged for
them (host allocation = transfer = device writes), `rows_held_bytes` over
`rows_staged_bytes` of the engine's flush metrics, summed by the generator
over the window's flushes.  One long room in a block as wide as its widest
room reads a few percent.  Source: program_counter; nothing where the
generator sums no such counters or nothing was staged."""


def read(trace, counters):
    if not counters.get("rows_staged_bytes"):
        return None
    return 100.0 * counters["rows_held_bytes"] / counters["rows_staged_bytes"]
