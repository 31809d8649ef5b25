"""segs_a_flush (count): segments a flush of the window created: a new
element's list of children, a new text's list, an attribute's chain
(`segs_created` of the engine's flush metrics, summed by the generator,
over the window's flushes).  Segments are what `seg_cap`, the width of
the device's table of list heads, grows with.  Source: program_counter;
nothing where the generator sums no such counter or the window held no
flush."""


def read(trace, counters):
    if "segs_created" not in counters or not counters.get("flushes"):
        return None
    return counters["segs_created"] / counters["flushes"]
