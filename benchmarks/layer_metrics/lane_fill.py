"""lane_fill (%): how full the lane keys a window's flushes dispatched
were: the links written through the element lanes over the lanes of the
keys dispatched (`lane_links` over `lanes_dispatched` of the engine's
flush metrics, summed by the generator over the timed flushes).  What is
not a link is a list head, a delete mark, or the padding `_bucket_lanes`
and `_covering_key` add so that a process meets few programs.  Source:
program_counter; nothing where the program keeps no such counters (the
parent of PR 46) or no lane was dispatched."""


def read(trace, counters):
    if not counters.get("lanes_dispatched") or "lane_links" not in counters:
        return None
    return 100.0 * counters["lane_links"] / counters["lanes_dispatched"]
