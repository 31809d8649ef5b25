"""lane_link_share (%): the links a window's flushes wrote through the
element lanes (`apply_plan2`: rooms that held rows) of all the links
they wrote, the row blocks of rooms loaded whole included (`lane_links`
over `lane_links + row_links` of the engine's flush metrics, summed by
the generator over the timed flushes).  Says whether a cell measures a
merge into resident rooms or a load.  Source: program_counter; nothing
where the program keeps no such counters (the parent of PR 46) or no
link was written."""


def read(trace, counters):
    if "lane_links" not in counters or "row_links" not in counters:
        return None
    links = counters["lane_links"] + counters["row_links"]
    if not links:
        return None
    return 100.0 * counters["lane_links"] / links
