"""pack_lanes_share (%): sizing, keying and packing the element lanes of
the rooms that held rows (`_covering_key`, `pack_apply_lanes`).  Self
time of the engine's own `ytpu.pack.lanes` span, opened inside
`ytpu.pack` once a chunk, as a share of the timed intervals.  Source:
program_span; nothing where the program opens no such span (the parent
of PR 46)."""

from benchmarks.trace_reduce import span_share

SPAN = "ytpu.pack.lanes"


def read(trace, counters):
    return span_share(trace, SPAN)
