"""rebuild_ms_a_load (ms): a load's per-room `rebuild_compacted_self` and
the copy of each rebuilt room into its staged block: self time of
`ytpu.compact.rebuild` over the loads timed (`units`).  A fragmented room
of 100,000 rows merges none of them.  Source: program_span; nothing where
the program opens no such span."""

from benchmarks.span_ms import spans_ms_a_unit

SPANS = ("ytpu.compact.rebuild",)


def read(trace, counters):
    return spans_ms_a_unit(trace, counters, SPANS)
