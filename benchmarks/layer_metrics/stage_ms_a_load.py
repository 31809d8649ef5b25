"""stage_ms_a_load (ms): a load's staging on the host: the blocks'
allocation, their transfer and the `scatter_rows` dispatches (a span each,
opened once a block).  Self time of `ytpu.compact.alloc` +
`ytpu.compact.put` + `ytpu.compact.scatter` over the loads timed (`units`).
Source: program_span; nothing where the program opens no such span."""

from benchmarks.span_ms import spans_ms_a_unit

SPANS = ("ytpu.compact.alloc", "ytpu.compact.put", "ytpu.compact.scatter")


def read(trace, counters):
    return spans_ms_a_unit(trace, counters, SPANS)
