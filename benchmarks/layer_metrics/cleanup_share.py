"""cleanup_share (%): the formatting clean-up after a remote
transaction: the look at what each planned room brought, once a flush,
and the walk of the texts that a format item came to or left
(`engine._format_cleanup`).  Self time of the engine's `ytpu.plan.cleanup`
span as a share of the timed intervals; the deletions it finds are
planned by a second round of the flush, under that round's own phase
spans.  Source: program_span; nothing where the program opens no such
span."""

from benchmarks.trace_reduce import span_share


def read(trace, counters):
    return span_share(trace, "ytpu.plan.cleanup")
