"""recover_validate_share (%): `validate_update` of every update record
before the engine sees it: the update decoded whole into Python objects and
thrown away (`updates.py` -> `ops/columns.py` `decode_update_refs`); the
planner decodes it again.  Self time of `ytpu.recover.validate` (once a
file) as a share of the timed intervals; nothing where the program opens no
such span.  Source: program_span."""

from benchmarks.span_sum import spans_share

SPANS = ("ytpu.recover.validate",)


def read(trace, counters):
    return spans_share(trace, SPANS)
