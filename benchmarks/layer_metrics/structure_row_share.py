"""structure_row_share (%): of the rows the planner integrated in the
window, those that are not text: `ContentFormat` rows (marks), rows
under a `parentSub` (attributes: last writer wins) and `ContentType` rows
(a new element, a new text).  `rows_format + rows_attr + rows_type` over
`rows_planned` of the engine's flush metrics, summed by the generator
over the window's flushes.  Source: program_counter; nothing where the
generator sums no such counters or no row was planned."""


def read(trace, counters):
    if not counters.get("rows_planned") or "rows_format" not in counters:
        return None
    structure = (
        counters["rows_format"] + counters.get("rows_attr", 0)
        + counters.get("rows_type", 0)
    )
    return 100.0 * structure / counters["rows_planned"]
