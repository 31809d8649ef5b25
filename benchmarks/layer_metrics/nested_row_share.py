"""nested_row_share (%): of the rows the planner integrated in the
window, those whose parent is a type item and not a root name: children
of a nested `Y.XmlElement` / `Y.XmlText`, attributes of an element.
`rows_nested` over `rows_planned` of the engine's flush metrics, summed
by the generator over the window's flushes.  Near 100 where the hot rooms
are trees; 0 where every keystroke lands in a root `Y.Text`.  Source:
program_counter; nothing where the generator sums no such counters (a
program without them) or no row was planned."""


def read(trace, counters):
    if not counters.get("rows_planned") or "rows_nested" not in counters:
        return None
    return 100.0 * counters["rows_nested"] / counters["rows_planned"]
