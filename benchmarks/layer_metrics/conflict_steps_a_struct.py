"""conflict_steps_a_struct (count): rows the native planner's conflict
scan stepped over (`plancore.cpp` `list_insert`: a sibling in the same
gap each), a struct the returning sessions brought back
(`conflict_steps` of the engine's flush metrics, summed by the generator
over the timed flushes, over `offline_structs`, which the generator
counts from its own writers).  0 where no two writers met in a gap.
Source: program_counter; nothing where the program keeps no such counter
(the parent of PR 46) or no struct was brought."""


def read(trace, counters):
    if "conflict_steps" not in counters or not counters.get("offline_structs"):
        return None
    return counters["conflict_steps"] / counters["offline_structs"]
