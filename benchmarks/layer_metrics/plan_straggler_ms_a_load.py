"""plan_straggler_ms_a_load (ms): the longest single room's `prepare` in
the native planner's pool, a flush (`plan_room_max_s` of the engine's flush
metrics, measured inside `ymx_prepare_many`), summed by the generator over
the window's flushes, over the loads timed (`units`): what one long
document costs the one thread that plans it.  Source: program_counter;
nothing where the program keeps no such counter."""


def read(trace, counters):
    if "plan_room_max_s" not in counters or not counters.get("units"):
        return None
    return 1e3 * counters["plan_room_max_s"] / counters["units"]
