"""plan_cache_hit (%): rooms whose plan the planner's cache served, of
all rooms planned in the window (`plan_cache_hits` and
`plan_cache_misses` of the engine's flush metrics).  Source:
program_counter; nothing where no room was planned."""


def read(trace, counters):
    planned = counters["plan_cache_hits"] + counters["plan_cache_misses"]
    if not planned:
        return None
    return 100.0 * counters["plan_cache_hits"] / planned
