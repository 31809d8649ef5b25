"""window_trend (%): how much longer the later half of the window's
timed intervals took than the earlier half, interval for interval (an
odd one in the middle is left out).  The intervals of a cell do equal
work, so a cell that is stationary reads about 0 either way; one whose
provider is still warming up, or whose rooms age within a run, reads
above it, and the rate then depends on how far a run got."""


def read(trace, counters):
    times = counters["timed_intervals_s"]
    half = len(times) // 2
    if not half:
        return None
    early, late = sum(times[:half]), sum(times[-half:])
    return 100.0 * (late - early) / early
