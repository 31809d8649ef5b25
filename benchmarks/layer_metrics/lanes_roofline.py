"""lanes_roofline (%): the element lanes' program (`apply_plan2`, not
`apply_plan2_rows`) against the HBM roof, by itself: the least bytes the
links written through the lanes need (benchmarks/roofline.py
`apply_plan2_bytes` of `lane_links`, which the generator sums from the
engine's flush metrics over the timed flushes) over the chip's peak
bandwidth, over that program's device time in the trace.
`apply_roofline` mixes it with the row writers'.  The byte count leaves
out row-index lanes, list heads, delete marks and padding, so it is a
lower bound and the share can only read low.  Source: device_trace;
nothing where the program keeps no `lane_links` (the parent of PR 46) or
no such program ran."""

from benchmarks import roofline

KERNEL = "apply_plan2"
NOT = "apply_plan2_rows"


def read(trace, counters):
    seconds = sum(
        rec["seconds"] for name, rec in trace["programs"].items()
        if KERNEL in name and NOT not in name
    )
    if seconds <= 0 or "lane_links" not in counters:
        return None
    needed = roofline.apply_plan2_bytes(counters["lane_links"], counters["cap"])
    peak = roofline.peaks(counters["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * needed / (peak * counters["chips"]) / seconds
