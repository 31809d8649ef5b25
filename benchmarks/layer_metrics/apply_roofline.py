"""apply_roofline (%): the device kernels `apply_plan2` (on a mesh, the
program that wraps `local_apply`) and `scatter_rows` against the HBM
roof.  The least bytes the window's work needs (benchmarks/roofline.py,
from the engine's counters) over the chips' peak bandwidth, over the
two programs' device time in the trace.  Both are scatters: bytes bound
them, not operations.  Source: device_trace."""

from benchmarks import roofline

KERNELS = ("apply_plan2", "scatter_rows", "local_apply")


def read(trace, counters):
    seconds = sum(
        rec["seconds"] for name, rec in trace["programs"].items()
        if any(k in name for k in KERNELS)
    )
    if seconds <= 0:
        return None
    needed = roofline.apply_plan2_bytes(
        counters["link_writes"], counters["cap"]
    ) + roofline.scatter_rows_bytes(
        counters["rows_compacted"], counters["cap"], counters["seg_cap"]
    )
    peak = roofline.peaks(counters["device_kind"])["hbm_bytes_per_s"]
    least = needed / (peak * counters["chips"])
    return 100.0 * least / seconds
