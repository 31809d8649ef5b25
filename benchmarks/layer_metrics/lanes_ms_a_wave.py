"""lanes_ms_a_wave (ms): device time of the element lanes' program in
the timed intervals (the trace's `programs` named `apply_plan2` and not
`apply_plan2_rows`, the row blocks' writer) over the waves timed
(`offline_waves`, which the generator counts).  Nothing where no such
program ran or no wave was timed.  Source: device_trace."""

from benchmarks.program_ms import programs_ms_a_unit

KERNEL = "apply_plan2"
NOT = "apply_plan2_rows"


def lanes_programs(trace: dict) -> dict:
    """The trace with the lanes' programs alone."""
    return {
        "programs": {
            name: rec for name, rec in trace["programs"].items()
            if KERNEL in name and NOT not in name
        }
    }


def read(trace, counters):
    return programs_ms_a_unit(
        lanes_programs(trace), {"units": counters.get("offline_waves")},
        (KERNEL,),
    )
