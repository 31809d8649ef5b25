"""plan_pool_balance (%): how evenly the native planner's pool was loaded:
the seconds its threads spent in `prepare`, summed over rooms
(`plan_pool_s` of the engine's flush metrics, measured inside
`ymx_prepare_many`), over what the pool had: its width
(`ymx_plan_threads()`, put into the counters by the generator) times the
self time of `ytpu.plan.native`, the flushing thread's wait for the call.
100% is every thread busy to the end; a long room planned last on one
thread while the others have nothing left reads low.  Source:
program_counter; nothing where the program keeps no such counter or opens
no such span."""

SPAN = "ytpu.plan.native"


def read(trace, counters):
    had = counters.get("plan_threads_host", 0) * trace["spans"].get(SPAN, 0.0)
    if "plan_pool_s" not in counters or had <= 0:
        return None
    return 100.0 * counters["plan_pool_s"] / had
