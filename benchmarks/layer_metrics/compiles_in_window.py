"""compiles_in_window (count): XLA compilations begun inside the window
(`jax.monitoring`, backend_compile_duration events).  Must read 0: a
run in which it does not is not correct.  Source: program_counter."""


def read(trace, counters):
    return float(counters["compiles_in_window"])
