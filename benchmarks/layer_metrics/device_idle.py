"""device_idle (%): the share of the timed intervals in which no
operation ran on the device: 1 - busy over window, busy being the union
of the `XLA Ops` intervals averaged over the chips used.  Source:
device_trace."""


def read(trace, counters):
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
