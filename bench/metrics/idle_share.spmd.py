"""Share of the traced window in which no operation ran on the devices
(four-chip cells): 1 - busy / window, busy averaged over the devices by
``trace_reduce``."""


def value(ctx):
    t = ctx["trace"]
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
