"""Device busy milliseconds per scheduler round in the traced window
(lab cells): the trace's busy time over the rounds that ran while it was
recorded (``SchedStats`` rounds read before and after)."""


def value(ctx):
    t, tr = ctx["trace"], ctx["window"].get("traced")
    if t is None or not tr or tr["rounds"] <= 0:
        return None
    return 1e3 * t["busy_s"] / tr["rounds"]
