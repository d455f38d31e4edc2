"""Device busy milliseconds per scheduler round in the traced window
(four-chip cells): the busy time averaged over the devices, over the
rounds that ran while it was recorded (``SchedStats`` rounds read before
and after)."""


def value(ctx):
    t, tr = ctx["trace"], ctx["window"].get("traced")
    if t is None or not tr or tr["rounds"] <= 0:
        return None
    return 1e3 * t["busy_s"] / tr["rounds"]
