"""Scheduler rounds per simulated millisecond over the window of a
four-chip cell (counts: ``SchedStats.rounds`` and the GVT advance)."""


def value(ctx):
    w = ctx["window"]
    c = w.get("counts") or {}
    if not c or w["bio_ms"] <= 0:
        return None
    return c["rounds"] / w["bio_ms"]
