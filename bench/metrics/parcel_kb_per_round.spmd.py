"""Spike-parcel payload each device shipped per scheduler round over the
window, in KiB (counts: the round's ``parcel_bytes`` counter, the ragged
transport's class chosen each round, over ``SchedStats.rounds``)."""


def value(ctx):
    c = ctx["window"].get("counts") or {}
    if not c or c.get("rounds", 0) <= 0 or "parcel_bytes" not in c:
        return None
    return c["parcel_bytes"] / c["rounds"] / 1024.0
