"""Share of the devices' busy time in the traced window spent on the
dependency horizon of the SPMD round: ``fap.horizon`` (the runnable mask)
and ``fap.horizon_update`` (the moved set and the incremental pull of the
rows it feeds)."""
from bench.stages import stage_share


def value(ctx):
    return stage_share(ctx, ("fap.horizon", "fap.horizon_update"))
