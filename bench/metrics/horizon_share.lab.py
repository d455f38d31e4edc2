"""Share of the device's busy time in the traced window spent computing
the dependency horizon: the operations under the program's scopes
``fap.horizon`` (runnable mask; the whole horizon on the dense and fused
paths) and ``fap.horizon_update`` (the incremental horizon's rows)."""
from bench.stages import stage_share


def value(ctx):
    return stage_share(ctx, ("fap.horizon", "fap.horizon_update"))
