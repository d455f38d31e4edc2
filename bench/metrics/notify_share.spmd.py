"""Share of the devices' busy time in the traced window spent notifying
clocks (scope ``fap.notify``: the boundary clocks gathered from every
shard and scattered into each shard's clock table)."""
from bench.stages import stage_share


def value(ctx):
    return stage_share(ctx, ("fap.notify",))
