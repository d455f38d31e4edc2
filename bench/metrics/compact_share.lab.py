"""Share of the device's busy time in the traced window spent compacting
the runnable frontier into the batch's ids (scope ``fap.compact``)."""
from bench.stages import stage_share


def value(ctx):
    return stage_share(ctx, ("fap.compact",))
