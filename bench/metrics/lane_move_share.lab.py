"""Share of the device's busy time in the traced window spent moving lanes
between the full state and the batch: gathers (scope ``fap.gather``) and
scatters back with the spike record (``fap.scatter``)."""
from bench.stages import stage_share


def value(ctx):
    return stage_share(ctx, ("fap.gather", "fap.scatter"))
