"""Share of the devices' busy time in the traced window spent in the
vmapped variable-step BDF advance of the SPMD round (scope
``fap.advance``)."""
from bench.stages import stage_share


def value(ctx):
    return stage_share(ctx, ("fap.advance",))
