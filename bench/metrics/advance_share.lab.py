"""Share of the device's busy time in the traced window spent in the
vmapped variable-step BDF advance (scope ``fap.advance``: steps, event
delivery, Newton setup and iterations)."""
from bench.stages import stage_share


def value(ctx):
    return stage_share(ctx, ("fap.advance",))
