"""Share of the device's busy time in the traced window spent in the
modified-Newton iterations (scope ``bdf.newton``: the rhs and the solve
of each BDF step), a part of ``advance_share.lab``."""
from bench.stages import stage_share


def value(ctx):
    return stage_share(ctx, ("bdf.newton",))
