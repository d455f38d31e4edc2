"""Share of the device's busy time in the traced window spent in the
Newton setup (scope ``bdf.setup``: assembly and factorization, under
``vmap`` a select that every lane pays), a part of ``advance_share.lab``."""
from bench.stages import stage_share


def value(ctx):
    return stage_share(ctx, ("bdf.setup",))
