"""Share of the devices' busy time in the traced window spent exchanging
spike parcels (scope ``fap.exchange``: the per-destination compaction,
the count ``pmax``, the ragged ``all_to_all`` and the received spikes
scattered into the global spike vector)."""
from bench.stages import stage_share


def value(ctx):
    return stage_share(ctx, ("fap.exchange",))
