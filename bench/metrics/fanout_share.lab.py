"""Share of the device's busy time in the traced window spent fanning
spikes out and inserting their events in the queue (scope ``fap.fanout``:
the spiked-round guard, the compact fan-out and its dense fallback)."""
from bench.stages import stage_share


def value(ctx):
    return stage_share(ctx, ("fap.fanout",))
