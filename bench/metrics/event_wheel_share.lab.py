"""Share of the device's busy time in the traced window spent in the
event-wheel Pallas kernels (``compact_ids_pallas``, ``segment_rank_pallas``:
the lab round's only Pallas calls)."""
from bench.kernel_cost import PALLAS
from bench.trace_reduce import op_time


def value(ctx):
    t = ctx["trace"]
    if t is None or t["busy_s"] <= 0:
        return None
    sec, n = op_time(t, PALLAS)
    if n == 0:
        return None
    return 100.0 * sec / t["busy_s"]
