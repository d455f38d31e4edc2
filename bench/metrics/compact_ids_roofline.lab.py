"""Roofline share of the ``compact_ids`` Pallas kernel in the traced
window: the least time its calls could take on this chip (bytes over HBM
bandwidth: the kernel is memory bound, see ``bench/kernel_cost.py``) over
the device time the trace gives them.  The calls are found by their
shapes: a mask of N lanes compacted to ``batch_cap`` ids (the frontier;
the compact fan-out's calls have the same shapes where ``spike_cap`` is
``batch_cap``, as in ``lab65k``)."""
from bench.kernel_cost import (bound_seconds, compact_ids_cost,
                               compact_ids_patterns)
from bench.trace_reduce import op_time


def value(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    s = ctx["setup"]
    cap = s["batch_cap"]
    sec, calls = op_time(t, compact_ids_patterns(s["n"], cap))
    if calls == 0 or sec <= 0:
        return None
    ops, nbytes = compact_ids_cost(s["n"], cap)
    low, _ = bound_seconds(ops, nbytes, ctx["peaks"])
    return 100.0 * calls * low / sec
