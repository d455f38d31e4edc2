"""Roofline share of the ``compact_ids`` Pallas kernel in the traced
window of a four-chip cell: the least time its calls could take on a
chip (bytes over HBM bandwidth: memory bound, see ``bench/kernel_cost.py``)
over the device time the trace gives them.  The calls are the frontier's:
a shard's mask of ``n_local`` lanes compacted to ``batch_cap`` ids (the
moved set's and the fan-out's calls have other shapes)."""
from bench.kernel_cost import (bound_seconds, compact_ids_cost,
                               compact_ids_patterns)
from bench.trace_reduce import op_time


def value(ctx):
    t = ctx["trace"]
    s = ctx["setup"]
    if t is None or "n_local" not in s:
        return None
    sec, calls = op_time(t, compact_ids_patterns(s["n_local"],
                                                 s["batch_cap"]))
    if calls == 0 or sec <= 0:
        return None
    ops, nbytes = compact_ids_cost(s["n_local"], s["batch_cap"])
    low, _ = bound_seconds(ops, nbytes, ctx["peaks"])
    return 100.0 * calls * low / sec
