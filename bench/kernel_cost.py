"""Operations and bytes that a kernel's call needs, computed from its
shapes, and the names under which its calls appear in a device trace.

The bound on a call's time is the larger of operations over the chip's
peak rate and bytes over its memory bandwidth (``bench/peaks.json``);
a kernel's roofline share is that bound over its traced time.
"""
from __future__ import annotations

# A v5e trace names each operation by its HLO text; a Pallas kernel is a
# custom call to this target (in the lab round: the event-wheel kernels
# ``compact_ids_pallas`` and ``segment_rank_pallas``).
PALLAS = ('custom_call_target="tpu_custom_call"',)

BLOCK_N = 256         # the mask block the compaction kernel reads


def compact_ids_patterns(n: int, cap: int, block_n: int = BLOCK_N):
    """The strings that a ``compact_ids_pallas`` call's HLO text holds: an
    int32 [1, N padded] mask in, int32 [1, cap] ids and a [1, 1] count
    out."""
    n_pad = -(-n // block_n) * block_n
    return PALLAS + (f"(s32[1,{cap}]", "s32[1,1]",
                     f"custom-call(s32[1,{n_pad}]")


def compact_ids_cost(n: int, cap: int, block_n: int = BLOCK_N):
    """(operations, bytes) of one ``compact_ids`` call on an int32 mask of
    ``n`` lanes (padded to ``block_n``) giving ``cap`` int32 ids and a
    count: the mask read once, the ids and count written once, one
    compare-and-add per lane."""
    n_pad = -(-n // block_n) * block_n
    return float(n_pad), float(4 * n_pad + 4 * cap + 4)


def bound_seconds(ops: float, nbytes: float, peaks: dict):
    """(seconds, which bound) for one call."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
