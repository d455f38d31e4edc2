"""Pallas TPU kernel: fused FAP horizon + runnable-mask + scheduler score.

The scheduler round's notification half: horizon[i] = min over in-edges of
(t[pre] + delay), clamped at t_end and at the per-round cap, then the
runnable test and score formation (clock if runnable else +inf) — one VMEM
pass instead of four HLO loops over [N].

Layout mirrors the hines kernel's TPU transpose: neurons lie along the
128-wide lane dimension, the K in-edges along sublanes, so the min-reduce
is a full-width VPU column reduction over a [K, BN] tile.  The edge gather
(t_clock[pre] -> cand) happens outside the kernel as a single XLA gather —
the by-post edge layout makes the in-kernel work purely dense.

VMEM/block = (K + 3) * BN * 8B; K = 16, BN = 256 -> ~39 KiB.  The
horizon kernel is opt-in (``horizon_impl="fused"``) and does not compile
for the TPU while clocks are f64.

The main path's two kernels work on int32 only and compile for v5e:
``compact_ids_pallas`` (the active-set, fan-out and parcel compaction)
and ``segment_rank_pallas`` (the wheel insert's slot ranking).  Values of
other dtypes are gathered by the callers in XLA with the emitted ids.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

BN_DEFAULT = 256
# caps above this are tiled over a second grid axis (compact_ids_pallas)
BCAP_DEFAULT = 1024


def _horizon_kernel(cand_ref, t_clock_ref, hor_ref, score_ref, *,
                    t_end, horizon_cap, eps):
    cand = cand_ref[...]                       # [K, BN]
    t_c = t_clock_ref[...]                     # [1, BN]
    hor = jnp.minimum(jnp.min(cand, axis=0, keepdims=True), t_end)
    hor = jnp.minimum(hor, t_c + horizon_cap)
    runnable = t_c < hor - eps
    hor_ref[...] = hor
    score_ref[...] = jnp.where(runnable, t_c, jnp.inf)


def horizon_score_pallas(cand, t_clock, *, t_end: float, horizon_cap: float,
                         eps: float = 1e-12, block_n: int = BN_DEFAULT,
                         interpret: bool = False):
    """cand: [K, N] by-post candidates; t_clock: [N] -> (horizon[N], score[N]).

    N must be a multiple of block_n (the ops wrapper pads).
    """
    K, N = cand.shape
    assert N % block_n == 0, (N, block_n)
    grid = (N // block_n,)
    kernel = functools.partial(_horizon_kernel, t_end=t_end,
                               horizon_cap=horizon_cap, eps=eps)
    row = pl.BlockSpec((1, block_n), lambda i: (0, i))
    hor, score = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((K, block_n), lambda i: (0, i)), row],
        out_specs=(row, row),
        out_shape=(jax.ShapeDtypeStruct((1, N), cand.dtype),) * 2,
        interpret=interpret,
    )(cand, t_clock.reshape(1, N))
    return hor[0], score[0]


# Index maps return int32 zeros: under x64 a bare python 0 traces to i64,
# which the TPU lowering refuses.
_Z = np.int32(0)


def _prefix_columns(m_row, bn):
    """Inclusive and exclusive prefix sums of a 0/1 row i32[1, BN], as
    i32[BN, 1] columns.  Pallas TPU has no ``cumsum``: the prefix sum is a
    triangular-ones matmul, exact in f32 for any count below 2^24 (0/1
    operands, integer partial sums)."""
    r = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 1)
    mf = m_row.astype(jnp.float32)
    dn = (((1,), (1,)), ((), ()))              # contract the lanes of both
    incl = jax.lax.dot_general((c <= r).astype(jnp.float32), mf, dn,
                               preferred_element_type=jnp.float32)
    excl = jax.lax.dot_general((c < r).astype(jnp.float32), mf, dn,
                               preferred_element_type=jnp.float32)
    return incl.astype(jnp.int32), excl.astype(jnp.int32)


def _compact_ids_kernel(mask_ref, ids_ref, cnt_ref, *, cb, bn):
    """Grid (cap block c, mask block i): place the set lanes of mask block i
    whose global rank falls in slots [c*CB, (c+1)*CB).  The rank of a set
    lane is the running set count of earlier blocks (``cnt_ref``, reset at
    the start of every cap-block sweep) plus its in-block exclusive prefix
    sum; placement is a [BN, CB] one-hot column reduction — compares and
    sums only, no sort, gather or scatter inside the kernel.  Ids
    accumulate +1-biased so empty slots read 0 until the wrapper rewrites
    them to the sentinel."""
    c, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        ids_ref[...] = jnp.zeros((1, cb), jnp.int32)
        cnt_ref[...] = jnp.zeros((1, 1), jnp.int32)

    base = cnt_ref[...]                                   # [1, 1]
    incl, excl = _prefix_columns(mask_ref[...], bn)       # [BN, 1]
    lane_set = (incl - excl) == 1
    slot = c * cb + jax.lax.broadcasted_iota(jnp.int32, (bn, cb), 1)
    hit = jnp.logical_and(base + excl == slot, lane_set)  # [BN, CB]
    gid1 = i * bn + jax.lax.broadcasted_iota(jnp.int32, (bn, 1), 0) + 1
    ids_ref[...] += jnp.sum(hit.astype(jnp.int32) * gid1, axis=0,
                            keepdims=True, dtype=jnp.int32)
    cnt_ref[...] = base + incl[bn - 1:bn, :]


def compact_ids_pallas(mask, *, cap: int, block_n: int = BN_DEFAULT,
                       block_cap: int = BCAP_DEFAULT,
                       interpret: bool = False):
    """Compact a bool[N] mask into the gather-id list of its set lanes.

    Returns (ids i32[cap] — indices of the first ``cap`` set lanes in
    index order, sentinel N for empty slots; count i32 — total set lanes,
    may exceed cap).  N must be a multiple of block_n (the ops wrapper
    pads with zeros).  Caps above ``block_cap`` are tiled over a second
    grid axis, so the [block_n, block_cap] placement tile bounds VMEM.
    """
    (N,) = mask.shape
    assert N % block_n == 0, (N, block_n)
    cb = cap if cap <= block_cap else block_cap
    n_cb = -(-cap // cb)
    kernel = functools.partial(_compact_ids_kernel, cb=cb, bn=block_n)
    acc, cnt = pl.pallas_call(
        kernel,
        grid=(n_cb, N // block_n),
        in_specs=[pl.BlockSpec((1, block_n), lambda c, i: (_Z, i))],
        out_specs=(pl.BlockSpec((1, cb), lambda c, i: (_Z, c)),
                   pl.BlockSpec((1, 1), lambda c, i: (_Z, _Z))),
        out_shape=(jax.ShapeDtypeStruct((1, n_cb * cb), jnp.int32),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)),
        interpret=interpret,
    )(mask.astype(jnp.int32).reshape(1, N))
    acc = acc[0, :cap]
    ids = jnp.where(acc > 0, acc - 1, N).astype(jnp.int32)
    return ids, cnt[0, 0]


def _segment_rank_kernel(kcol_ref, krow_ref, rank_ref, *, be):
    """Grid (j-block, i-block): add to rank[j] the events of block i that
    share j's key and come earlier.  Block i's keys arrive as a column and
    block j's as a row, so the [BE_i, BE_j] equality tile reduces over
    sublanes straight into a lane-dense rank row — no per-round key table,
    no scatter, no sort.  Strictly-earlier blocks count whole; the
    diagonal block counts i < j only; later blocks are skipped."""
    jb, ib = pl.program_id(0), pl.program_id(1)

    @pl.when(ib == 0)
    def _init():
        rank_ref[...] = jnp.zeros((1, be), jnp.int32)

    def _accum(strict):
        same = kcol_ref[...] == krow_ref[...]               # [BE_i, BE_j]
        if strict:
            ii = jax.lax.broadcasted_iota(jnp.int32, (be, be), 0)
            jj = jax.lax.broadcasted_iota(jnp.int32, (be, be), 1)
            same = jnp.logical_and(same, ii < jj)
        rank_ref[...] += jnp.sum(same.astype(jnp.int32), axis=0,
                                 keepdims=True, dtype=jnp.int32)

    pl.when(ib < jb)(lambda: _accum(False))
    pl.when(ib == jb)(lambda: _accum(True))


def segment_rank_pallas(key, *, max_rank: int, block_e: int = 512,
                        interpret: bool = False):
    """Pairwise segment ranking for the wheel's generic insert: rank[j] =
    |{i < j : key[i] == key[j]}| clipped at ``max_rank`` — one VMEM pass
    over [BE, BE] tiles instead of ``segment_rank``'s ``max_rank`` rounds
    of scatter-min over an O(n_keys) table.  Keys are int32; E is padded
    to block_e by the ops wrapper."""
    (E,) = key.shape
    assert E % block_e == 0, (E, block_e)
    nb = E // block_e
    k = key.astype(jnp.int32)
    rank = pl.pallas_call(
        functools.partial(_segment_rank_kernel, be=block_e),
        grid=(nb, nb),
        in_specs=[pl.BlockSpec((block_e, 1), lambda j, i: (i, _Z)),
                  pl.BlockSpec((1, block_e), lambda j, i: (_Z, j))],
        out_specs=pl.BlockSpec((1, block_e), lambda j, i: (_Z, j)),
        out_shape=jax.ShapeDtypeStruct((1, E), jnp.int32),
        interpret=interpret,
    )(k.reshape(E, 1), k.reshape(1, E))
    return jnp.minimum(rank[0], max_rank)
