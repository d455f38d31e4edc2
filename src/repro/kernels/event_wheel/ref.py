"""Pure-jnp oracles for the fused horizon/selection kernel."""
from __future__ import annotations

import jax.numpy as jnp


def horizon_score_ref(cand, t_clock, *, t_end: float, horizon_cap: float,
                      eps: float = 1e-12):
    """cand: f64[K, N] = t_clock[pre] + delay in by-post layout.

    Returns (horizon[N], score[N]): the per-neuron dependency horizon
    (min over in-edges, clamped at t_end and t_clock + horizon_cap) and
    the scheduler score (clock if runnable else +inf).
    """
    hor = jnp.minimum(cand.min(axis=0), t_end)
    hor = jnp.minimum(hor, t_clock + horizon_cap)
    runnable = t_clock < hor - eps
    return hor, jnp.where(runnable, t_clock, jnp.inf)


def select_earliest_ref(score, k: int):
    """Sort-based earliest-K oracle (the dense scheduler path): select all
    entries with score <= k-th smallest (ties included)."""
    kth = jnp.sort(score)[min(k, score.shape[0]) - 1]
    return jnp.logical_and(jnp.isfinite(score), score <= kth)


def compact_ids_ref(mask, cap: int):
    """Pure-jnp gather-id compaction: cumsum ranks + one masked scatter
    (O(N), sort-free).  Returns (ids i32[cap] — indices of the first
    ``cap`` set lanes in index order, sentinel N for empty slots; count
    i32 — total set lanes, may exceed cap)."""
    n = mask.shape[0]
    msk = mask.astype(jnp.int32)
    csum = jnp.cumsum(msk)
    pos = csum - msk
    slot = jnp.where(jnp.logical_and(msk == 1, pos < cap), pos, cap)
    ids = jnp.full((cap,), n, jnp.int32).at[slot].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    return ids, csum[-1] if n else jnp.zeros((), jnp.int32)


def compact_gather_ref(mask, table, cap: int, fill: int):
    """Pure-jnp oracle for ``ops.compact_gather``: gather-id compaction
    (``compact_ids_ref``) followed by one XLA row gather of the static
    table.  Returns (ids i32[cap], rows i32[cap, MO] — table[ids]
    with ``fill`` in empty slots, count i32)."""
    n = mask.shape[0]
    ids, cnt = compact_ids_ref(mask, cap)
    rows = jnp.where((ids < n)[:, None],
                     table[jnp.minimum(ids, n - 1)], fill).astype(jnp.int32)
    return ids, rows, cnt


def segment_rank_ref(key, max_rank: int):
    """O(E^2) pairwise oracle for the segment-ranking kernel: rank[j] =
    |{i < j : key[i] == key[j]}| clipped at ``max_rank``."""
    E = key.shape[0]
    same = key[:, None] == key[None, :]
    earlier = jnp.arange(E)[None, :] < jnp.arange(E)[:, None]
    return jnp.minimum(jnp.sum(jnp.logical_and(same, earlier), axis=1),
                       max_rank).astype(jnp.int32)


def compact_rows_ref(mask, values, *, cap: int):
    """Pure-jnp oracle for the spike-compaction kernel: cumsum ranks + a
    masked scatter (still sort-free — the dense-queue argsort is the thing
    being avoided, and the test census checks this path too)."""
    D, M = mask.shape
    msk = mask.astype(jnp.int32)
    csum = jnp.cumsum(msk, axis=-1)
    pos = csum - msk
    total = csum[:, -1]
    rows = jnp.broadcast_to(jnp.arange(D, dtype=jnp.int32)[:, None], (D, M))
    col = jnp.where(jnp.logical_and(msk == 1, pos < cap), pos, cap)
    idx = jnp.full((D, cap), M, jnp.int32).at[rows, col].set(
        jnp.broadcast_to(jnp.arange(M, dtype=jnp.int32)[None, :], (D, M)),
        mode="drop")
    vals = jnp.zeros((D, cap), values.dtype).at[rows, col].set(
        jnp.broadcast_to(values, (D, M)), mode="drop")
    return idx, vals, total
