"""Public jit'd wrappers: fused horizon + sort-free earliest-K selection.

``fused_horizon_select`` replaces the scheduler round's scatter-min +
clamp + runnable chain with one Pallas pass, and — when k_select > 0 —
replaces ``jnp.sort(score)[k-1]`` with ``select_threshold``'s bisection on
counts: the jaxpr of the whole selection path carries no sort primitive.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import use_interpret
from repro.kernels.event_wheel.event_wheel import (BN_DEFAULT,
                                                   compact_ids_pallas,
                                                   horizon_score_pallas,
                                                   segment_rank_pallas)


def select_threshold(score, k: int, n_iters: int = 48):
    """Smallest bisection point tau with count(score <= tau) >= k.

    ``score <= tau`` then selects the K earliest runnable neurons (ties and
    entries within the bisection resolution, (max-min)/2^n_iters, are all
    included — with n_iters = 48 that is below f64 time resolution for any
    millisecond-scale window, so the selection matches the sort-based kth
    threshold).  Fewer than k finite scores -> tau = max (select all), the
    same semantics as ``sort(score)[k-1] = +inf``.  Reductions only.
    """
    finite = jnp.isfinite(score)
    any_run = finite.any()
    lo = jnp.min(jnp.where(finite, score, jnp.inf))
    hi = jnp.max(jnp.where(finite, score, -jnp.inf))

    def body(_, c):
        lo, hi = c
        mid = 0.5 * (lo + hi)
        enough = jnp.sum(score <= mid) >= k
        return jnp.where(enough, lo, mid), jnp.where(enough, mid, hi)

    lo, hi = jax.lax.fori_loop(0, n_iters, body, (lo, hi))
    return jnp.where(any_run, hi, jnp.inf)


@partial(jax.jit, static_argnames=("t_end", "horizon_cap", "k_select",
                                  "n_iters", "block_n"))
def fused_horizon_select(t_clock, pre_byk, delay_byk, *, t_end: float,
                         horizon_cap: float, k_select: int = 0,
                         n_iters: int = 48, block_n: int = BN_DEFAULT):
    """Fused scheduler-round notification half.

    t_clock: f64[N]; pre_byk: i32[K, N], delay_byk: f64[K, N] (the static
    by-post edge layout: column i holds neuron i's K in-edges).
    Returns (horizon[N], runnable bool[N]); with k_select > 0 the mask is
    restricted to the K earliest runnable neurons by threshold count.
    """
    K, N = pre_byk.shape
    cand = t_clock[pre_byk] + delay_byk        # one XLA gather
    n_pad = (-N) % block_n
    tc = t_clock
    if n_pad:
        cand = jnp.concatenate(
            [cand, jnp.full((K, n_pad), jnp.inf, cand.dtype)], axis=1)
        tc = jnp.concatenate(
            [tc, jnp.full((n_pad,), t_end, tc.dtype)])   # pad never runnable
    hor, score = horizon_score_pallas(cand, tc, t_end=t_end,
                                      horizon_cap=horizon_cap,
                                      block_n=block_n,
                                      interpret=use_interpret())
    hor, score = hor[:N], score[:N]
    runnable = jnp.isfinite(score)
    if k_select > 0:
        tau = select_threshold(score, k_select, n_iters=n_iters)
        runnable = jnp.logical_and(runnable, score <= tau)
    return hor, runnable


def auto_impls() -> dict:
    """What ``impl="auto"`` resolves to for each dispatched op on the
    current backend: the Pallas kernel on a TPU, the jnp/scatter path
    elsewhere (interpret-mode grids walk the blocks in python)."""
    kernel = not use_interpret()
    return {"compact_ids": "pallas" if kernel else "jnp",
            "compact_gather": "pallas" if kernel else "jnp",
            "spike_compact": "pallas" if kernel else "jnp",
            "segment_rank": "pallas" if kernel else "scatter"}


def spike_compact(mask, values, cap: int, *, impl: str = "auto"):
    """Sort-free row-wise compaction of sparse spike streams into capped
    parcel buffers — the packer of the sparse spike-parcel transport
    (``repro.distributed.exchange``).

    mask: [D, M] (row d = the spikes destined for shard d); values: [D, M].
    Returns (idx i32[D, cap] — source column of each packed entry, sentinel M
    marks empty slots; vals [D, cap] — values at idx, 0 in empty slots;
    count i32[D] — kept per row, may exceed cap so callers can account
    drops).  ``impl="pallas"`` runs ``compact_ids`` per row (the kernel
    emits int32 ids only; the values are gathered here in XLA),
    ``"jnp"`` the scatter oracle, ``"auto"`` the kernel on a TPU only.
    """
    if impl == "auto":
        impl = auto_impls()["spike_compact"]
    if impl == "jnp":
        from repro.kernels.event_wheel import ref
        return ref.compact_rows_ref(mask, values, cap=cap)
    if impl != "pallas":
        raise ValueError(f"unknown spike_compact impl {impl!r}")
    M = mask.shape[1]
    idx, cnt = jax.vmap(lambda m: compact_ids(m, cap, impl="pallas"))(mask)
    vals = jnp.take_along_axis(values, jnp.minimum(idx, M - 1), axis=1)
    vals = jnp.where(idx < M, vals, jnp.zeros((), values.dtype))
    return idx, vals, cnt


def compact_ids(mask, cap: int, *, impl: str = "auto",
                block_n: int = BN_DEFAULT):
    """Compact a bool[N] runnable mask into a gather-id list — the active
    set of the compact–step–scatter execution path (``batch="compact"``).

    Returns (ids i32[cap] — indices of the first ``cap`` set lanes in
    index order, sentinel N for empty slots; count i32 — total set lanes,
    which may exceed cap: the overflow rolls to a later dispatch, never
    drops).  ``impl="pallas"`` runs the blocked [BN, cap] one-hot kernel
    (prefix sums as triangular-ones matmuls), ``"jnp"`` the O(N) cumsum +
    scatter oracle; ``"auto"`` picks the kernel on a TPU and the oracle
    elsewhere.
    """
    if impl == "auto":
        impl = auto_impls()["compact_ids"]
    if impl == "jnp":
        from repro.kernels.event_wheel import ref
        return ref.compact_ids_ref(mask, cap)
    if impl != "pallas":
        raise ValueError(f"unknown compact_ids impl {impl!r}")
    (n,) = mask.shape
    n_pad = (-n) % block_n
    m = mask
    if n_pad:
        m = jnp.concatenate([m, jnp.zeros((n_pad,), m.dtype)])
    ids, cnt = compact_ids_pallas(m, cap=cap, block_n=block_n,
                                  interpret=use_interpret())
    return jnp.minimum(ids, n).astype(jnp.int32), cnt


def compact_gather(mask, table, cap: int, *, fill: int = None,
                   impl: str = "auto", block_n: int = BN_DEFAULT):
    """``compact_ids`` plus the rows of a static i32[N, MO] table for the
    compacted lanes — the edge-index emitter of the compact fan-out path
    (``fanout="compact"``), where ``table`` is
    ``exec_common.out_edge_table`` and the emitted rows are the spiking
    lanes' out-edge ids.  The rows are one XLA gather by the compacted
    ids; ``impl`` selects the compaction (see ``compact_ids``).

    Returns (ids i32[cap] — set-lane indices in index order, sentinel N;
    rows i32[cap, MO] — table[ids], ``fill`` (default N, callers pass E)
    for empty slots; count i32 — total set lanes, may exceed cap: the
    caller must fall back, never drop).
    """
    (n,) = mask.shape
    if fill is None:
        fill = n
    ids, cnt = compact_ids(mask, cap, impl=impl, block_n=block_n)
    rows = jnp.where((ids < n)[:, None], table[jnp.minimum(ids, n - 1)],
                     fill).astype(jnp.int32)
    return ids, rows, cnt


def segment_rank(key, n_keys: int, max_rank: int, *, impl: str = "auto",
                 block_e: int = 512, domain: str = "global"):
    """Rank of each event within its key group, in event-index order —
    the wheel's generic-insert slot ranking, dispatched (the ROADMAP
    follow-up from PR 1).

    ``impl="pallas"`` runs the pairwise [BE, BE] tile kernel: one VMEM
    pass, no per-round O(n_keys) key table; ``"scatter"`` the original
    ``max_rank``-round scatter-min (``sched.wheel.segment_rank``);
    ``"auto"`` picks pallas on real TPU, scatter elsewhere.  Ranks agree
    on all events with key < n_keys (invalid events differ: the scatter
    path parks them at ``max_rank``, the pairwise path ranks them among
    themselves — both are masked out by the insert's validity test).

    ``domain="batch"`` (the PR 5 follow-up) remaps the keys of a small
    batch to the dense [E] event domain before the scatter ranking: each
    valid event's key becomes the index of its group's first occurrence
    (a pairwise [E, E] first-occurrence argmax), so the per-round key
    table shrinks from O(n_keys + 1) = O(N*B) to O(E + 1) — the compact
    fan-out's cap-bounded edge batches stop allocating an N-proportional
    table per call off-TPU.  The remap is a bijection on key groups, so
    valid-event ranks are identical to the global domain; invalid events
    park at the E sentinel (rank ``max_rank``), as before.  The pallas
    path is already N-free and ignores the domain.
    """
    if impl == "auto":
        impl = auto_impls()["segment_rank"]
    if domain not in ("global", "batch"):
        raise ValueError(f"unknown segment_rank domain {domain!r}")
    if impl == "scatter":
        from repro.sched import wheel as wh
        if domain == "batch":
            (E,) = key.shape
            valid = key < n_keys
            same = key[:, None] == key[None, :]
            rep = jnp.argmax(same, axis=1).astype(key.dtype)
            key2 = jnp.where(valid, rep, E)
            return wh.segment_rank(key2, E, max_rank)
        return wh.segment_rank(key, n_keys, max_rank)
    if impl != "pallas":
        raise ValueError(f"unknown segment_rank impl {impl!r}")
    (E,) = key.shape
    e_pad = (-E) % block_e
    k = key
    if e_pad:
        # pad with a never-used key so pad ranks stay self-contained
        k = jnp.concatenate([k, jnp.full((e_pad,), n_keys + 1, key.dtype)])
    return segment_rank_pallas(k, max_rank=max_rank,
                               block_e=block_e,
                               interpret=use_interpret())[:E]


def by_post_layout(net):
    """Host-side static prep: the [K, N] by-post (pre, delay) layout the
    fused kernel consumes.  Requires the uniform grouped edge list that
    ``make_network`` emits (``sched.grouped_k``)."""
    import numpy as np

    from repro.sched import grouped_k
    k = grouped_k(net)
    if k is None:
        raise ValueError("fused horizon kernel needs a uniform by-post "
                         "edge layout (make_network's grouping)")
    n = int(net.n)
    pre_byk = jnp.asarray(np.asarray(net.pre).reshape(n, k).T)
    delay_byk = jnp.asarray(np.asarray(net.delay).reshape(n, k).T)
    return pre_byk, delay_byk
