"""Pluggable spike-parcel transport for the SPMD FAP round.

The paper's asynchronous execution model has exactly two point-to-point
channels — stepping notifications (neuron clocks) and spike parcels — and
the efficiency claim rests on their cost scaling with *activity*, not
network size.  This module makes the channel realisation a first-class
knob (``transport="allgather"|"sparse"`` on ``build_fap_round``):

``allgather`` (reference)
    Both channels are dense all-gathers of full N-length vectors, exactly
    the collectives GSPMD would insert: bytes scale with N regardless of
    firing rate.

``sparse`` (the activity-scaled transport)
    * spike parcels: each shard compacts its (spiked, t_spike) into a
      destination-routed parcel buffer [n_shards, parcel_cap] of
      (global id, time) entries via the sort-free prefix-rank compaction
      (``kernels.event_wheel.ops.spike_compact``), then exchanges
      rows with one tiled ``all_to_all``: per-device parcel bytes are
      ``n_shards * parcel_cap * (4 + 8)`` — a function of the static
      activity cap, independent of N.  Parcel-cap overflow is detected,
      never silent: the per-round drop counter rides the round outputs
      (``RunResult.dropped`` via ``run_fap_spmd``).
    * clock notifications: an all-gather over each shard's *boundary set*
      (local neurons with cross-shard out-edges — the static frontier
      ``sharding.shard_frontier`` derives at build time from the by-post
      edge layout), scattered back into an N-length clock table.  For
      spatially local connectivity the frontier, and hence notify bytes,
      shrinks far below N; for uniform random wiring it degenerates to
      ~N (every neuron is boundary), which the channel attribution makes
      visible instead of hiding.  Locality is *manufactured* one layer up:
      structured topologies (``repro.core.topology``) plus a
      locality-aware id permutation (``distributed.placement``, the
      ``placement=`` knob on ``run_fap_spmd``) hand this transport an edge
      list whose frontier — and notify gather — is already small; the
      transport itself needs no placement awareness because the routing
      tables are derived from whatever (relabeled) net it is given.

``sparse_ragged`` (the two-phase activity-sized transport)
    The static per-(src,dst) parcel cap wastes slots on quiet pairs.  The
    ragged transport exchanges *counts* first (one scalar ``pmax`` over
    the per-destination spike counts, tagged ``exchange_counts``), then
    runs the parcel ``all_to_all`` at the smallest *bucket class* — a
    static ascending ladder of caps ending at ``parcel_cap``
    (``ExchangeSpec.classes``) — that fits this round's fullest shard
    pair.  Shapes stay static per class (one ``lax.switch`` branch each,
    tagged ``exchange_parcel_c<cap>`` so the per-class bytes are
    HLO-attributable); quiet rounds ship the smallest class, ~2-4x fewer
    parcel bytes, and no round ever ships more than the static cap.
    Overflow semantics are identical to ``sparse``: events beyond
    ``parcel_cap`` are counted in ``dropped``, never silent.

Every collective is wrapped in ``jax.named_scope`` with a channel tag
(``exchange_notify`` / ``exchange_parcel`` / ``exchange_counts``) that
survives into compiled HLO metadata, so
``launch.hlo_analysis.collective_channel_bytes`` can *assert* the
bytes-scale-with-activity claim per channel rather than assume it.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

NOTIFY_TAG = "exchange_notify"
PARCEL_TAG = "exchange_parcel"
COUNTS_TAG = "exchange_counts"
TRANSPORTS = ("allgather", "sparse", "sparse_ragged")


def class_tag(cap: int) -> str:
    """HLO-query tag for one ragged bucket class's parcel scope.

    Includes the trailing scope delimiter so per-class attribution cannot
    alias across classes whose caps share a decimal prefix
    ("exchange_parcel_c1" is a substring of "exchange_parcel_c12"; the
    op_name path always delimits the scope with "/")."""
    return f"{PARCEL_TAG}_c{cap}/"


class ExchangeSpec(NamedTuple):
    """Static sparse-transport geometry (python constants, closed over by
    jit — the ``WheelSpec`` of the communication layer)."""
    parcel_cap: int = 64          # parcel slots per (source, dest) shard pair
    classes: tuple = ()           # ragged bucket-class caps (ascending);
    #                               () -> (cap//8, cap//2, cap) deduped

    def class_ladder(self) -> tuple:
        """The realized ascending class ladder, always ending at
        ``parcel_cap`` (so the largest class is exactly the static
        transport's geometry and ragged can never ship more)."""
        cap = int(self.parcel_cap)
        base = self.classes or (max(1, cap // 8), max(1, cap // 2))
        return tuple(sorted({int(c) for c in base if 0 < int(c) < cap})) \
            + (cap,)


class Transport(NamedTuple):
    """One realisation of the two FAP notification channels.

    ``notify``/``exchange`` run *inside* shard_map on shard-local arrays;
    ``example_args``/``in_specs``/``shardings`` describe the transport's
    extra static-routing arguments (empty for the dense reference).
    """
    name: str
    notify: Callable       # (t_local, *targs) -> (f64[N] global clock table,
    #                         aux: gathered boundary clock vector for the
    #                         sparse family, None for allgather — the
    #                         incremental-horizon moved-set source)
    exchange: Callable     # (spiked_l, t_sp_l, *targs) ->
    #                         (spiked bool[N], t_spike f64[N], local drops
    #                          i32, parcel bytes shipped this round i32)
    example_args: tuple    # transport arg arrays, appended to the round args
    in_specs: tuple        # shard_map PartitionSpecs for those args
    shardings: tuple       # jit NamedShardings for those args


def _flat_axes(mesh) -> tuple:
    return tuple(mesh.axis_names)


def _gather_axes(x, flat):
    for ax in reversed(flat):
        x = jax.lax.all_gather(x, ax, tiled=True)
    return x


def shard_index(mesh, flat):
    """Flat shard index of the calling device inside shard_map (row-major
    over the given mesh axes) — shared by the transports and the
    shard-local round."""
    idx = jnp.zeros((), jnp.int32)
    for ax in flat:
        idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
    return idx


def allgather_transport(mesh) -> Transport:
    """The reference transport: both channels as dense N-length gathers."""
    flat = _flat_axes(mesh)

    def notify(t_local):
        with jax.named_scope(NOTIFY_TAG):
            return _gather_axes(t_local, flat), None

    def exchange(spiked, t_sp):
        with jax.named_scope(PARCEL_TAG):
            spiked_all = _gather_axes(spiked, flat)
            tsp_all = _gather_axes(t_sp, flat)
        n = spiked_all.shape[0]
        return (spiked_all, tsp_all, jnp.zeros((), jnp.int32),
                jnp.asarray(n * (1 + 8), jnp.int32))

    return Transport("allgather", notify, exchange, (), (), ())


def sparse_transport(mesh, n: int, net, spec: ExchangeSpec,
                     ragged: bool = False) -> Transport:
    """Activity-scaled transport: frontier-gather notify + capped
    destination-routed parcel ``all_to_all``.  Routing tables are derived
    host-side from the concrete edge list (``net``) at build time.

    ``ragged=True`` (``transport="sparse_ragged"``) sizes the parcel
    exchange per round: a counts phase (scalar ``pmax`` of the fullest
    (src, dst) pair) picks the smallest static bucket class
    (``spec.class_ladder()``) that fits, and only that class's sized
    ``all_to_all`` runs (``lax.switch``).  Semantics are identical to the
    static-cap exchange — the chosen class always covers every pending
    parcel entry up to ``parcel_cap``, and overflow beyond ``parcel_cap``
    hits the same drop counter."""
    from repro.distributed.sharding import shard_frontier
    from repro.kernels.event_wheel import ops as ew_ops

    flat = _flat_axes(mesh)
    n_shards = int(np.prod([mesh.shape[a] for a in flat]))
    n_local = n // n_shards
    cap = int(spec.parcel_cap)
    classes = spec.class_ladder() if ragged else (cap,)
    fr = shard_frontier(np.asarray(net.pre), np.asarray(net.post), n, n_shards)
    b_rel = jnp.asarray(fr.boundary_rel)            # i32[n_shards, F] sharded
    b_gid = jnp.asarray(fr.boundary_gid)            # i32[n_shards, F] replicated
    dest_map = jnp.asarray(fr.dest_map)             # bool[N, n_shards] sharded

    def notify(t_local, b_rel_l, b_gid_all, dest_l):
        del dest_l
        with jax.named_scope(NOTIFY_TAG):
            mine = t_local[jnp.clip(b_rel_l[0], 0, n_local - 1)]      # [F]
            allv = _gather_axes(mine, flat)                # [n_shards * F]
            table = jnp.full((n,), jnp.inf, t_local.dtype)
            # pad slots carry the gid sentinel n -> parked out of range
            table = table.at[b_gid_all.reshape(-1)].set(allv, mode="drop")
            offset = shard_index(mesh, flat) * n_local
            table = jax.lax.dynamic_update_slice(table, t_local, (offset,))
        return table, allv

    def _ship(gid, ts, c_cap):
        """One sized parcel exchange: the first ``c_cap`` slots of every
        (src, dst) parcel row, padded back to the static cap after the
        collective so every class branch has one output shape."""
        tag = PARCEL_TAG if not ragged else f"{PARCEL_TAG}_c{c_cap}"
        with jax.named_scope(tag):
            gid_r = jax.lax.all_to_all(gid[:, :c_cap], flat, 0, 0, tiled=True)
            ts_r = jax.lax.all_to_all(ts[:, :c_cap], flat, 0, 0, tiled=True)
        pad = cap - c_cap
        if pad:
            gid_r = jnp.concatenate(
                [gid_r, jnp.full((n_shards, pad), n, gid_r.dtype)], axis=1)
            ts_r = jnp.concatenate(
                [ts_r, jnp.zeros((n_shards, pad), ts_r.dtype)], axis=1)
        return gid_r, ts_r, jnp.asarray(n_shards * c_cap * (4 + 8), jnp.int32)

    def exchange(spiked, t_sp, b_rel_l, b_gid_all, dest_l):
        del b_rel_l, b_gid_all
        # row d of the parcel buffer = this shard's spikes with at least
        # one synapse into shard d (deduped by the static dest map)
        mask = jnp.logical_and(dest_l, spiked[:, None]).T  # [S, n_local]
        vals = jnp.broadcast_to(t_sp[None, :], mask.shape)
        idx, ts, cnt = ew_ops.spike_compact(mask, vals, cap)
        offset = shard_index(mesh, flat) * n_local
        gid = jnp.where(idx < n_local, idx + offset, n)  # sentinel -> n
        if len(classes) == 1:
            gid_r, ts_r, pbytes = _ship(gid, ts, classes[0])
        else:
            # phase 1: global fullest (src, dst) pair -> smallest class
            with jax.named_scope(COUNTS_TAG):
                worst = jax.lax.pmax(jnp.max(cnt), flat)
            cidx = sum((worst > c).astype(jnp.int32) for c in classes[:-1])
            gid_r, ts_r, pbytes = jax.lax.switch(
                cidx, [(lambda g, t, c=c: _ship(g, t, c)) for c in classes],
                gid, ts)
        with jax.named_scope(PARCEL_TAG):
            spiked_all = jnp.zeros((n,), bool).at[gid_r.reshape(-1)].set(
                True, mode="drop")
            tsp_all = jnp.zeros((n,), t_sp.dtype).at[gid_r.reshape(-1)].set(
                ts_r.reshape(-1), mode="drop")
        drops = jnp.sum(jnp.maximum(cnt - cap, 0)).astype(jnp.int32)
        return spiked_all, tsp_all, drops, pbytes

    rowspec = P(flat, None)
    return Transport(
        "sparse_ragged" if ragged else "sparse", notify, exchange,
        example_args=(b_rel, b_gid, dest_map),
        in_specs=(rowspec, P(None, None), rowspec),
        shardings=(NamedSharding(mesh, rowspec),
                   NamedSharding(mesh, P(None, None)),
                   NamedSharding(mesh, rowspec)),
    )


def get_transport(name: str, mesh, *, n: int, net=None,
                  spec: ExchangeSpec = ExchangeSpec()) -> Transport:
    """Transport dispatch — the ``transport=`` knob."""
    if name == "allgather":
        return allgather_transport(mesh)
    if name in ("sparse", "sparse_ragged"):
        if net is None:
            raise ValueError(f"transport={name!r} derives its routing tables "
                             "from the concrete edge list: pass net=")
        return sparse_transport(mesh, n, net, spec,
                                ragged=name == "sparse_ragged")
    raise ValueError(f"unknown transport {name!r} (want one of {TRANSPORTS})")
