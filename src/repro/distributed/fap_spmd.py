"""SPMD (multi-pod) realisation of the FAP variable-timestep scheduler round.

This is the paper's execution model mapped onto the production mesh
(DESIGN.md §3): neurons (and their BDF integrator states, event queues and
out-edge lists) are sharded across every mesh axis; one jitted ``fap_round``
advances all runnable neurons to their dependency horizons.

The round decomposes into five composable stages, shared with the
single-host execution models through ``exec_common`` and ``repro.sched``:

  notify   — exchange neuron clocks (the paper's stepping notifications):
             ``distributed.exchange.Transport.notify``
  horizon  — per-neuron dependency horizon + runnable mask:
             ``exec_common.horizon_times`` / ``runnable_mask`` (the same
             helper the single-host exec models call, here with the
             shard-relative post index and the notify clock table)
  advance  — per-neuron variable-order variable-step BDF to the horizon:
             ``exec_bsp.make_vardt_advance`` (unchanged, vmapped)
  parcels  — exchange (spiked, t_spike): ``Transport.exchange``
  insert   — shard-local grouped queue insert (``repro.sched``; with
             queue="wheel" the bucketed O(E) scatter, no sort anywhere)

Communication is owned entirely by the transport (the
``transport="allgather"|"sparse"`` knob, mirroring the ``queue`` knob):

  * ``allgather`` — the reference realisation: both channels as dense
    all-gathers of full N-length vectors (bytes scale with N),
  * ``sparse``   — capped destination-routed parcel ``all_to_all`` plus a
    boundary-set notify gather (parcel bytes scale with the static
    activity cap, not N).  Requires ``optimized=True`` and the concrete
    ``net`` (routing tables are static, derived at build time).

Each channel's collectives are tagged with ``jax.named_scope`` so
``launch.hlo_analysis.collective_channel_bytes`` attributes per-channel
bytes in the compiled HLO — the bytes-scale-with-activity claim is
asserted by tests/benchmarks, not assumed.

Parcel-cap and queue overflow stay detected-never-silent: every round
returns a ``dropped`` counter (queue + transport), accumulated into
``RunResult.dropped`` by ``run_fap_spmd``.

``build_fap_round`` returns (fn, example_args, in_shardings) so the dry-run
can lower it on the 16x16 and 2x16x16 meshes like any LM cell.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import sched
from repro.core import bdf
from repro.core import exec_common as xc
from repro.core.cell import CellModel
from repro.core.exec_bsp import make_vardt_advance
from repro.distributed.exchange import (ExchangeSpec, get_transport,
                                        shard_index)


class PaperNeuroSpec(NamedTuple):
    n_neurons: int = 1 << 20          # 2^20 neurons (4x the paper's 219k lab run)
    k_in: int = 16
    n_comp: int = 29                  # branched_tree(depth=3)
    ev_cap: int = 32
    t_end: float = 1000.0
    horizon_cap: float = 2.0


def build_fap_round(model: CellModel, spec: PaperNeuroSpec, mesh,
                    opts: bdf.BDFOptions = bdf.BDFOptions(),
                    optimized: bool = False, queue: str = "dense",
                    wheel: sched.WheelSpec = sched.WheelSpec(),
                    transport: str = "allgather",
                    exchange: ExchangeSpec = ExchangeSpec(), net=None,
                    batch: str = "dense", batch_cap: int = 0,
                    fanout: str = "dense", spike_cap: int = 0,
                    horizon: str = "full", move_cap: int = 0):
    """optimized=False: paper-faithful baseline — horizon scatter-min and
    event insert as *global* ops, lowered by GSPMD (collective-heavy: with
    queue="dense" the global argsort in the insert becomes a distributed
    sort; queue="wheel" already removes the sort from the global path).

    optimized=True (§Perf): the communication is exactly the paper's two
    notification channels and nothing else, realised by the chosen
    transport (see module docstring); horizon computation and queue
    insertion run SHARD-LOCAL inside shard_map (edges are sharded by
    postsynaptic neuron, aligned with the neuron sharding, so no event
    ever crosses shards again).  With queue="wheel" the shard-local insert
    is the bucketed event-wheel scatter (repro.sched) — no sort of any
    kind, local or distributed.

    batch="compact" compacts each shard's runnable mask into a local
    gather-id list and advances only a fixed [batch_cap]-wide batch per
    round (earliest-clock threshold selection on overflow, exactly the
    single-host ``exec_fap`` semantics, here per shard) — composing with
    the sparse transport (spiked/t_spike are scattered back to full
    shard-width before the parcel exchange) and with placement (locality
    shrinks the frontier the compact batch has to cover).  Shard-local
    only: with ``optimized=False`` there is no shard-local stage to
    compact and the knob is rejected.

    fanout="compact" compacts each round's (global) spiking set and
    gathers only those neurons' out-edges from the replicated static
    ``exec_common.out_edge_table``; each shard keeps the rows that land in
    its contiguous global-edge-id slice and inserts that fixed
    [spike_cap * k_out] batch instead of scanning all E/n_shards local
    in-edges — the delivery-side twin of ``batch="compact"``.  More than
    ``spike_cap`` global spikes fall back to the dense insert under
    ``lax.cond`` (identical events, never a drop).  spike_cap <= 0 means
    min(N, 256).

    horizon="incremental" extends PR 4's incremental horizon maintenance
    to the shard-local round: the moved set is (a) last round's advanced
    batch (carried compact ids) and (b) the *notify frontier* entries
    whose gathered clock changed this round (compared against the carried
    previous boundary-clock vector — ``sharding.shard_frontier`` tables),
    and only rows fed by a moved clock recompute, bit-identical to the
    full scatter-min because min is exact.  Requires optimized +
    batch="compact" + a sparse-family transport (the frontier tables).
    The round then carries (horizon, prev_boundary_clocks, moved_ids)
    through its inputs/outputs; ``run_fap_spmd`` seeds them.

    The round returns (sts, eq_t, eq_a, eq_g, spiked, t_spike, n_deliv,
    n_resets, dropped, parcel_bytes[, horizon, prev_bnd, moved_ids]);
    ``dropped`` counts this round's queue overflow plus sparse-transport
    parcel overflow (detected, never silent); ``parcel_bytes`` is the
    transport's realized parcel-channel payload this round (the ragged
    transport's per-round class choice made visible — cross-checked
    against the per-class HLO attribution in tests).
    """
    if transport != "allgather" and not optimized:
        raise ValueError("sparse transport realises the shard-local "
                         "(optimized=True) round; the global path has no "
                         "explicit channels to replace")
    if batch not in ("dense", "compact"):
        raise ValueError(f"unknown batch mode {batch!r}")
    if batch == "compact" and not optimized:
        raise ValueError("active-set compaction is shard-local "
                         "(optimized=True); the global path has no "
                         "shard-local advance stage to compact")
    if fanout not in ("dense", "compact"):
        raise ValueError(f"unknown fanout mode {fanout!r}")
    if fanout == "compact" and (not optimized or net is None):
        raise ValueError("compact fan-out is shard-local (optimized=True) "
                         "and derives its out-edge table from the concrete "
                         "edge list: pass net=")
    if horizon not in ("full", "incremental"):
        raise ValueError(f"unknown horizon mode {horizon!r}")
    incremental = horizon == "incremental"
    if incremental and (not optimized or batch != "compact"
                        or not transport.startswith("sparse")
                        or net is None):
        raise ValueError("incremental horizon maintenance needs the "
                         "shard-local round (optimized=True), the compact "
                         "batch's moved set (batch='compact') and the "
                         "sparse transport's frontier tables "
                         "(transport='sparse'|'sparse_ragged', net=)")
    n, E = spec.n_neurons, spec.n_neurons * spec.k_in
    flat = tuple(mesh.axis_names)                  # shard over ALL axes
    nshard = P(flat)
    advance = make_vardt_advance(model, opts, eg_window=0.0, step_budget=8)
    vadvance = jax.vmap(advance)
    n_shards = int(np.prod([mesh.shape[a] for a in flat]))
    n_local = n // n_shards
    e_local = E // n_shards
    cap = n_local if batch_cap <= 0 else min(int(batch_cap), n_local)
    s_cap = min(int(spike_cap), n) if spike_cap > 0 else min(n, 256)
    qops = sched.get_queue_ops(queue, ev_cap=spec.ev_cap, wheel=wheel)
    qcap = qops.capacity
    tp = get_transport(transport, mesh, n=n, net=net, spec=exchange) \
        if optimized else None
    n_targs = len(tp.example_args) if tp is not None else 0
    # replicated static tables (appended to the round args after targs);
    # one host-side grouping pass serves both views
    tbl_args, tbl_specs = (), ()
    if fanout == "compact" or incremental:
        post_np, edge_np = xc.out_tables(net)
    if fanout == "compact":
        tbl_args += (jnp.asarray(edge_np),)                # [N, MO], sent. E
        tbl_specs += (P(None, None),)
    if incremental:
        tbl_args += (jnp.asarray(post_np),)                # [N, MOp], sent. N
        tbl_specs += (P(None, None),)
        sf_len = int(np.prod(tp.example_args[1].shape))    # n_shards * F
        mcap = min(sf_len, n_shards * cap) if move_cap <= 0 \
            else min(int(move_cap), sf_len)

    def _insert_byk(eq_t, eq_a, eq_g, t_ev, wa, wg, valid):
        """Grouped insert over the by-post edge layout (k_in per neuron);
        row index is the (shard-relative) target neuron.  Only used on the
        shard-local path, which already constructs post_rel as
        repeat(arange, k_in) — i.e. the layout is guaranteed there."""
        k = spec.k_in
        eq = qops.wrap(eq_t, eq_a, eq_g, jnp.zeros((), jnp.int32))
        eq = qops.insert_grouped(eq, t_ev.reshape(-1, k), wa.reshape(-1, k),
                                 wg.reshape(-1, k), valid.reshape(-1, k))
        return eq

    def _round_local(sts, eq_t, eq_a, eq_g, pre_l, delay_l, wa_l, wg_l, iinj,
                     *rest):
        """One scheduler round on this shard's neurons.  All arrays are
        shard-local (the static tables replicated); the ONLY communication
        is the transport's channels (plus the scalar telemetry psums)."""
        from repro.kernels.event_wheel import ops as ew_ops

        n_carry = 3 if incremental else 0
        carry, rest = rest[:n_carry], rest[n_carry:]
        targs, tbls = rest[:n_targs], rest[n_targs:]
        t_local = sts.t
        n_loc = t_local.shape[0]
        sidx = shard_index(mesh, flat)
        offset = sidx * n_local
        # --- notify: clock exchange (stepping notifications) --------------
        t_table, bnd_new = tp.notify(t_local, *targs)
        # --- horizon + runnable (shared helper, shard-relative post) ------
        post_rel = jnp.repeat(jnp.arange(n_loc), spec.k_in)
        dloc = xc.DeviceNet(pre_l, post_rel, delay_l, wa_l, wg_l)

        def _full_horizon(_):
            return xc.horizon_times(dloc, n_loc, t_local, spec.t_end,
                                    t_table=t_table,
                                    horizon_cap=spec.horizon_cap)

        if incremental:
            hor_c, prev_bnd, moved_prev = carry
            post_tbl_r = tbls[-1]
            b_gid_flat = targs[1].reshape(-1)              # [n_shards * F]
            sf = b_gid_flat.shape[0]
            pre_byk = pre_l.reshape(n_loc, spec.k_in).T    # [K, n_loc]
            delay_byk = delay_l.reshape(n_loc, spec.k_in).T

            def _rows(p):
                """Recompute horizon rows ``p`` (sentinel-padded) from the
                fresh notify table — the same min/clamp chain as the full
                scatter-min (min is exact: incremental == full, bitwise)."""
                pc = jnp.minimum(p, n_loc - 1)
                cand = t_table[pre_byk[:, pc]] + delay_byk[:, pc]
                h = jnp.minimum(jnp.min(cand, axis=0), spec.t_end)
                return jnp.minimum(h, t_local[pc] + spec.horizon_cap)

            # moved set: frontier clocks that changed since last round
            # (pad slots carry the gid sentinel n -> masked out) plus last
            # round's locally advanced lanes (carried compact ids)
            moved_b = jnp.logical_and(bnd_new != prev_bnd, b_gid_flat < n)
            mids, mcnt = ew_ops.compact_ids(moved_b, mcap)
            gids = jnp.where(mids < sf,
                             b_gid_flat[jnp.minimum(mids, sf - 1)], n)

            def _incr_horizon(hor):
                own = jnp.where(moved_prev < n_loc, moved_prev + offset, n)
                srcs = jnp.concatenate([gids, own])
                posts = jnp.where((srcs < n)[:, None],
                                  post_tbl_r[jnp.minimum(srcs, n - 1)], n)
                p_loc = posts - offset
                p_loc = jnp.where(
                    jnp.logical_and(p_loc >= 0, p_loc < n_loc), p_loc, n_loc)
                p = jnp.concatenate([moved_prev, p_loc.reshape(-1)])
                return hor.at[p].set(_rows(p), mode="drop")

            horizon = jax.lax.cond(mcnt <= mcap, _incr_horizon,
                                   _full_horizon, hor_c)
            prev_bnd = bnd_new
        else:
            horizon = _full_horizon(None)
        runnable = xc.runnable_mask(t_local, horizon)
        # --- advance (dense: all lanes; compact: the shard-local active
        # set, gathered into a fixed [cap] batch and scattered back) -------
        if batch == "compact":
            ids, _ = xc.compact_frontier(runnable, t_local, cap)
            lane_ok = ids < n_loc
            idc = jnp.minimum(ids, n_loc - 1)
            sts_b = xc.gather_lanes(sts, idc)
            t_b_prev = sts_b.t
            sts_b, eqt_b, spiked_b, tsp_b, nd, nrs = vadvance(
                sts_b, eq_t[idc], eq_a[idc], eq_g[idc], horizon[idc],
                lane_ok, iinj[idc])
            sts = xc.scatter_lanes(sts, sts_b, ids)
            eq_t = xc.scatter_at(eq_t, ids, eqt_b)
            spiked = xc.scatter_at(jnp.zeros((n_loc,), bool), ids, spiked_b)
            t_sp = xc.scatter_at(jnp.zeros((n_loc,)), ids, tsp_b)
            if incremental:
                moved = jnp.logical_and(lane_ok, sts_b.t != t_b_prev)
                moved_prev = jnp.where(moved, ids, n_loc).astype(jnp.int32)
        else:
            sts, eq_t, spiked, t_sp, nd, nrs = vadvance(
                sts, eq_t, eq_a, eq_g, horizon, runnable, iinj)
        # --- parcel exchange ----------------------------------------------
        spiked_all, tsp_all, pdrop, pbytes = tp.exchange(spiked, t_sp, *targs)

        # --- insert (shard-local): dense = grouped scan of all E/n_shards
        # in-edges; compact = gather only the spiking set's out-edges that
        # land in this shard's contiguous global-edge-id slice ------------
        def _ins_dense(eq_t, eq_a, eq_g):
            valid = spiked_all[pre_l]
            t_ev = tsp_all[pre_l] + delay_l
            return _insert_byk(eq_t, eq_a, eq_g, t_ev, wa_l, wg_l, valid)

        if fanout == "compact":
            edge_tbl_r = tbls[0]

            def _ins_compact(eq_t, eq_a, eq_g):
                ids_s, eids, _ = ew_ops.compact_gather(
                    spiked_all, edge_tbl_r, s_cap, fill=E)
                idc_s = jnp.minimum(ids_s, n - 1)
                le = eids - sidx * e_local
                ok = jnp.logical_and(
                    (ids_s < n)[:, None],
                    jnp.logical_and(eids < E,
                                    jnp.logical_and(le >= 0, le < e_local)))
                lec = jnp.clip(le, 0, e_local - 1)
                tgt = lec // spec.k_in          # shard-relative post (grouped)
                t_ev = tsp_all[idc_s][:, None] + delay_l[lec]
                eq = qops.wrap(eq_t, eq_a, eq_g, jnp.zeros((), jnp.int32))
                return qops.insert_batch(
                    eq, tgt.ravel(), t_ev.ravel(), wa_l[lec].ravel(),
                    wg_l[lec].ravel(), ok.ravel())

            eq = jax.lax.cond(spiked_all.sum() <= s_cap, _ins_compact,
                              _ins_dense, eq_t, eq_a, eq_g)
        else:
            eq = _ins_dense(eq_t, eq_a, eq_g)
        nd = jax.lax.psum(nd.sum(), flat)
        nrs = jax.lax.psum(nrs.sum(), flat)
        dropped = jax.lax.psum(eq.dropped + pdrop, flat)
        out = (sts, eq.t, eq.w_ampa, eq.w_gaba, spiked, t_sp, nd, nrs,
               dropped, pbytes)
        if incremental:
            out += (horizon, prev_bnd, moved_prev)
        return out

    # carried-extra specs: horizon [N] sharded, boundary-clock vector
    # replicated (the all_gather output IS replicated), moved ids [S*cap]
    # sharded (each shard's own compact batch)
    carry_specs = (P(flat), P(None), P(flat)) if incremental else ()

    def fap_round(sts, eq_t, eq_a, eq_g, pre, post, delay, w_a, w_g, iinj,
                  *rest):
        if optimized:
            # per-leaf specs: leading neuron dim sharded over every axis
            sts_specs = jax.tree_util.tree_map(
                lambda leaf: P(flat, *([None] * (leaf.ndim - 1))), sts)
            n2 = P(flat, None)
            fn_l = jax.shard_map(
                _round_local, mesh=mesh,
                in_specs=(sts_specs, n2, n2, n2, P(flat), P(flat), P(flat),
                          P(flat), P(flat)) + carry_specs + tp.in_specs
                + tbl_specs,
                out_specs=(sts_specs, n2, n2, n2, P(flat), P(flat), P(), P(),
                           P(), P()) + carry_specs,
                check_vma=False)
            return fn_l(sts, eq_t, eq_a, eq_g, pre, delay, w_a, w_g, iinj,
                        *rest)
        t_clock = sts.t
        dnet = xc.DeviceNet(pre, post, delay, w_a, w_g)
        horizon = xc.horizon_times(dnet, n, t_clock, spec.t_end,
                                   horizon_cap=spec.horizon_cap)
        runnable = xc.runnable_mask(t_clock, horizon)
        sts, eq_t, spiked, t_sp, nd, nrs = vadvance(
            sts, eq_t, eq_a, eq_g, horizon, runnable, iinj)
        valid = spiked[pre]
        t_ev = t_sp[pre] + delay
        # the global path honours the runtime `post` array (arbitrary edge
        # order); both queue impls insert to explicit targets sort-free or
        # not per their contract
        eq = qops.wrap(eq_t, eq_a, eq_g, jnp.zeros((), jnp.int32))
        eq = qops.insert(eq, post, t_ev, w_a, w_g, valid)
        return (sts, eq.t, eq.w_ampa, eq.w_gaba, spiked, t_sp, nd.sum(),
                nrs.sum(), eq.dropped, jnp.zeros((), jnp.int32))

    # ---- example args (ShapeDtypeStructs) and shardings -------------------
    f8 = jnp.float64
    sts = jax.eval_shape(
        lambda: jax.vmap(lambda i: bdf.reinit(
            model, 0.0, model.init_state(), i, opts))(jnp.zeros((n,), f8)))
    args = (
        sts,
        jax.ShapeDtypeStruct((n, qcap), f8),           # eq_t
        jax.ShapeDtypeStruct((n, qcap), f8),           # eq_a
        jax.ShapeDtypeStruct((n, qcap), f8),           # eq_g
        jax.ShapeDtypeStruct((E,), jnp.int32),         # pre
        jax.ShapeDtypeStruct((E,), jnp.int32),         # post
        jax.ShapeDtypeStruct((E,), f8),                # delay
        jax.ShapeDtypeStruct((E,), f8),                # w_ampa
        jax.ShapeDtypeStruct((E,), f8),                # w_gaba
        jax.ShapeDtypeStruct((n,), f8),                # iinj
    )
    carry_args = ()
    if incremental:
        carry_args = (
            jax.ShapeDtypeStruct((n,), f8),                      # horizon
            jax.ShapeDtypeStruct((sf_len,), f8),                 # prev_bnd
            jax.ShapeDtypeStruct((n_shards * cap,), jnp.int32),  # moved ids
        )
    args = args + carry_args + \
        (tp.example_args if tp is not None else ()) + tbl_args

    def st_spec(leaf):
        return NamedSharding(mesh, P(flat, *([None] * (leaf.ndim - 1))))

    sts_sh = jax.tree_util.tree_map(
        lambda leaf: st_spec(leaf) if leaf.ndim >= 1 else NamedSharding(mesh, P()),
        sts)
    esh = NamedSharding(mesh, nshard)
    n2 = NamedSharding(mesh, P(flat, None))
    carry_sh = tuple(NamedSharding(mesh, s) for s in carry_specs)
    in_shardings = (sts_sh, n2, n2, n2, esh, esh, esh, esh, esh,
                    NamedSharding(mesh, nshard)) + carry_sh + \
        (tp.shardings if tp is not None else ()) + \
        tuple(NamedSharding(mesh, s) for s in tbl_specs)
    return fap_round, args, in_shardings


def run_fap_spmd(model: CellModel, net, iinj, t_end: float, mesh,
                 opts: bdf.BDFOptions = bdf.BDFOptions(),
                 optimized: bool = True, queue: str = "dense",
                 wheel: sched.WheelSpec = sched.WheelSpec(),
                 transport: str = "allgather",
                 exchange: ExchangeSpec = ExchangeSpec(),
                 ev_cap: int = 32, horizon_cap: float = 2.0,
                 max_rounds: int = 400, spk_cap: int = 128,
                 placement=None, batch: str = "dense", batch_cap: int = 0,
                 fanout: str = "dense", spike_cap: int = 0,
                 horizon: str = "full", move_cap: int = 0,
                 checkpoint_every: int = 0, ckpt_dir=None,
                 resume: bool = False, fault=None, watchdog: bool = True,
                 max_rollbacks: int = 2, ckpt_keep: int = 3):
    """Drive the SPMD round to t_end on a concrete network; the host loop
    records spike trains and accumulates the per-round telemetry into the
    standard ``RunResult`` (dropped = queue + parcel overflow — detected,
    never silent).  Returns (RunResult, rounds).

    placement: optional ``distributed.placement.Placement`` (or a method
    name for ``compute_placement``) — the neuron-id relabeling is applied
    before sharding and inverted on the returned spike record / final
    state, so results stay in the caller's neuron order while the notify
    frontier and parcel routing shrink with the realized locality.
    Checkpoints store the *placed* order: resume with the same placement.

    batch / batch_cap / fanout / spike_cap / horizon / move_cap: forwarded
    to ``build_fap_round`` — "compact" runs the shard-local advance
    (delivery) on the compacted runnable (spiking) set only, and
    horizon="incremental" carries the dependency horizon across rounds
    recomputing only frontier-fed rows (``RunResult.sched`` telemetry is
    not collected on the SPMD path; ``RunResult.comm`` records the
    realized parcel bytes summed over rounds — with the ragged transport
    this is the per-round class choice made visible).

    Preemption tolerance (exec_common.run_checkpointed):
    checkpoint_every=k snapshots the full round state (``SimCarry``) into
    ckpt_dir every k rounds with the atomic-commit protocol; resume=True
    restarts from the latest complete checkpoint, event-for-event
    identical to the uninterrupted run.  Elastic resume onto a different
    mesh shape works transparently: only the mesh-shaped horizon-carry
    leaves are reseeded (a full recompute the incremental scheme equals
    bitwise), everything else restores through
    ``restore_checkpoint(shardings=)``.  fault: a
    ``checkpoint.FaultPlan`` for kill/poison injection; watchdog (on by
    default here) runs the per-round ``exec_common.health_check`` and
    quarantine-and-rollback on non-finite state (bounded by
    max_rollbacks, then ``RunResult.failed``); telemetry lands on
    ``RunResult.health``.
    """
    from repro.core import events as ev
    from repro.core.exec_bsp import RunResult
    from repro.distributed import placement as plc

    pl = None
    if placement is not None:
        n_shards = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
        pl = placement if isinstance(placement, plc.Placement) else \
            plc.compute_placement(net, n_shards, method=placement)
        net, iinj = plc.place_inputs(net, iinj, pl)
    n = int(net.n)
    k = sched.grouped_k(net)
    if k is None:
        raise ValueError("run_fap_spmd needs make_network's grouped by-post "
                         "edge layout")
    spec = PaperNeuroSpec(n_neurons=n, k_in=k, ev_cap=ev_cap, t_end=t_end,
                          horizon_cap=horizon_cap)
    fn, ex_args, in_sh = build_fap_round(model, spec, mesh, opts,
                                         optimized=optimized, queue=queue,
                                         wheel=wheel, transport=transport,
                                         exchange=exchange, net=net,
                                         batch=batch, batch_cap=batch_cap,
                                         fanout=fanout, spike_cap=spike_cap,
                                         horizon=horizon, move_cap=move_cap)
    qops = sched.get_queue_ops(queue, ev_cap=ev_cap, wheel=wheel)
    iinj_v = jnp.broadcast_to(jnp.asarray(iinj, jnp.float64), (n,))
    dnet = xc.to_device(net)
    n_carry = 3 if horizon == "incremental" else 0
    # round-invariant args placed once with the build's shardings (the loop
    # then pays the two transport channels only, no per-round resharding)
    static = jax.device_put(
        (dnet.pre, dnet.post, dnet.delay, dnet.w_ampa, dnet.w_gaba, iinj_v)
        + ex_args[10 + n_carry:],
        in_sh[4:10] + in_sh[10 + n_carry:])

    def seed_hcarry(clocks):
        """(horizon, prev boundary clocks, moved ids) seeded from a clock
        vector: a full-recompute horizon (which the incremental chain
        equals bitwise — min is exact), all-zero previous boundary clocks
        (every frontier entry looks moved next round -> extra or full
        recompute, still exact) and sentinel moved ids.  Round 0 and
        elastic resume share this."""
        hor0 = xc.horizon_times(dnet, n, clocks, t_end,
                                horizon_cap=horizon_cap)
        prev0 = jnp.zeros(ex_args[11].shape, jnp.float64)  # boundary clocks
        moved0 = jnp.full(ex_args[12].shape, n // int(np.prod(
            [mesh.shape[a] for a in mesh.axis_names])), jnp.int32)
        return tuple(jax.device_put((hor0, prev0, moved0), in_sh[10:13]))

    jfn = jax.jit(fn, in_shardings=in_sh)
    neuron_ids = jnp.arange(n, dtype=jnp.int32)    # hoisted round constant
    repl = NamedSharding(mesh, P())
    z64 = jnp.zeros((), jnp.int64)

    def init_fn():
        Y = xc.batch_init(model, n)
        sts = jax.vmap(lambda y, i: bdf.reinit(model, 0.0, y, i, opts))(
            Y, iinj_v)
        eq = qops.make(n)
        # placed as the round returns them: an unplaced carry is another
        # input type, and round 0 would compile the round a second time
        sts, eqs = jax.device_put((sts, (eq.t, eq.w_ampa, eq.w_gaba)),
                                  (in_sh[0], tuple(in_sh[1:4])))
        hcarry = seed_hcarry(jnp.zeros((n,), jnp.float64)) if n_carry else ()
        rec = ev.make_spike_record(n, spk_cap)
        return xc.SimCarry(sts, eqs, rec, hcarry,
                           {"n_ev": z64, "n_rs": z64, "dropped": z64,
                            "parcel_bytes": z64,
                            "rounds": jnp.zeros((), jnp.int32)})

    def step_fn(sc):
        eq_t, eq_a, eq_g = sc.eq
        out = jfn(sc.sts, eq_t, eq_a, eq_g, *static[:6], *sc.hcarry,
                  *static[6:])
        (sts, eq_t, eq_a, eq_g, spiked, t_sp, nd, nrs, dropped,
         pbytes) = out[:10]
        rec = ev.record_spikes(sc.rec, neuron_ids, t_sp, spiked)
        c = sc.counters
        return xc.SimCarry(sts, (eq_t, eq_a, eq_g), rec, out[10:], {
            "n_ev": c["n_ev"] + nd, "n_rs": c["n_rs"] + nrs,
            "dropped": c["dropped"] + dropped,
            "parcel_bytes": c["parcel_bytes"] + pbytes,
            "rounds": c["rounds"] + 1})

    def cond_fn(sc):
        return (int(sc.counters["rounds"]) < max_rounds
                and float(sc.sts.t.min()) < t_end - 1e-9
                and not bool(sc.sts.failed.any()))

    # SimCarry-shaped sharding tree for restore: the build's input
    # shardings where they exist, replicated for the host-side leaves
    # (spike record + counters) — the elastic-resume device_put path
    sh_tree = xc.SimCarry(
        in_sh[0], in_sh[1:4],
        jax.tree_util.tree_map(lambda _: repl, ev.make_spike_record(1, 1)),
        tuple(in_sh[10:13]) if n_carry else (),
        {k_: repl for k_ in ("n_ev", "n_rs", "dropped", "parcel_bytes",
                             "rounds")})

    def health_of(sc, t_prev):
        return xc.health_check(
            sc.sts, t_prev, horizon=sc.hcarry[0] if n_carry else None,
            horizon_cap=horizon_cap)

    # layout fingerprint: a resume whose mesh/transport layout changed must
    # reseed the shard-relative hcarry even when its widths coincide
    fingerprint = {"mesh_shape": [int(mesh.shape[a]) for a in mesh.axis_names],
                   "transport": transport, "batch_cap": int(batch_cap),
                   "horizon": horizon} if n_carry else None
    sc, health = xc.run_checkpointed(
        init_fn, step_fn, cond_fn, ckpt_dir=ckpt_dir,
        checkpoint_every=checkpoint_every, resume=resume, keep=ckpt_keep,
        fault=fault, health_of=health_of if watchdog else None,
        max_rollbacks=max_rollbacks, shardings=sh_tree,
        fingerprint=fingerprint,
        reseed=(lambda sc: sc._replace(hcarry=seed_hcarry(sc.sts.t)))
        if n_carry else None)
    sts = sc.sts
    rounds = int(sc.counters["rounds"])
    health["dropped_events"] = int(sc.counters["dropped"])
    res = RunResult(sc.rec, sts.nst.sum(),
                    jnp.asarray(sc.counters["n_ev"], jnp.int32),
                    jnp.asarray(sc.counters["n_rs"], jnp.int32),
                    jnp.asarray(sc.counters["dropped"], jnp.int32),
                    jnp.logical_or(sts.failed.any(),
                                   health["rollback_exhausted"]),
                    sts.zn[:, 0],
                    comm={"parcel_bytes": int(sc.counters["parcel_bytes"]),
                          "rounds": rounds},
                    solver=xc.solver_stats(sts), health=health)
    if pl is not None:
        res = plc.unpermute_result(res, pl)
    return res, rounds
