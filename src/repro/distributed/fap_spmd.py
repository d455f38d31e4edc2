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
             shard-relative post index and the notify clock table); with
             horizon="incremental" the moved clocks are pushed into a
             shard-local in-edge clock table and the horizon is a dense
             fold over it (``exec_common.in_clock_horizon``, as in
             ``exec_fap``)
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
asserted by tests/benchmarks, not assumed.  Each stage of the round runs
under its ``exec_common.ROUND_STAGES`` / ``CHANNEL_STAGES`` scope
(``fap.notify``, ``fap.horizon``, ..., ``fap.exchange``, ``fap.fanout``),
where the benchmark reads device time per stage.

Parcel-cap and queue overflow stay detected-never-silent: every round
adds its ``dropped`` count (queue + transport) to the carry's counters,
which ``run_fap_spmd`` reports as ``RunResult.dropped``.

``build_fap_round`` returns (fn, example_args, in_shardings) so the dry-run
can lower it on the 16x16 and 2x16x16 meshes like any LM cell;
``make_fap_spmd_runner`` wraps the same round as a runner (init, round,
device-side cond), which ``run_fap_spmd`` and the benchmark drive.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import sched
from repro.core import bdf
from repro.core import events as ev
from repro.core import exec_common as xc
from repro.core.cell import CellModel
from repro.core.exec_bsp import make_vardt_advance
from repro.distributed.exchange import (ExchangeSpec, get_transport,
                                        shard_index)


def _byk(x, k: int, n_shards: int = 1):
    """An edge array of ``make_network``'s grouped by-post layout (local
    edge ``j * k + kk`` of each shard's contiguous slice) in each shard's
    by-k layout, the [k, n_local] table row-major: local edge ``j * k +
    kk`` at ``kk * n_local + j``, shard after shard."""
    return x.reshape(n_shards, -1, k).transpose(0, 2, 1).reshape(-1)


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
                    horizon: str = "full", move_cap: int = 0,
                    spk_cap: int = 128):
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

    horizon="incremental" maintains the horizon shard-locally by pushing
    clocks, as ``exec_fap``'s compact round does on one chip.  Each shard
    carries an in-edge clock table: the [K, n_local] table of the clocks
    of its in-edges' pres, by-k (local edge ``j * K + k`` at slot ``k *
    n_local + j``) and stored row-major.  The moved set is (a) the
    *notify frontier* entries of other shards whose gathered clock
    changed this round (compared against the carried previous
    boundary-clock vector — ``sharding.shard_frontier`` tables; at most
    ``move_cap``, by default n_shards * batch_cap) and (b) the
    shard's own lanes that advanced last round (carried compact ids).
    Each moved clock is written into the slots of its out-edges (the
    replicated ``exec_common.out_edge_table``) that land in the shard's
    contiguous global-edge-id slice; the others, and the padding, drop.
    The horizon is then one dense pass min_k(table + delay) clamped at
    t_end and t + horizon_cap: bit-identical to the full scatter-min
    because min is exact.  A moved set over ``move_cap`` (the round after
    a seed, whose previous boundary clocks are zeros) rebuilds the table
    whole from the notify table.  Requires optimized + batch="compact" +
    a sparse-family transport (the frontier tables).  The round then
    carries (in-edge clock table, prev_boundary_clocks, moved_ids) in
    ``SimCarry.hcarry``; ``make_fap_spmd_runner`` seeds them.
    ``SchedStats.horizon_live`` sums, over shards, the batch's lanes and
    the slots the push wrote (all of them in a rebuild).

    Returns ``(fap_round, (carry, static), (carry_sh, static_sh))``:
    ``fap_round(carry, static) -> carry`` advances one round.  ``carry`` is
    the whole round state as one ``exec_common.SimCarry``: the sharded
    BDF states, queue planes ``(t, w_ampa, w_gaba)`` and spike record
    (``spk_cap`` spikes a neuron, sharded by neuron and written inside
    the round), the horizon carry, and the counters ``n_ev``, ``n_rs``,
    ``dropped`` (this round's queue overflow plus sparse-transport parcel
    overflow: detected, never silent), ``parcel_bytes`` (the parcel
    payload each device shipped: the ragged transport's per-round class
    choice made visible, cross-checked against the per-class HLO
    attribution in tests), ``rounds`` and ``stats`` (``SchedStats``:
    ``runnable`` and ``stepped`` summed over shards with ``psum``,
    ``lanes`` the dispatch width of all shards, ``rounds`` the global
    round count).  ``static`` holds the round-invariant arrays: the edge
    list (pre, post, delay, w_ampa, w_gaba), the drive, the transport's
    routing tables and the compact tables.  The example carry holds
    shapes; ``static`` the concrete tables (shapes for the edge list and
    the drive); the shardings are the jit's ``in_shardings``.
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
    # replicated static table (appended to the round args after targs):
    # the out-edge table serves the compact fan-out and the push
    tbl_args, tbl_specs = (), ()
    if fanout == "compact" or incremental:
        tbl_args += (jnp.asarray(xc.out_edge_table(net)),)  # [N, MO], sent. E
        tbl_specs += (P(None, None),)
    if incremental:
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

    def _round_local(sts, eq_t, eq_a, eq_g, rec_t, rec_n, pre_l, delay_l,
                     wa_l, wg_l, iinj, *rest):
        """One scheduler round on this shard's neurons.  All arrays are
        shard-local (the static tables replicated); the ONLY communication
        is the transport's channels (plus the scalar telemetry psums).
        Each stage runs under its ``exec_common`` stage scope."""
        from repro.kernels.event_wheel import ops as ew_ops

        n_carry = 3 if incremental else 0
        carry, rest = rest[:n_carry], rest[n_carry:]
        targs, tbls = rest[:n_targs], rest[n_targs:]
        t_local = sts.t
        n_loc = t_local.shape[0]
        sidx = shard_index(mesh, flat)
        offset = sidx * n_local
        rec = ev.SpikeRecord(rec_t, rec_n, jnp.zeros((), jnp.int32))
        # --- notify: clock exchange (stepping notifications) --------------
        with jax.named_scope("fap.notify"):
            t_table, bnd_new = tp.notify(t_local, *targs)
        # --- horizon + runnable -------------------------------------------
        if incremental:
            tin, prev_bnd, moved_prev = carry
            edge_tbl_r = tbls[0]
            b_gid_flat = targs[1].reshape(-1)              # [n_shards * F]
            sf = b_gid_flat.shape[0]
            k_in, n_slots = spec.k_in, spec.k_in * n_loc

            def _push(tin):
                """Each moved clock into the slots of its out-edges that
                land in this shard (by-k: local edge le = j * K + k sits at
                k * n_loc + j); others, and padding, drop."""
                own = jnp.where(moved_prev < n_loc, moved_prev + offset, n)
                srcs = jnp.concatenate([gids, own])
                vals = jnp.concatenate(
                    [bnd_new[jnp.minimum(mids, sf - 1)],
                     t_local[jnp.minimum(moved_prev, n_loc - 1)]])
                le = edge_tbl_r[jnp.minimum(srcs, n - 1)] - sidx * e_local
                ok = jnp.logical_and((srcs < n)[:, None], jnp.logical_and(
                    le >= 0, le < e_local))
                slots = jnp.where(ok, (le % k_in) * n_loc + le // k_in,
                                  n_slots)
                return xc.push_clocks(tin, slots, vals)

            def _rebuild(_):
                """The whole table from the notify table (the moved set
                overflowed ``move_cap``)."""
                return (t_table[_byk(pre_l, k_in)],
                        jnp.asarray(n_slots, jnp.int64))

            with jax.named_scope("fap.horizon_update"):
                # moved set: frontier clocks that changed since last round
                # (pad slots carry the gid sentinel n -> masked out) plus
                # last round's locally advanced lanes (carried compact ids);
                # this shard's own frontier clocks come with its lanes
                moved_b = jnp.logical_and(bnd_new != prev_bnd, b_gid_flat < n)
                mids, mcnt = ew_ops.compact_ids(moved_b, mcap)
                gids = jnp.where(mids < sf,
                                 b_gid_flat[jnp.minimum(mids, sf - 1)], n)
                gids = jnp.where(jnp.logical_and(gids >= offset,
                                                 gids < offset + n_loc),
                                 n, gids)
                tin, pushed = jax.lax.cond(mcnt <= mcap, _push, _rebuild,
                                           tin)
            prev_bnd = bnd_new
            with jax.named_scope("fap.horizon"):
                horizon = xc.in_clock_horizon(
                    tin, _byk(delay_l, k_in), t_local, spec.t_end,
                    spec.horizon_cap)
                runnable = xc.runnable_mask(t_local, horizon)
        else:
            # the shared scatter-min with the shard-relative post index
            post_rel = jnp.repeat(jnp.arange(n_loc), spec.k_in)
            dloc = xc.DeviceNet(pre_l, post_rel, delay_l, wa_l, wg_l)
            with jax.named_scope("fap.horizon"):
                horizon = xc.horizon_times(dloc, n_loc, t_local, spec.t_end,
                                           t_table=t_table,
                                           horizon_cap=spec.horizon_cap)
                runnable = xc.runnable_mask(t_local, horizon)
        n_runnable = runnable.sum(dtype=jnp.int64)
        # --- advance (dense: all lanes; compact: the shard-local active
        # set, gathered into a fixed [cap] batch and scattered back) -------
        if batch == "compact":
            with jax.named_scope("fap.compact"):
                ids, _ = xc.compact_frontier(runnable, t_local, cap)
            with jax.named_scope("fap.gather"):
                lane_ok = ids < n_loc
                idc = jnp.minimum(ids, n_loc - 1)
                sts_b = xc.gather_lanes(sts, idc)
                t_b_prev = sts_b.t
                eqt_b, eqa_b, eqg_b = eq_t[idc], eq_a[idc], eq_g[idc]
                hor_b, iinj_b = horizon[idc], iinj[idc]
            with jax.named_scope("fap.advance"):
                sts_b, eqt_b, spiked_b, tsp_b, nd, nrs = vadvance(
                    sts_b, eqt_b, eqa_b, eqg_b, hor_b, lane_ok, iinj_b)
            with jax.named_scope("fap.scatter"):
                sts = xc.scatter_lanes(sts, sts_b, ids)
                eq_t = xc.scatter_at(eq_t, ids, eqt_b)
                spiked = xc.scatter_at(jnp.zeros((n_loc,), bool), ids,
                                       spiked_b)
                t_sp = xc.scatter_at(jnp.zeros((n_loc,)), ids, tsp_b)
                rec = ev.record_spikes(rec, ids, tsp_b, spiked_b)
            if incremental:
                with jax.named_scope("fap.horizon_update"):
                    moved = jnp.logical_and(lane_ok, sts_b.t != t_b_prev)
                    moved_prev = jnp.where(moved, ids, n_loc).astype(
                        jnp.int32)
            n_stepped = lane_ok.sum(dtype=jnp.int64)
            if incremental:
                # the batch's lanes and the table slots the push wrote
                live = jax.lax.psum(n_stepped + pushed, flat)
        else:
            with jax.named_scope("fap.advance"):
                sts, eq_t, spiked, t_sp, nd, nrs = vadvance(
                    sts, eq_t, eq_a, eq_g, horizon, runnable, iinj)
            with jax.named_scope("fap.scatter"):
                rec = ev.record_spikes(rec, jnp.arange(n_loc), t_sp, spiked)
            n_stepped = n_runnable
        # --- parcel exchange ----------------------------------------------
        with jax.named_scope("fap.exchange"):
            spiked_all, tsp_all, pdrop, pbytes = tp.exchange(spiked, t_sp,
                                                             *targs)

        # --- insert (shard-local): dense = grouped scan of all E/n_shards
        # in-edges; compact = gather only the spiking set's out-edges that
        # land in this shard's contiguous global-edge-id slice ------------
        def _ins_dense(eq_t, eq_a, eq_g):
            valid = spiked_all[pre_l]
            t_ev = tsp_all[pre_l] + delay_l
            return _insert_byk(eq_t, eq_a, eq_g, t_ev, wa_l, wg_l, valid)

        with jax.named_scope("fap.fanout"):
            if fanout == "compact":
                edge_tbl_r = tbls[0]

                def _ins_compact(eq_t, eq_a, eq_g):
                    ids_s, eids, _ = ew_ops.compact_gather(
                        spiked_all, edge_tbl_r, s_cap, fill=E)
                    idc_s = jnp.minimum(ids_s, n - 1)
                    le = eids - sidx * e_local
                    ok = jnp.logical_and(
                        (ids_s < n)[:, None],
                        jnp.logical_and(eids < E, jnp.logical_and(
                            le >= 0, le < e_local)))
                    lec = jnp.clip(le, 0, e_local - 1)
                    tgt = lec // spec.k_in      # shard-relative post (grouped)
                    t_ev = tsp_all[idc_s][:, None] + delay_l[lec]
                    eq = qops.wrap(eq_t, eq_a, eq_g,
                                   jnp.zeros((), jnp.int32))
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
        over = jax.lax.psum(rec.overflow, flat)
        n_runnable = jax.lax.psum(n_runnable, flat)
        n_stepped = jax.lax.psum(n_stepped, flat)
        out = (sts, eq.t, eq.w_ampa, eq.w_gaba, rec.times, rec.count, over,
               nd, nrs, dropped, pbytes, n_runnable, n_stepped)
        if incremental:
            out += (live, tin, prev_bnd, moved_prev)
        return out

    # carried-extra specs: in-edge clock table [K * N] sharded (each
    # shard's [K, n_local] by-k table), boundary-clock vector replicated
    # (the all_gather output IS replicated), moved ids [S*cap] sharded
    # (each shard's own compact batch)
    carry_specs = (P(flat), P(None), P(flat)) if incremental else ()
    # lanes dispatched a round, all shards together
    lanes = n_shards * cap if batch == "compact" else n

    def fap_round(sc, static):
        pre, post, delay, w_a, w_g, iinj = static[:6]
        eq_t, eq_a, eq_g = sc.eq
        rec = sc.rec
        if optimized:
            # per-leaf specs: leading neuron dim sharded over every axis
            sts_specs = jax.tree_util.tree_map(
                lambda leaf: P(flat, *([None] * (leaf.ndim - 1))), sc.sts)
            n2 = P(flat, None)
            fn_l = jax.shard_map(
                _round_local, mesh=mesh,
                in_specs=(sts_specs, n2, n2, n2, n2, P(flat), P(flat),
                          P(flat), P(flat), P(flat), P(flat)) + carry_specs
                + tp.in_specs + tbl_specs,
                out_specs=(sts_specs, n2, n2, n2, n2, P(flat))
                + (P(),) * 7 + ((P(),) if incremental else ())
                + carry_specs,
                check_vma=False)
            (sts, eq_t, eq_a, eq_g, rec_t, rec_n, over, nd, nrs, dropped,
             pbytes, n_runnable, n_stepped, *hc) = fn_l(
                sc.sts, eq_t, eq_a, eq_g, rec.times, rec.count, pre, delay,
                w_a, w_g, iinj, *sc.hcarry, *static[6:])
            rec = ev.SpikeRecord(rec_t, rec_n, rec.overflow + over)
            if incremental:
                live, *hc = hc
        else:
            t_clock = sc.sts.t
            dnet = xc.DeviceNet(pre, post, delay, w_a, w_g)
            horizon = xc.horizon_times(dnet, n, t_clock, spec.t_end,
                                       horizon_cap=spec.horizon_cap)
            runnable = xc.runnable_mask(t_clock, horizon)
            sts, eq_t, spiked, t_sp, nd, nrs = vadvance(
                sc.sts, eq_t, eq_a, eq_g, horizon, runnable, iinj)
            rec = ev.record_spikes(rec, jnp.arange(n), t_sp, spiked)
            valid = spiked[pre]
            t_ev = t_sp[pre] + delay
            # the global path honours the runtime `post` array (arbitrary
            # edge order); both queue impls insert to explicit targets
            # sort-free or not per their contract
            eq = qops.wrap(eq_t, eq_a, eq_g, jnp.zeros((), jnp.int32))
            eq = qops.insert(eq, post, t_ev, w_a, w_g, valid)
            eq_t, eq_a, eq_g = eq.t, eq.w_ampa, eq.w_gaba
            nd, nrs, dropped = nd.sum(), nrs.sum(), eq.dropped
            pbytes = jnp.zeros((), jnp.int32)
            n_runnable = n_stepped = runnable.sum(dtype=jnp.int64)
            hc = ()
        c = sc.counters
        st = c["stats"]
        stats = st._replace(runnable=st.runnable + n_runnable,
                            stepped=st.stepped + n_stepped,
                            lanes=st.lanes + lanes, rounds=st.rounds + 1)
        if incremental:
            stats = stats._replace(horizon_live=st.horizon_live + live)
        return xc.SimCarry(sts, (eq_t, eq_a, eq_g), rec, tuple(hc), {
            "n_ev": c["n_ev"] + nd, "n_rs": c["n_rs"] + nrs,
            "dropped": c["dropped"] + dropped,
            "parcel_bytes": c["parcel_bytes"] + pbytes,
            "rounds": c["rounds"] + 1, "stats": stats})

    # ---- example args (ShapeDtypeStructs) and shardings -------------------
    f8, i4, i8 = jnp.float64, jnp.int32, jnp.int64

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    sts = jax.eval_shape(
        lambda: jax.vmap(lambda i: bdf.reinit(
            model, 0.0, model.init_state(), i, opts))(jnp.zeros((n,), f8)))
    hcarry = ()
    if incremental:
        hcarry = (sds((E,), f8),                       # in-edge clocks
                  sds((sf_len,), f8),                  # prev boundary clocks
                  sds((n_shards * cap,), i4))          # moved ids
    carry = xc.SimCarry(
        sts, (sds((n, qcap), f8),) * 3,                # queue t, w_a, w_g
        ev.SpikeRecord(sds((n, spk_cap), f8), sds((n,), i4), sds((), i4)),
        hcarry, {"n_ev": sds((), i8), "n_rs": sds((), i8),
                 "dropped": sds((), i8), "parcel_bytes": sds((), i8),
                 "rounds": sds((), i4),
                 "stats": jax.eval_shape(xc.SchedStats.zeros)})
    static = (sds((E,), i4), sds((E,), i4), sds((E,), f8), sds((E,), f8),
              sds((E,), f8), sds((n,), f8)) \
        + (tp.example_args if tp is not None else ()) + tbl_args

    def named(spec_):
        return NamedSharding(mesh, spec_)

    rows, repl = named(P(flat)), named(P())
    sts_sh = jax.tree_util.tree_map(
        lambda leaf: named(P(flat, *([None] * (leaf.ndim - 1))))
        if leaf.ndim >= 1 else repl, sts)
    n2 = named(P(flat, None))
    carry_sh = xc.SimCarry(
        sts_sh, (n2,) * 3, ev.SpikeRecord(n2, rows, repl),
        tuple(named(s_) for s_ in carry_specs),
        jax.tree_util.tree_map(lambda _: repl, carry.counters))
    static_sh = (rows,) * 6 + (tp.shardings if tp is not None else ()) \
        + tuple(named(s_) for s_ in tbl_specs)
    fap_round.batch_cap, fap_round.spike_cap = cap, s_cap    # caps in force
    fap_round.move_cap = mcap if incremental else 0
    return fap_round, (carry, static), (carry_sh, static_sh)


def make_fap_spmd_runner(model: CellModel, net, iinj, t_end: float, mesh,
                         opts: bdf.BDFOptions = bdf.BDFOptions(),
                         optimized: bool = True, queue: str = "dense",
                         wheel: sched.WheelSpec = sched.WheelSpec(),
                         transport: str = "allgather",
                         exchange: ExchangeSpec = ExchangeSpec(),
                         ev_cap: int = 32, horizon_cap: float = 2.0,
                         max_rounds: int = 1_000_000, spk_cap: int = 128,
                         batch: str = "dense", batch_cap: int = 0,
                         fanout: str = "dense", spike_cap: int = 0,
                         horizon: str = "full", move_cap: int = 0):
    """The SPMD round as a runner, the surface ``exec_fap``'s runners give:
    ``run.init_carry()`` (round 0, placed on the mesh), ``run.round_body``
    (carry -> carry, jittable: one round of ``build_fap_round``, spikes
    recorded inside it), ``run.cond`` (on the device: GVT < t_end, no
    solver failure, fewer than ``max_rounds`` rounds) and ``run.pack``
    (carry -> ``SimCarry``; the carry already is one).  Calling
    ``run(...)`` drives ``round_body`` host-stepped through
    ``exec_common.run_checkpointed`` (checkpoints, faults, the watchdog;
    see ``run_fap_spmd``) and returns (RunResult, rounds).

    ``net`` must have ``make_network``'s grouped by-post edge layout; the
    keyword arguments are ``build_fap_round``'s (``spk_cap``: spikes
    recorded a neuron).  The round-invariant arrays (``run.static``) are
    placed on the mesh once; ``round_body`` closes over them and
    ``run.round_with(carry, static)`` takes them as arguments.
    ``run.batch_cap`` / ``run.spike_cap`` / ``run.move_cap`` are the caps
    in force, ``run.n_local`` the neurons a shard holds.  With
    horizon="incremental", ``run.horizon_of(carry)`` is the horizon the
    carry's in-edge clock table implies at the carry's clocks and
    ``run.reseed(carry)`` seeds the horizon carry anew from the clocks
    (what a resume onto another layout does)."""
    n = int(net.n)
    k = sched.grouped_k(net)
    if k is None:
        raise ValueError("the SPMD round needs make_network's grouped by-post "
                         "edge layout")
    incremental = horizon == "incremental"
    n_shards = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    spec = PaperNeuroSpec(n_neurons=n, k_in=k, ev_cap=ev_cap, t_end=t_end,
                          horizon_cap=horizon_cap)
    fn, (ex, ex_static), (carry_sh, static_sh) = build_fap_round(
        model, spec, mesh, opts, optimized=optimized, queue=queue,
        wheel=wheel, transport=transport, exchange=exchange, net=net,
        batch=batch, batch_cap=batch_cap, fanout=fanout,
        spike_cap=spike_cap, horizon=horizon, move_cap=move_cap,
        spk_cap=spk_cap)
    qops = sched.get_queue_ops(queue, ev_cap=ev_cap, wheel=wheel)
    dnet = xc.to_device(net)
    iinj_v = jnp.broadcast_to(jnp.asarray(iinj, jnp.float64), (n,))
    # round-invariant arrays placed once with the round's shardings (the
    # loop then pays the two transport channels only, no resharding)
    static = jax.device_put(
        (dnet.pre, dnet.post, dnet.delay, dnet.w_ampa, dnet.w_gaba, iinj_v)
        + tuple(ex_static[6:]), static_sh)
    n_local = n // n_shards

    def seed_hcarry(clocks, static):
        """(in-edge clock table, prev boundary clocks, moved ids) seeded
        from a clock vector: every shard's table gathered whole, all-zero
        previous boundary clocks (every frontier entry whose clock is not
        0 looks moved next round -> a push or a rebuild of clocks the
        table already holds, still exact) and sentinel moved ids.  Round 0
        and elastic resume share this."""
        return (clocks[_byk(static[0], k, n_shards)],
                jnp.zeros(ex.hcarry[1].shape, jnp.float64),
                jnp.full(ex.hcarry[2].shape, n_local, jnp.int32))

    @jax.jit
    def _horizon_of(tin, t_clock, delay):
        fold = functools.partial(xc.in_clock_horizon, t_end=t_end,
                                 horizon_cap=horizon_cap)
        return jax.vmap(fold)(tin.reshape(n_shards, -1),
                              _byk(delay, k, n_shards).reshape(n_shards, -1),
                              t_clock.reshape(n_shards, -1)).reshape(-1)

    def horizon_of(sc):
        """The dependency horizon an incremental carry's in-edge clock
        table implies at the carry's clocks."""
        return _horizon_of(sc.hcarry[0], sc.sts.t, static[2])

    def _init(static):
        iv = static[5]
        Y = xc.batch_init(model, n)
        sts = jax.vmap(lambda y, i: bdf.reinit(model, 0.0, y, i, opts))(
            Y, iv)
        eq = qops.make(n)
        hcarry = seed_hcarry(jnp.zeros((n,), jnp.float64), static) \
            if incremental else ()
        z64 = jnp.zeros((), jnp.int64)
        return xc.SimCarry(sts, (eq.t, eq.w_ampa, eq.w_gaba),
                           ev.make_spike_record(n, spk_cap), hcarry,
                           {"n_ev": z64, "n_rs": z64, "dropped": z64,
                            "parcel_bytes": z64,
                            "rounds": jnp.zeros((), jnp.int32),
                            "stats": xc.SchedStats.zeros()})

    # placed as the round returns them: an unplaced carry is another input
    # type, and the first round would compile the round a second time
    jinit = jax.jit(_init, out_shardings=carry_sh)
    jseed = jax.jit(seed_hcarry, out_shardings=carry_sh.hcarry)

    def reseed(sc):
        """``sc`` with its horizon carry seeded anew from its clocks."""
        return sc._replace(hcarry=jseed(sc.sts.t, static))

    def init_carry():
        return jinit(static)

    def round_body(sc):
        return fn(sc, static)

    def cond(sc):
        return jnp.logical_and(
            sc.sts.t.min() < t_end - 1e-9,
            jnp.logical_and(sc.counters["rounds"] < max_rounds,
                            ~sc.sts.failed.any()))

    jround = jcond = None

    def run(checkpoint_every: int = 0, ckpt_dir=None, resume: bool = False,
            fault=None, watchdog: bool = True, max_rollbacks: int = 2,
            ckpt_keep: int = 3):
        """Host-stepped drive of ``round_body`` (one compile, reused by
        every call): ``run_fap_spmd``'s robustness knobs."""
        from repro.core.exec_bsp import RunResult
        nonlocal jround, jcond
        if jround is None:
            jround = jax.jit(fn, in_shardings=(carry_sh, static_sh))
            jcond = jax.jit(cond)

        def health_of(sc, t_prev):
            return xc.health_check(
                sc.sts, t_prev,
                horizon=horizon_of(sc) if incremental else None,
                horizon_cap=horizon_cap)

        # layout fingerprint: a resume whose mesh/transport layout changed
        # must reseed the shard-relative hcarry even when its widths
        # coincide, and so must a snapshot of the earlier carry, which held
        # the horizon [N] itself (the same shape where k_in is 1)
        fingerprint = {"mesh_shape": [int(mesh.shape[a])
                                      for a in mesh.axis_names],
                       "transport": transport, "batch_cap": int(batch_cap),
                       "horizon": horizon,
                       "hcarry": "in_edge_clocks"} if incremental else None
        sc, health = xc.run_checkpointed(
            init_carry, lambda sc: jround(sc, static),
            lambda sc: bool(jcond(sc)), ckpt_dir=ckpt_dir,
            checkpoint_every=checkpoint_every, resume=resume,
            keep=ckpt_keep, fault=fault,
            health_of=health_of if watchdog else None,
            max_rollbacks=max_rollbacks, shardings=carry_sh,
            fingerprint=fingerprint, reseed=reseed if incremental else None)
        sts = sc.sts
        rounds = int(sc.counters["rounds"])
        health["dropped_events"] = int(sc.counters["dropped"])
        # a run cut by max_rounds before t_end says so
        health["max_rounds_reached"] = bool(
            rounds >= max_rounds and float(sts.t.min()) < t_end - 1e-9)
        res = RunResult(sc.rec, sts.nst.sum(),
                        jnp.asarray(sc.counters["n_ev"], jnp.int32),
                        jnp.asarray(sc.counters["n_rs"], jnp.int32),
                        jnp.asarray(sc.counters["dropped"], jnp.int32),
                        jnp.logical_or(sts.failed.any(),
                                       health["rollback_exhausted"]),
                        sts.zn[:, 0], sched=sc.counters["stats"],
                        comm={"parcel_bytes": int(sc.counters[
                            "parcel_bytes"]), "rounds": rounds},
                        solver=xc.solver_stats(sts), health=health)
        return res, rounds

    run.init_carry = init_carry
    run.round_body = round_body
    run.round_with = fn
    run.cond = cond
    run.pack = lambda sc: sc
    run.static = static
    run.batch_cap, run.spike_cap = fn.batch_cap, fn.spike_cap
    run.move_cap = fn.move_cap
    run.n_local = n_local
    if incremental:
        run.horizon_of = horizon_of   # incremental carry -> its horizon
        run.reseed = reseed
    return run


def run_fap_spmd(model: CellModel, net, iinj, t_end: float, mesh,
                 opts: bdf.BDFOptions = bdf.BDFOptions(),
                 optimized: bool = True, queue: str = "dense",
                 wheel: sched.WheelSpec = sched.WheelSpec(),
                 transport: str = "allgather",
                 exchange: ExchangeSpec = ExchangeSpec(),
                 ev_cap: int = 32, horizon_cap: float = 2.0,
                 max_rounds: int = 1_000_000, spk_cap: int = 128,
                 placement=None, batch: str = "dense", batch_cap: int = 0,
                 fanout: str = "dense", spike_cap: int = 0,
                 horizon: str = "full", move_cap: int = 0,
                 checkpoint_every: int = 0, ckpt_dir=None,
                 resume: bool = False, fault=None, watchdog: bool = True,
                 max_rollbacks: int = 2, ckpt_keep: int = 3):
    """Drive the SPMD round to t_end on a concrete network: the round of
    ``make_fap_spmd_runner``, host-stepped, each round recording its spikes
    and accumulating its telemetry into the standard ``RunResult``
    (dropped = queue + parcel overflow — detected, never silent).  Returns
    (RunResult, rounds).  A run that ``max_rounds`` (by default the
    single-host runners' 1,000,000) cuts before ``t_end`` reports
    ``health["max_rounds_reached"]``.

    placement: optional ``distributed.placement.Placement`` (or a method
    name for ``compute_placement``) — the neuron-id relabeling is applied
    before sharding and inverted on the returned spike record / final
    state, so results stay in the caller's neuron order while the notify
    frontier and parcel routing shrink with the realized locality.
    Checkpoints store the *placed* order: resume with the same placement.

    batch / batch_cap / fanout / spike_cap / horizon / move_cap: forwarded
    to ``build_fap_round`` — "compact" runs the shard-local advance
    (delivery) on the compacted runnable (spiking) set only, and
    horizon="incremental" carries each shard's in-edge clock table across
    rounds and pushes only the moved clocks into it.  ``RunResult.sched``
    holds the round's ``SchedStats`` (summed over shards);
    ``RunResult.comm`` the parcel bytes each device shipped, summed over
    rounds — with the ragged transport the per-round class choice made
    visible.

    Preemption tolerance (exec_common.run_checkpointed):
    checkpoint_every=k snapshots the full round state (``SimCarry``) into
    ckpt_dir every k rounds with the atomic-commit protocol; resume=True
    restarts from the latest complete checkpoint, event-for-event
    identical to the uninterrupted run.  Elastic resume onto a different
    mesh shape works transparently: only the mesh-shaped horizon-carry
    leaves are reseeded from the clocks (a full rebuild, which the pushed
    table equals bitwise), everything else restores through
    ``restore_checkpoint(shardings=)``.  fault: a
    ``checkpoint.FaultPlan`` for kill/poison injection; watchdog (on by
    default here) runs the per-round ``exec_common.health_check`` and
    quarantine-and-rollback on non-finite state (bounded by
    max_rollbacks, then ``RunResult.failed``); telemetry lands on
    ``RunResult.health``.
    """
    from repro.distributed import placement as plc

    pl = None
    if placement is not None:
        n_shards = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
        pl = placement if isinstance(placement, plc.Placement) else \
            plc.compute_placement(net, n_shards, method=placement)
        net, iinj = plc.place_inputs(net, iinj, pl)
    run = make_fap_spmd_runner(
        model, net, iinj, t_end, mesh, opts, optimized=optimized,
        queue=queue, wheel=wheel, transport=transport, exchange=exchange,
        ev_cap=ev_cap, horizon_cap=horizon_cap, max_rounds=max_rounds,
        spk_cap=spk_cap, batch=batch, batch_cap=batch_cap, fanout=fanout,
        spike_cap=spike_cap, horizon=horizon, move_cap=move_cap)
    res, rounds = run(checkpoint_every=checkpoint_every, ckpt_dir=ckpt_dir,
                      resume=resume, fault=fault, watchdog=watchdog,
                      max_rollbacks=max_rollbacks, ckpt_keep=ckpt_keep)
    if pl is not None:
        res = plc.unpermute_result(res, pl)
    return res, rounds
