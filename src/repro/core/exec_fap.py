"""Fully-Asynchronous Parallel execution models (the paper's contribution).

``make_fap_fixed_runner``  — method 1c: fixed-timestep FAP from the authors'
previous work [2]: per-neuron clocks advance to the pairwise dependency
horizon; no global barrier.

``make_fap_vardt_runner`` — method 2c (THIS paper, Fig. 1d / Fig. 4 right):
non-speculative scheduled variable-timestep stepping.  Each round:

  1. horizon[i] = min over in-edges (t[pre] + delay)   (stepping-notification
     map; scatter-min over the static edge list — DESIGN.md §3),
  2. the *earliest neurons step next*: every neuron strictly behind its
     horizon advances (optionally restricted to the K earliest — the
     scheduler knob), each by its own variable-order variable-step BDF,
     clamped at min(horizon, next event) => exhaustive, never speculative,
  3. spikes fan out as events (t_spike + delay) into destination queues.

Event grouping (eg_window = dt/2 or dt) reproduces the paper's 2c variants.

The conservative-lookahead argument of the paper holds here: delays are
>= min_delay > 0, so the globally earliest neuron always has
horizon > t — every round makes progress and no deadlock or backstepping can
occur.  ``tests/test_property_fap.py`` checks this invariant by construction.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import sched
from repro.core import bdf
from repro.core import events as ev
from repro.core import exec_common as xc
from repro.core.cell import CellModel
from repro.core.exec_bsp import EV_CAP, SPK_CAP, RunResult, make_vardt_advance
from repro.core.fixed_step import make_stepper
from repro.core.network import Network
from repro.kernels.event_wheel import ops as ew_ops


def make_fap_fixed_runner(model: CellModel, net: Network, iinj, t_end: float,
                          method: str = "cnexp", dt: float = 0.025,
                          round_cap_steps: int = 16, ev_cap: int = EV_CAP,
                          max_rounds: int = 2_000_000, queue: str = "dense",
                          wheel: sched.WheelSpec = sched.WheelSpec(),
                          fanout: str = "dense", spike_cap: int = 0):
    """Fixed-step FAP (method 1c).  Returns a nullary jitted runner."""
    n = net.n
    dnet = xc.to_device(net)
    qops = sched.get_queue_ops(queue, ev_cap=ev_cap, wheel=wheel)
    qinsert = sched.edge_insert(qops, net)
    spike_ins = xc.make_spike_insert(net, dnet, qops, qinsert, fanout,
                                     spike_cap)
    step = make_stepper(model, method, dt)
    vstep = jax.vmap(step)
    iinj_v = jnp.broadcast_to(jnp.asarray(iinj, jnp.float64), (n,))
    neuron_ids = jnp.arange(n, dtype=jnp.int32)     # hoisted round constant
    n_total_steps = int(round(t_end / dt))

    def round_body(carry):
        Y, k, eq, rec, n_ev, n_st, rounds = carry
        t_clock = k * dt
        horizon = xc.horizon_times(dnet, n, t_clock, t_end)
        # whole fixed steps available below the horizon, capped per round
        n_adv = jnp.clip(jnp.floor((horizon - t_clock) / dt + 1e-9).astype(jnp.int32),
                         0, round_cap_steps)
        spiked_r = jnp.zeros((n,), bool)
        t_sp_r = jnp.zeros((n,))

        def inner(j, c):
            Y, k, eq, rec, n_ev, n_st, spiked_r, t_sp_r = c
            act = j < n_adv
            t_j = k * dt
            eq2, wa, wg, cnt = qops.deliver_until(eq, jnp.where(act, t_j + dt, -jnp.inf))
            Y2 = jax.vmap(model.apply_event)(Y, wa, wg)
            v_prev = Y2[:, model.idx_vsoma]
            Y2 = vstep(Y2, iinj_v)
            sp, tsp = xc.detect_spikes(v_prev, Y2[:, model.idx_vsoma], t_j, t_j + dt)
            sp = jnp.logical_and(sp, act)
            Y = jnp.where(act[:, None], Y2, Y)
            k = jnp.where(act, k + 1, k)
            spiked_r = jnp.logical_or(spiked_r, sp)
            t_sp_r = jnp.where(sp, tsp, t_sp_r)
            rec = ev.record_spikes(rec, neuron_ids, tsp, sp)
            return (Y, k, eq2, rec, n_ev + cnt.sum(dtype=jnp.int32), n_st + act.sum(dtype=jnp.int32), spiked_r, t_sp_r)

        Y, k, eq, rec, n_ev, n_st, spiked_r, t_sp_r = jax.lax.fori_loop(
            0, round_cap_steps, inner,
            (Y, k, eq, rec, n_ev, n_st, spiked_r, t_sp_r))
        eq = spike_ins(eq, spiked_r, t_sp_r)
        return Y, k, eq, rec, n_ev, n_st, rounds + 1

    def cond(carry):
        _, k, _, _, _, _, rounds = carry
        return jnp.logical_and((k.min() < n_total_steps), rounds < max_rounds)

    @jax.jit
    def run():
        Y = xc.batch_init(model, n)
        eq = qops.make(n)
        rec = ev.make_spike_record(n, SPK_CAP)
        z = jnp.zeros((), jnp.int32)
        Y, k, eq, rec, n_ev, n_st, rounds = jax.lax.while_loop(
            cond, round_body, (Y, jnp.zeros((n,), jnp.int32), eq, rec, z, z, z))
        return RunResult(rec, n_st, n_ev, z, eq.dropped,
                         jnp.zeros((), bool), Y), rounds

    return run


def make_fap_vardt_runner(model: CellModel, net: Network, iinj, t_end: float,
                          opts: bdf.BDFOptions = bdf.BDFOptions(),
                          eg_window: float = 0.0, horizon_cap: float = 2.0,
                          k_select: int = 0, step_budget: int = 12,
                          ev_cap: int = EV_CAP, max_rounds: int = 1_000_000,
                          queue: str = "dense",
                          wheel: sched.WheelSpec = sched.WheelSpec(),
                          select: str = "sort", horizon_impl: str = "scatter",
                          n_bisect: int = 48, batch: str = "dense",
                          batch_cap=0, fanout: str = "dense",
                          spike_cap: int = 0, probe_t: float = 5.0):
    """Variable-step FAP (method 2c, the paper's reference method).

    eg_window: 0 -> precise delivery (2c-);  dt/2 or dt -> grouped variants.
    k_select:  0 -> all runnable neurons advance each round; K>0 restricts to
               the K earliest (the explicit scheduler of paper §2.4).
    horizon_cap bounds per-round advancement (ms) so one spike per neuron per
    round is guaranteed (ISI >> cap at all five regimes).
    queue:        "dense" (argsort slot queue) or "wheel" (bucketed event
                  wheel, O(E) scatter insert — repro.sched).
    select:       "sort" (kth via jnp.sort) or "threshold" (bisection on
                  counts — no sort primitive in the round's jaxpr).
    horizon_impl: "scatter" (edge scatter-min) or "fused" (Pallas kernel
                  over the static by-post layout — kernels/event_wheel).
    batch:        "dense" vmaps the step machinery over all N neurons every
                  round; "compact" compacts the runnable mask into a
                  gather-id list, advances only a fixed-size [batch_cap]
                  batch and scatters results back — per-round stepping
                  cost O(batch_cap * step_budget) instead of
                  O(N * step_budget).  When the frontier overflows
                  batch_cap the earliest-clock neurons are kept
                  (``select_threshold`` bisection; the globally earliest
                  neuron is always included, preserving the conservative-
                  lookahead progress argument) and overflowed neurons
                  roll to the next round.  batch_cap <= 0 means N;
                  batch_cap="auto" runs a short dense probe
                  (min(t_end, probe_t) ms) and picks the cap from the
                  measured frontier occupancy
                  (``exec_common.auto_batch_cap``; the chosen value is
                  exposed as ``run.batch_cap``) — one extra compile.
                  Two further compact-only structural savings keep the
                  round ~flat in N at fixed cap: the O(E) fan-out/insert
                  runs under a ``lax.cond`` (a semantic no-op on
                  spike-free rounds, the common case off the burst
                  regimes), and with the scatter horizon on a grouped net
                  the dependency horizon is maintained *incrementally*:
                  the carry holds the in-edge clock table ``tin``, the
                  [K, N] table ``t[pre_byk]`` stored row-major as f64[K *
                  N] (flat, so the TPU's scatter needs no relayout of
                  it), each round pushes the batch's moved clocks into
                  their out-edge slots (one scatter of batch_cap *
                  max_out_degree indices), and the horizon is one
                  dense pass min_k(tin + delay) clamped at t_end and t +
                  cap — bit-identical to the full scatter-min because min
                  is exact in fp.  ``run.horizon_of(carry)`` derives it.
    fanout:       "dense" fans every spike over all E edges; "compact"
                  gathers only the <= spike_cap spiking lanes' out-edges
                  (static ``out_edge_table`` rows via
                  ``ops.compact_gather``) and inserts that fixed
                  [spike_cap * k_out] batch — bursty regimes stop paying
                  O(E) per spiking round.  More spikes than spike_cap
                  fall back to the dense branch (identical events,
                  never a drop).  spike_cap <= 0 defaults to the batch
                  cap under batch="compact" (stepped lanes bound spikes,
                  so the fallback never fires) and min(N, 256) otherwise.
                  spike_cap="auto" sizes the cap from the probe run's
                  spike-rate telemetry (``exec_common.auto_spike_cap``;
                  exposed as ``run.spike_cap``) — the probe is shared
                  with batch_cap="auto" when both are requested.

    The returned nullary runner also exposes ``run.init_carry`` /
    ``run.round_body`` / ``run.cond`` so benchmarks can drive and time
    single scheduler rounds.
    """
    n = net.n
    if batch not in ("dense", "compact"):
        raise ValueError(f"unknown batch mode {batch!r}")
    if batch_cap == "auto" or spike_cap == "auto":
        # one dense probe run serves both auto caps
        probe = make_fap_vardt_runner(
            model, net, iinj, min(t_end, probe_t), opts=opts,
            eg_window=eg_window, horizon_cap=horizon_cap,
            k_select=k_select, step_budget=step_budget, ev_cap=ev_cap,
            max_rounds=max_rounds, queue=queue, wheel=wheel, select=select,
            horizon_impl=horizon_impl, n_bisect=n_bisect)
        pres, _ = probe()
        if batch_cap == "auto":
            batch_cap = xc.auto_batch_cap(pres.sched, n)
        if spike_cap == "auto":
            spike_cap = xc.auto_spike_cap(pres.rec, pres.sched, n)
    cap = n if batch_cap <= 0 else min(int(batch_cap), n)
    s_cap = spike_cap if spike_cap > 0 else \
        (cap if batch == "compact" else min(n, 256))
    dnet = xc.to_device(net)
    iinj_v = jnp.broadcast_to(jnp.asarray(iinj, jnp.float64), (n,))
    neuron_ids = jnp.arange(n, dtype=jnp.int32)     # hoisted round constant
    advance = make_vardt_advance(model, opts, eg_window, step_budget)
    vadvance = jax.vmap(advance)
    qops = sched.get_queue_ops(queue, ev_cap=ev_cap, wheel=wheel)
    qinsert = sched.edge_insert(qops, net)
    if select not in ("sort", "threshold"):
        raise ValueError(f"unknown select {select!r}")
    if horizon_impl == "fused":
        pre_byk, delay_byk = ew_ops.by_post_layout(net)
    elif horizon_impl != "scatter":
        raise ValueError(f"unknown horizon_impl {horizon_impl!r}")
    # incremental horizon maintenance: compact + scatter impl + grouped net
    incremental = (batch == "compact" and horizon_impl == "scatter"
                   and sched.grouped_k(net) is not None)
    edge_tbl = None
    if incremental:
        pre_byk, delay_byk = ew_ops.by_post_layout(net)
        # one grouping pass serves the push ([N, MO] slots of the in-edge
        # clock table) and the compact fan-out's edge table
        _, edge_np = xc.out_tables(net)
        edge_slot = jnp.asarray(xc.in_edge_slots(edge_np, n,
                                                 pre_byk.shape[0]))
        n_slots = int(pre_byk.size)
        delay_flat = delay_byk.reshape(-1)
        if fanout == "compact":
            edge_tbl = edge_np

    def _horizon_of(tin, t_clock):
        """The dependency horizon from the in-edge clock table: the same
        adds and exact mins as the full scatter-min, so bitwise equal.
        The min over K folds the table's K rows elementwise."""
        cand = tin + delay_flat
        hor = cand[:n]
        for k in range(1, pre_byk.shape[0]):
            hor = jnp.minimum(hor, cand[k * n:(k + 1) * n])
        hor = jnp.minimum(hor, t_end)
        return jnp.minimum(hor, t_clock + horizon_cap)

    def _push_clocks(tin, idc, moved, t_b):
        """Write each moved lane's clock into its out-edge slots of ``tin``
        (each edge has one pre, so no two lanes write one slot); padding
        and lanes that did not move point past the table and drop.
        Returns the table and the number of slots written."""
        slots = jnp.where(moved[:, None], edge_slot[idc], n_slots)
        vals = jnp.broadcast_to(t_b[:, None], slots.shape)
        return (tin.at[slots].set(vals, mode="drop"),
                (slots < n_slots).sum(dtype=jnp.int64))

    spike_ins = xc.make_spike_insert(net, dnet, qops, qinsert, fanout, s_cap,
                                     edge_table=edge_tbl)

    def _insert_spikes(eq, spiked_b, tsp_b, ids):
        spiked = xc.scatter_at(jnp.zeros((n,), bool), ids, spiked_b)
        t_sp = xc.scatter_at(jnp.zeros((n,)), ids, tsp_b)
        return spike_ins(eq, spiked, t_sp)

    def _round(carry, iinj_r, active=None, k_qos=None):
        """One scheduler round.  ``iinj_r`` is the per-neuron stimulus as a
        traced argument (the legacy path closes over the construction-time
        value — identical jaxpr); ``active``/``k_qos`` are the multi-tenant
        serving hooks (``repro.serve``): a scalar bool that masks the whole
        lane out of the round (a quarantined or idle tenant — the round is
        then a semantic no-op on its state, which is what makes the
        tenant's trajectory independent of the service's activity
        schedule) and a traced earliest-``k`` frontier restriction (the
        per-tenant QoS cap; 0 = unlimited).  Both default to None, which
        traces nothing extra — the single-tenant runners are untouched."""
        if incremental:
            sts, eq, rec, tin, n_ev, n_rs, stats, rounds = carry
        else:
            sts, eq, rec, n_ev, n_rs, stats, rounds = carry
        t_clock = sts.t
        # each stage under its ``exec_common.ROUND_STAGES`` scope
        with jax.named_scope("fap.horizon"):
            if incremental:
                horizon = _horizon_of(tin, t_clock)
                runnable = xc.runnable_mask(t_clock, horizon)
            elif horizon_impl == "fused":
                # fused kernel: min over in-edges + clamps + runnable (+ the
                # earliest-K threshold when selection is sort-free too)
                horizon, runnable = ew_ops.fused_horizon_select(
                    t_clock, pre_byk, delay_byk, t_end=t_end,
                    horizon_cap=horizon_cap, n_iters=n_bisect,
                    k_select=k_select if select == "threshold" else 0)
            else:
                horizon = xc.horizon_times(dnet, n, t_clock, t_end,
                                           horizon_cap=horizon_cap)
                runnable = xc.runnable_mask(t_clock, horizon)
            if k_select > 0 and select == "threshold" and \
                    (incremental or horizon_impl == "scatter"):
                score = jnp.where(runnable, t_clock, jnp.inf)
                tau = ew_ops.select_threshold(score, k_select,
                                              n_iters=n_bisect)
                runnable = jnp.logical_and(runnable, score <= tau)
            if k_select > 0 and select == "sort":
                # earliest-neuron-steps-next: keep only the K earliest
                # runnable
                score = jnp.where(runnable, t_clock, jnp.inf)
                kth = jnp.sort(score)[min(k_select, n) - 1]
                runnable = jnp.logical_and(runnable, score <= kth)
            if active is not None:
                # tenant-lane mask: an inactive lane advances nothing
                runnable = jnp.logical_and(runnable, active)
            if k_qos is not None:
                # per-tenant QoS frontier cap: restrict to the k_qos
                # earliest runnable neurons (traced k — one compiled round
                # serves every class); k_qos <= 0 selects everything (tau =
                # max finite)
                score = jnp.where(runnable, t_clock, jnp.inf)
                k_eff = jnp.where(k_qos > 0, jnp.minimum(k_qos, n), n)
                tau = ew_ops.select_threshold(score, k_eff, n_iters=n_bisect)
                runnable = jnp.logical_and(runnable, score <= tau)
            n_runnable = runnable.sum(dtype=jnp.int64)

        if batch == "compact":
            # --- compact -> step -> scatter: only the runnable frontier
            # pays the step machinery ----------------------------------
            with jax.named_scope("fap.compact"):
                ids, _ = xc.compact_frontier(runnable, t_clock, cap,
                                             n_bisect)
            with jax.named_scope("fap.gather"):
                lane_ok = ids < n
                idc = jnp.minimum(ids, n - 1)
                sts_b = xc.gather_lanes(sts, idc)
                t_b_prev = sts_b.t
                eqt_b, eqa_b, eqg_b = sched.gather_rows(eq, idc)
                hor_b, iinj_b = horizon[idc], iinj_r[idc]
            with jax.named_scope("fap.advance"):
                sts_b, eqt_b, spiked_b, tsp_b, nd, nrs = vadvance(
                    sts_b, eqt_b, eqa_b, eqg_b, hor_b, lane_ok, iinj_b)
            with jax.named_scope("fap.scatter"):
                sts = xc.scatter_lanes(sts, sts_b, ids)
                eq = sched.scatter_rows(eq, ids, eqt_b)
                rec = ev.record_spikes(rec, ids, tsp_b, spiked_b)
            with jax.named_scope("fap.fanout"):
                # O(E) fan-out + insert only on rounds that actually
                # spiked (identical either way: zero spikes insert nothing)
                spiked_any = spiked_b.any()
                eq = jax.lax.cond(spiked_any, _insert_spikes,
                                  lambda eq, *_: eq, eq, spiked_b, tsp_b, ids)
            live = 0
            if incremental:
                with jax.named_scope("fap.horizon_update"):
                    # only the in-edge slots of moved clocks change; the
                    # lanes' own cap terms follow from the clocks
                    moved = jnp.logical_and(lane_ok, sts_b.t != t_b_prev)
                    tin, pushed = _push_clocks(tin, idc, moved, sts_b.t)
                    live = lane_ok.sum(dtype=jnp.int64) + pushed
            stats = stats._replace(
                runnable=stats.runnable + n_runnable,
                stepped=stats.stepped + lane_ok.sum(dtype=jnp.int64),
                lanes=stats.lanes + cap,
                rounds=stats.rounds + 1,
                horizon_live=stats.horizon_live + live,
                fanout_rounds=stats.fanout_rounds
                + spiked_any.astype(jnp.int32))
        else:
            sts, eq_t, spiked, t_sp, nd, nrs = vadvance(
                sts, eq.t, eq.w_ampa, eq.w_gaba, horizon, runnable, iinj_r)
            eq = eq._replace(t=eq_t)
            rec = ev.record_spikes(rec, neuron_ids, t_sp, spiked)
            eq = spike_ins(eq, spiked, t_sp)
            stats = stats._replace(
                runnable=stats.runnable + n_runnable,
                stepped=stats.stepped + n_runnable,
                lanes=stats.lanes + n,
                rounds=stats.rounds + 1)
        out = (sts, eq, rec, n_ev + nd.sum(dtype=jnp.int32),
               n_rs + nrs.sum(dtype=jnp.int32), stats, rounds + 1)
        if incremental:
            out = out[:3] + (tin,) + out[3:]
        return out

    def round_body(carry):
        return _round(carry, iinj_v)

    def tenant_round(carry, iinj, active, k_qos=0):
        """Round with call-time stimulus + lane mask + QoS frontier cap —
        the per-tenant unit ``repro.serve`` vmaps over its lane axis
        (in_axes=(0, 0, 0, 0): carry leaves [T, ...], iinj [T, N],
        active bool[T], k_qos i32[T])."""
        i = jnp.broadcast_to(jnp.asarray(iinj, jnp.float64), (n,))
        return _round(carry, i, active, k_qos)

    def cond(carry):
        sts, rounds = carry[0], carry[-1]
        return jnp.logical_and(sts.t.min() < t_end - 1e-9,
                               jnp.logical_and(rounds < max_rounds,
                                               ~sts.failed.any()))

    def init_carry(iinj=None):
        """Fresh round-0 carry; ``iinj`` overrides the construction-time
        stimulus (per-tenant admission in ``repro.serve``)."""
        iv = iinj_v if iinj is None else \
            jnp.broadcast_to(jnp.asarray(iinj, jnp.float64), (n,))
        Y = xc.batch_init(model, n)
        sts = jax.vmap(lambda y, i: bdf.reinit(model, 0.0, y, i, opts))(Y, iv)
        eq = qops.make(n)
        rec = ev.make_spike_record(n, SPK_CAP)
        z = jnp.zeros((), jnp.int32)
        carry = sts, eq, rec, z, z, xc.SchedStats.zeros(), z
        if incremental:
            carry = carry[:3] + (_tin_of(sts.t),) + carry[3:]
        return carry

    def _tin_of(t_clock):
        """The in-edge clock table of ``t_clock``: one gather."""
        return t_clock[pre_byk].reshape(-1)

    def horizon_of(carry):
        """The dependency horizon an incremental round's carry implies."""
        return _horizon_of(carry[3], carry[0].t)

    @jax.jit
    def _run():
        out = jax.lax.while_loop(cond, round_body, init_carry())
        if incremental:
            sts, eq, rec, _, n_ev, n_rs, stats, rounds = out
        else:
            sts, eq, rec, n_ev, n_rs, stats, rounds = out
        return RunResult(rec, sts.nst.sum(), n_ev, n_rs, eq.dropped,
                         sts.failed.any(), sts.zn[:, 0], stats,
                         solver=xc.solver_stats(sts)), rounds

    # --- preemption tolerance: SimCarry <-> round-carry packing ----------
    # hcarry holds the in-edge clock table (layout-dependent; a snapshot
    # of another layout is reseeded from its clocks); all solver/sched
    # counters ride a dict so repro.checkpoint snapshots the WHOLE round
    # state leaf-for-leaf.
    def pack(c):
        if incremental:
            sts, eq, rec, tin, n_ev, n_rs, stats, rounds = c
            h = (tin,)
        else:
            sts, eq, rec, n_ev, n_rs, stats, rounds = c
            h = ()
        return xc.SimCarry(sts, eq, rec, h, {
            "n_ev": n_ev, "n_rs": n_rs, "stats": stats, "rounds": rounds})

    def unpack(sc):
        c = (sc.sts, sc.eq, sc.rec, sc.counters["n_ev"],
             sc.counters["n_rs"], sc.counters["stats"],
             sc.counters["rounds"])
        return c[:3] + tuple(sc.hcarry) + c[3:] if incremental else c

    jround = None
    # a snapshot of another horizon carry (the horizon itself, before the
    # in-edge clock table) is reseeded from its clocks: by shape, and by
    # this fingerprint where the shapes coincide (K = 1)
    ckpt_layout = dict(
        reseed=lambda sc: sc._replace(hcarry=(_tin_of(sc.sts.t),)),
        fingerprint={"hcarry": "in_edge_clocks"}) if incremental else {}

    def run(checkpoint_every: int = 0, ckpt_dir=None, resume: bool = False,
            fault=None, watchdog=None, max_rollbacks: int = 2,
            ckpt_keep: int = 3):
        """Nullary fast path (jitted ``while_loop``); any robustness knob
        switches to the host-stepped checkpointed driver.  Knobs are
        call-time so ONE runner (one ``jax.jit(round_body)`` compile)
        serves many kill/resume/poison scenarios — the Hypothesis
        property tests depend on this.  Within the host-stepped mode
        every run shares that one compiled round, so kill/resume and
        watchdog-rollback runs are event-for-event IDENTICAL to the
        uninterrupted host-stepped run; against the ``while_loop`` fast
        path agreement is to floating-point ulp only (XLA fuses the
        standalone round differently than the loop body)."""
        robust = bool(checkpoint_every or resume or watchdog
                      or fault is not None)
        if not robust:
            return _run()
        nonlocal jround
        if jround is None:      # compile once; reused across run() calls
            jround = jax.jit(round_body)
        if watchdog is None:
            watchdog = True

        health_of = None
        if watchdog:
            def health_of(sc, t_prev):
                return xc.health_check(
                    sc.sts, t_prev,
                    horizon=horizon_of(unpack(sc)) if incremental else None,
                    horizon_cap=horizon_cap)

        sc, health = xc.run_checkpointed(
            lambda: pack(init_carry()),
            lambda sc: pack(jround(unpack(sc))),
            lambda sc: bool(cond(unpack(sc))),
            ckpt_dir=ckpt_dir, checkpoint_every=checkpoint_every,
            resume=resume, keep=ckpt_keep, fault=fault,
            health_of=health_of, max_rollbacks=max_rollbacks,
            **ckpt_layout)
        sts, eq, rec = sc.sts, sc.eq, sc.rec
        health["dropped_events"] = int(eq.dropped)
        res = RunResult(rec, sts.nst.sum(), sc.counters["n_ev"],
                        sc.counters["n_rs"], eq.dropped,
                        jnp.logical_or(sts.failed.any(),
                                       health["rollback_exhausted"]),
                        sts.zn[:, 0], sc.counters["stats"],
                        solver=xc.solver_stats(sts), health=health)
        return res, sc.counters["rounds"]

    run.jitted = _run         # the nullary fast path (AOT lower/compile)
    run.init_carry = init_carry
    run.round_body = round_body
    run.tenant_round = tenant_round   # (carry, iinj, active, k_qos) — serve
    run.cond = cond
    run.pack = pack           # carry tuple <-> SimCarry (checkpoint tests)
    run.unpack = unpack
    run.horizon_of = horizon_of   # incremental carry -> its horizon
    run.batch_cap = cap
    run.spike_cap = s_cap
    return run


def run_fap_fixed(*args, **kw):
    res, _ = make_fap_fixed_runner(*args, **kw)()
    return res


def run_fap_vardt(*args, **kw):
    res, _ = make_fap_vardt_runner(*args, **kw)()
    return res
