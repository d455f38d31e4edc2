"""Bulk Synchronous Parallel execution models (paper baselines, Fig. 1 a/b).

``run_bsp_fixed``  — fixed-timestep lockstep execution with a communication
window equal to the minimum synaptic delay (0.1 ms): methods 1a (cnexp),
1b (euler), 2a (derivimplicit).

``run_bsp_vardt`` — NEURON-style BSP variable-timestep (method 2b): each
neuron runs its own CVODE/BDF integrator but a collective barrier clamps all
integrators to the communication-window boundary; spikes are exchanged at the
barrier.  The window clamp is precisely why variable-step struggles under
BSP: steps can never grow beyond the global 0.1 ms interval even in quiet
regimes (paper §4.3).
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
from repro.core.fixed_step import make_stepper
from repro.core.network import Network

EV_CAP = 64
SPK_CAP = 256


class RunResult(NamedTuple):
    rec: ev.SpikeRecord
    n_steps: jnp.ndarray       # total interpolation steps (all neurons)
    n_events: jnp.ndarray      # delivered events
    n_resets: jnp.ndarray      # IVP resets (vardt only)
    dropped: jnp.ndarray       # event-queue overflow (must be 0)
    failed: jnp.ndarray        # integrator failures (must be 0)
    y_final: jnp.ndarray       # [N, n_state] (vardt: zn[0])
    sched: object = None       # xc.SchedStats active-set telemetry (vardt
                               # runners; None where not collected)
    comm: object = None        # transport telemetry dict (run_fap_spmd:
                               # realized parcel bytes / class counts;
                               # None on single-host runners)
    solver: object = None      # solver telemetry dict summed over lanes
                               # (vardt runners: nst/nni/nfe/nsetups/netf/
                               # nncf — nsetups/nni is the Jacobian-reuse
                               # ratio of the freshness policy)
    health: object = None      # robustness telemetry dict (checkpointed
                               # drivers: watchdog checks/rollbacks,
                               # resume round, straggler stats, escalated
                               # drop counters — exec_common.empty_health;
                               # None on the fast jitted paths)


def make_bsp_fixed_runner(model: CellModel, net: Network, iinj, t_end: float,
                          method: str = "cnexp", dt: float = 0.025,
                          window: float = 0.1, ev_cap: int = EV_CAP,
                          queue: str = "dense",
                          wheel: sched.WheelSpec = sched.WheelSpec(),
                          fanout: str = "dense", spike_cap: int = 0):
    n = net.n
    dnet = xc.to_device(net)
    qops = sched.get_queue_ops(queue, ev_cap=ev_cap, wheel=wheel)
    qinsert = sched.edge_insert(qops, net)
    spike_ins = xc.make_spike_insert(net, dnet, qops, qinsert, fanout,
                                     spike_cap)
    steps_w = max(1, int(round(window / dt)))
    n_windows = int(math.ceil(t_end / (steps_w * dt)))
    step = make_stepper(model, method, dt)
    vstep = jax.vmap(step)
    iinj = jnp.broadcast_to(jnp.asarray(iinj, jnp.float64), (n,))

    def window_body(carry, w_idx):
        Y, eq, rec, n_ev = carry
        t0 = w_idx * (steps_w * dt)

        def step_body(c, j):
            Y, eq, rec, n_ev = c
            t_j = t0 + j * dt
            # deliver all events due by this step boundary (fixed-step grid)
            eq, wa, wg, cnt = qops.deliver_until(eq, jnp.full((n,), t_j + dt))
            Y = jax.vmap(model.apply_event)(Y, wa, wg)
            v_prev = Y[:, model.idx_vsoma]
            Y = vstep(Y, iinj)
            v_new = Y[:, model.idx_vsoma]
            spiked, t_sp = xc.detect_spikes(v_prev, v_new, t_j, t_j + dt)
            rec = ev.record_spikes(rec, jnp.arange(n), t_sp, spiked)
            return (Y, eq, rec, n_ev + cnt.sum(dtype=jnp.int32)), (spiked, t_sp)

        (Y, eq, rec, n_ev), (spk, tsp) = jax.lax.scan(
            step_body, (Y, eq, rec, n_ev), jnp.arange(steps_w))
        # collective exchange at the window barrier (<=1 spike per 0.1 ms)
        spiked_w = spk.any(axis=0)
        t_spike_w = jnp.where(spk, tsp, 0.0).sum(axis=0)
        eq = spike_ins(eq, spiked_w, t_spike_w)
        return (Y, eq, rec, n_ev), None

    @jax.jit
    def run():
        Y = xc.batch_init(model, n)
        eq = qops.make(n)
        rec = ev.make_spike_record(n, SPK_CAP)
        (Y, eq, rec, n_ev), _ = jax.lax.scan(
            window_body, (Y, eq, rec, jnp.zeros((), jnp.int32)),
            jnp.arange(n_windows))
        n_steps = jnp.asarray(n * n_windows * steps_w)
        z = jnp.zeros((), jnp.int32)
        return RunResult(rec, n_steps, n_ev, z, eq.dropped,
                         jnp.zeros((), bool), Y)

    return run


def run_bsp_fixed(*args, **kw) -> RunResult:
    return make_bsp_fixed_runner(*args, **kw)()


# ---------------------------------------------------------------------------
# variable-timestep neuron advance, shared by BSP-vardt and FAP-vardt
# ---------------------------------------------------------------------------
def make_vardt_advance(model: CellModel, opts: bdf.BDFOptions,
                       eg_window: float = 0.0, step_budget: int = 12):
    """Per-neuron advance to a horizon with exact (or grouped) event delivery.

    Returns fn(st, eq_t, eq_a, eq_g, horizon, active, iinj)
        -> (st, eq_t, spiked, t_spike, n_deliv, n_resets)
    designed for vmap over neurons.  Non-speculative: the BDF step is clamped
    (tstop) at min(horizon, next event time) so no step ever crosses an event.

    The deliver/step branches are fused through ``bdf.step_or_deliver``:
    one rhs + Hines-solve stream per loop iteration serves both (the
    delivery reset's rhs is the Newton corrector's first evaluation)
    instead of each branch paying its own full evaluation per iteration.
    """

    def advance(st: bdf.BDFState, eq_t, eq_a, eq_g, horizon, active, iinj_n):
        def body(i, c):
            st, eq_t, spiked, t_sp, nd, nrs = c
            run = jnp.logical_and(active, st.t < horizon - 1e-12)
            due = eq_t.min()
            deliver_now = jnp.logical_and(run, due <= st.t + 1e-12)
            step_now = jnp.logical_and(run, ~deliver_now)

            # --- grouped delivery weights at current time -----------------
            mask = eq_t <= due + eg_window + 1e-12
            wa = jnp.sum(jnp.where(mask, eq_a, 0.0))
            wg = jnp.sum(jnp.where(mask, eq_g, 0.0))

            # --- fused: event reset OR one BDF step clamped at the horizon
            # / next event — a single shared evaluation stream -------------
            t_lim = jnp.minimum(horizon, due)
            v_prev = st.zn[0][model.idx_vsoma]
            t_prev = st.t
            st_n = bdf.step_or_deliver(model, st, t_lim, wa, wg, deliver_now,
                                       iinj_n, opts)
            sp, tsp = xc.detect_spikes(v_prev, st_n.zn[0][model.idx_vsoma],
                                       t_prev, st_n.t)

            st = jax.tree_util.tree_map(
                lambda n_, o: jnp.where(run, n_, o), st_n, st)
            eq_t = jnp.where(deliver_now, jnp.where(mask, jnp.inf, eq_t),
                             eq_t)
            new_spike = jnp.logical_and(step_now, sp)
            spiked = jnp.logical_or(spiked, new_spike)
            t_sp = jnp.where(new_spike, tsp, t_sp)
            nd = nd + jnp.where(deliver_now, mask.sum(dtype=jnp.int32), 0)
            nrs = nrs + jnp.where(deliver_now, 1, 0)
            return st, eq_t, spiked, t_sp, nd, nrs

        z = jnp.zeros((), jnp.int32)
        init = (st, eq_t, jnp.zeros((), bool), jnp.zeros(()), z, z)
        return jax.lax.fori_loop(0, step_budget, body, init)

    return advance


def make_bsp_vardt_runner(model: CellModel, net: Network, iinj, t_end: float,
                          opts: bdf.BDFOptions = bdf.BDFOptions(),
                          eg_window: float = 0.0, window: float = 0.1,
                          step_budget: int = 8, ev_cap: int = EV_CAP,
                          queue: str = "dense",
                          wheel: sched.WheelSpec = sched.WheelSpec(),
                          batch: str = "dense", batch_cap: int = 0,
                          n_bisect: int = 48, fanout: str = "dense",
                          spike_cap: int = 0):
    """Method 2b: CVODE under BSP — barrier at every communication window.

    batch: "dense" vmaps the vardt advance over all N neurons per window;
    "compact" gathers only the lanes still behind the barrier into
    ``batch_cap``-wide dispatches (compact -> step -> scatter), chunking
    the window's frontier by earliest clock.  Every lane is advanced
    exactly once per window with the same barrier horizon either way, so
    compact spike trains are event-for-event identical to dense at ANY
    cap (chunks, unlike the FAP round's roll-over, never change a lane's
    horizon).  batch_cap <= 0 means N.

    fanout: "dense" | "compact" — the spike-delivery twin of ``batch``
    (``exec_common.make_spike_insert``): "compact" gathers only the
    spiking lanes' out-edges per window; spike_cap overflow falls back to
    the dense branch, never drops.  spike_cap <= 0 means min(N, 256).
    """
    if batch not in ("dense", "compact"):
        raise ValueError(f"unknown batch mode {batch!r}")
    n = net.n
    cap = n if batch_cap <= 0 else min(int(batch_cap), n)
    dnet = xc.to_device(net)
    qops = sched.get_queue_ops(queue, ev_cap=ev_cap, wheel=wheel)
    qinsert = sched.edge_insert(qops, net)
    spike_ins = xc.make_spike_insert(net, dnet, qops, qinsert, fanout,
                                     spike_cap)
    n_windows = int(math.ceil(t_end / window))
    iinj = jnp.broadcast_to(jnp.asarray(iinj, jnp.float64), (n,))
    advance = make_vardt_advance(model, opts, eg_window, step_budget)
    vadvance = jax.vmap(advance)
    neuron_ids = jnp.arange(n, dtype=jnp.int32)     # hoisted round constant

    def window_body(carry, w_idx):
        sts, eq, rec, n_ev, n_rs, stats = carry
        w_end = (w_idx + 1.0) * window
        horizon = jnp.full((n,), 1.0) * w_end          # global barrier
        behind = sts.t < w_end - 1e-12
        n_behind = behind.sum(dtype=jnp.int64)

        if batch == "compact":
            def chunk_cond(c):
                return c[-1].any()

            def chunk_body(c):
                sts, eq, spiked, t_sp, nd, nrs, stepped, disp, todo = c
                ids, _ = xc.compact_frontier(todo, sts.t, cap, n_bisect)
                lane_ok = ids < n
                idc = jnp.minimum(ids, n - 1)
                sts_b = xc.gather_lanes(sts, idc)
                eqt_b, eqa_b, eqg_b = sched.gather_rows(eq, idc)
                sts_b, eqt_b, spk_b, tsp_b, nd_b, nrs_b = vadvance(
                    sts_b, eqt_b, eqa_b, eqg_b, horizon[idc], lane_ok,
                    iinj[idc])
                sts = xc.scatter_lanes(sts, sts_b, ids)
                eq = sched.scatter_rows(eq, ids, eqt_b)
                spiked = xc.scatter_at(spiked, ids, spk_b)
                t_sp = xc.scatter_at(t_sp, ids, tsp_b)
                todo = xc.scatter_at(todo, ids, False)
                return (sts, eq, spiked, t_sp,
                        nd + nd_b.sum(dtype=jnp.int32),
                        nrs + nrs_b.sum(dtype=jnp.int32),
                        stepped + lane_ok.sum(dtype=jnp.int64),
                        disp + 1, todo)

            z = jnp.zeros((), jnp.int32)
            sts, eq, spiked, t_sp, nd, nrs, stepped, disp, _ = \
                jax.lax.while_loop(chunk_cond, chunk_body,
                                   (sts, eq, jnp.zeros((n,), bool),
                                    jnp.zeros((n,)), z, z,
                                    jnp.zeros((), jnp.int64), z, behind))
            stats = stats._replace(
                runnable=stats.runnable + n_behind,
                stepped=stats.stepped + stepped,
                lanes=stats.lanes + disp.astype(jnp.int64) * cap,
                rounds=stats.rounds + disp)
        else:
            active = jnp.ones((n,), bool)
            sts, eq_t, spiked, t_sp, nd, nrs = vadvance(
                sts, eq.t, eq.w_ampa, eq.w_gaba, horizon, active, iinj)
            eq = eq._replace(t=eq_t)
            nd, nrs = nd.sum(dtype=jnp.int32), nrs.sum(dtype=jnp.int32)
            stats = stats._replace(
                runnable=stats.runnable + n_behind,
                stepped=stats.stepped + n_behind,
                lanes=stats.lanes + n,
                rounds=stats.rounds + 1)
        rec = ev.record_spikes(rec, neuron_ids, t_sp, spiked)
        eq = spike_ins(eq, spiked, t_sp)
        return (sts, eq, rec, n_ev + nd, n_rs + nrs, stats), None

    def init_carry():
        Y = xc.batch_init(model, n)
        sts = jax.vmap(lambda y, i: bdf.reinit(model, 0.0, y, i, opts))(Y, iinj)
        eq = qops.make(n)
        rec = ev.make_spike_record(n, SPK_CAP)
        return (sts, eq, rec, jnp.zeros((), jnp.int32),
                jnp.zeros((), jnp.int32), xc.SchedStats.zeros())

    @jax.jit
    def _run():
        (sts, eq, rec, n_ev, n_rs, stats), _ = jax.lax.scan(
            window_body, init_carry(), jnp.arange(n_windows))
        return RunResult(rec, sts.nst.sum(), n_ev, n_rs, eq.dropped,
                         sts.failed.any(), sts.zn[:, 0], stats,
                         solver=xc.solver_stats(sts))

    jwindow = None

    def run(checkpoint_every: int = 0, ckpt_dir=None, resume: bool = False,
            fault=None, watchdog=None, max_rollbacks: int = 2,
            ckpt_keep: int = 3):
        """Nullary fast path; any robustness knob switches to the
        host-stepped checkpointed driver (``exec_common.run_checkpointed``)
        — one jitted window per host iteration, window index carried in
        ``counters["rounds"]``.  Kill/resume and rollback runs are
        bit-identical to the uninterrupted host-stepped run (one shared
        compiled window); vs the scanned fast path agreement is to
        floating-point ulp (XLA fuses the scan body differently)."""
        robust = bool(checkpoint_every or resume or watchdog
                      or fault is not None)
        if not robust:
            return _run()
        nonlocal jwindow
        if jwindow is None:     # compile once; reused across run() calls
            jwindow = jax.jit(lambda c, w: window_body(c, w)[0])
        if watchdog is None:
            watchdog = True

        def pack(c):
            sts, eq, rec, n_ev, n_rs, stats = c[:6]
            w = c[6] if len(c) > 6 else jnp.zeros((), jnp.int64)
            return xc.SimCarry(sts, eq, rec, (), {
                "n_ev": n_ev, "n_rs": n_rs, "stats": stats, "rounds": w})

        def step_fn(sc):
            c = jwindow((sc.sts, sc.eq, sc.rec, sc.counters["n_ev"],
                         sc.counters["n_rs"], sc.counters["stats"]),
                        sc.counters["rounds"])
            return pack(c + (sc.counters["rounds"] + 1,))

        sc, health = xc.run_checkpointed(
            lambda: pack(init_carry()), step_fn,
            lambda sc: int(sc.counters["rounds"]) < n_windows,
            ckpt_dir=ckpt_dir, checkpoint_every=checkpoint_every,
            resume=resume, keep=ckpt_keep, fault=fault,
            health_of=((lambda sc, t_prev: xc.health_check(sc.sts, t_prev))
                       if watchdog else None),
            max_rollbacks=max_rollbacks)
        sts, eq, rec = sc.sts, sc.eq, sc.rec
        health["dropped_events"] = int(eq.dropped)
        return RunResult(rec, sts.nst.sum(), sc.counters["n_ev"],
                         sc.counters["n_rs"], eq.dropped,
                         jnp.logical_or(sts.failed.any(),
                                        health["rollback_exhausted"]),
                         sts.zn[:, 0], sc.counters["stats"],
                         solver=xc.solver_stats(sts), health=health)

    return run


def run_bsp_vardt(*args, **kw) -> RunResult:
    return make_bsp_vardt_runner(*args, **kw)()
