"""The laboratory-experiment cells: one network, one constant
drive, advanced by ``exec_fap.make_fap_vardt_runner``'s own round.

Set-up builds the runner from the configuration's keyword arguments, jits
a loop of ``rounds_per_call`` rounds over ``run.cond`` / ``run.round_body``
(the network and the drive passed as arguments, so every seed runs one
executable), and runs one call as the warm-up.  The window then calls that
loop from the state the warm-up reached until ``seconds`` have passed,
each call ending in a host read of the simulated time (GVT, the least
neuron clock) and the run's guarantees.  Simulated time advanced over the
window's wall seconds is ``bio_ms_per_s``.

After the window the spike trains, clocks and counters come back to the
host, the program's state is freed, and the plain reference
(``bench/reference.py``) simulates the same network from rest to the GVT
reached plus a margin; ``bench/checks.py`` compares the two.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import checks, gen, reference
from bench.common import CompileClock, hoist, now, peak_bytes, span


def build_network(cfg: dict, seed: int) -> dict:
    return gen.make_network(cfg["n_neurons"], cfg["k_in"], seed,
                            pct_gaba=cfg["pct_gaba"], w_exc=cfg["w_exc"],
                            w_inh=cfg["w_inh"],
                            max_out_degree=cfg.get("max_out_degree", 0))


def program_network(g: dict):
    from repro.core import network
    return network.Network(n=int(g["n"]), pre=g["pre"], post=g["post"],
                           delay=g["delay"], w_ampa=g["w_ampa"],
                           w_gaba=g["w_gaba"],
                           min_delay=float(g["delay"].min()))


def cell_model(cfg: dict):
    from repro.core import morphology
    from repro.core.cell import CellModel
    return CellModel(morphology.soma_only(cfg["soma_diam_um"]))


def make_runner(cfg: dict, g: dict, iinj, t_end: float, bdf_over=None):
    """The configuration's runner: ``make_fap_vardt_runner`` with its
    keyword arguments, the wheel and BDF options built from their dicts."""
    from repro import sched
    from repro.core import bdf, exec_fap
    kw = dict(cfg["runner"])
    if "wheel" in kw:
        kw["wheel"] = sched.WheelSpec(**kw["wheel"])
    opts = bdf.BDFOptions(**{**cfg.get("bdf", {}), **(bdf_over or {})})
    model = cell_model(cfg)
    return exec_fap.make_fap_vardt_runner(model, program_network(g), iinj,
                                          t_end, opts=opts, **kw)


def probe_of(run, carry):
    """Scalars the host reads after every call: GVT, the guarantees and
    the per-layer counters (device-side sums)."""
    from repro.core import exec_common as xc
    sc = run.pack(carry)
    sts = sc.sts
    stats = sc.counters["stats"]
    solver = xc.solver_stats(sts)
    return {"gvt": sts.t.min(),
            "dropped": sc.eq.dropped,
            "failed": sts.failed.any(),
            "finite": jnp.logical_and(jnp.isfinite(sts.zn).all(),
                                      jnp.isfinite(sts.t).all()),
            "overflow": sc.rec.overflow,
            "spikes": sc.rec.count.sum(),
            "rounds": stats.rounds,
            "stepped": stats.stepped,
            "lanes": stats.lanes,
            "runnable": stats.runnable,
            "nst": solver["nst"]}


def k_round_fn(run, k: int):
    def k_rounds(carry):
        def cond(s):
            return jnp.logical_and(s[1] < k, run.cond(s[0]))

        def body(s):
            return run.round_body(s[0]), s[1] + 1

        carry, _ = jax.lax.while_loop(cond, body, (carry, jnp.int32(0)))
        return carry, probe_of(run, carry)
    return k_rounds


class LabCell:
    """One lab cell at one seed: ``setup()``, ``window(seconds)``,
    ``check()``, each returning what it measured."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, *,
                 bdf_over=None, round_fn=None):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.bdf_over = bdf_over
        self.round_fn = round_fn          # tests: a broken round in place
        self.t_end = float(cfg["t_end_ms"])
        model = cell_model(cfg)       # where the state keeps g_AMPA, g_GABA
        self.syn_index = (model.idx_g_ampa, model.idx_g_gaba)

    # -- set-up -------------------------------------------------------------
    def setup(self, like=None):
        """Build, compile (or load) and warm up.  ``like``: another seed's
        set-up cell whose executables this one runs on its own arrays."""
        cfg = self.cfg
        self.net = build_network(cfg, self.seed)
        self.iinj = gen.drive(self.traffic, cfg["n_neurons"], self.seed)
        self.run = make_runner(cfg, self.net, self.iinj, self.t_end,
                               self.bdf_over)
        with CompileClock() as cc:
            self.init = hoist(self.run.init_carry,
                              jitted=like and like.init.jitted)
            carry = self.init()
            fn = k_round_fn(self.run, int(cfg["rounds_per_call"]))
            if self.round_fn is not None:
                fn = self.round_fn(self.run, fn)
            self.kcall = hoist(fn, carry, donate=True,
                               jitted=like and like.kcall.jitted)
            carry, p = self.kcall(carry)
            p = jax.device_get(p)
        self.carry, self.last = carry, p
        self.restarts = 0
        return {"compile": cc.as_dict(), "warmup_gvt_ms": float(p["gvt"]),
                "n": int(cfg["n_neurons"]),
                "batch_cap": int(self.run.batch_cap),
                "spike_cap": int(self.run.spike_cap)}

    # -- the window ------------------------------------------------------------
    def window(self, seconds: float, trace_s: float = 0.0, tracer=None):
        """Run calls until ``seconds`` have passed (one at least).  With a
        ``tracer``, the
        first ``trace_s`` seconds are traced (``tracer.start()`` /
        ``tracer.stop()``)."""
        p0 = self.last
        advance = 0.0
        gvt_prev = float(p0["gvt"])
        calls = failed = 0
        call_s = []
        traced = {}
        tracing = False
        with CompileClock() as cc:
            t0 = now()
            if tracer is not None:
                tracer.start()
                tracing = True
                traced["p0"], traced["t0"] = p0, t0
            while True:
                t_call = now()
                if calls and t_call - t0 >= seconds:
                    break
                if tracing and t_call - t0 >= trace_s:
                    traced["p1"], traced["t1"] = self.last, t_call
                    tracer.stop()
                    tracing = False
                t_k = now()
                with span("bench.call", tracing):
                    carry, p = self.kcall(self.carry)
                    p = jax.device_get(p)
                self.carry = carry
                calls += 1
                call_s.append(now() - t_k)
                gvt = float(p["gvt"])
                if self._call_failed(self.last, p):
                    failed += 1
                advance += gvt - gvt_prev
                gvt_prev = gvt
                self.last = p
                if gvt >= self.t_end - 1e-9:
                    # the run reached t_end: start anew, keep counting
                    self.carry = self.init()
                    self.last = p = jax.device_get(
                        probe_of(self.run, self.carry))
                    gvt_prev = float(p["gvt"])
                    self.restarts += 1
            t1 = now()
            if tracing:
                traced["p1"], traced["t1"] = self.last, now()
                tracer.stop()
        wall = t1 - t0
        d = {k: float(self.last[k]) - float(p0[k])
             for k in ("rounds", "stepped", "lanes", "runnable", "nst",
                       "spikes")} if not self.restarts else {}
        self.attempted, self.failed = calls, failed
        out = {"wall_s": wall, "calls": calls, "failed_calls": failed,
               "bio_ms": advance, "bio_ms_per_s": advance / wall,
               "gvt_start_ms": float(p0["gvt"]),
               "gvt_end_ms": float(self.last["gvt"]),
               "restarts": self.restarts, "window_compile": cc.as_dict(),
               # wall seconds of the calls: a slow run shows whether one
               # call or all of them were slow
               "call_s": {"min": min(call_s), "median": float(
                   np.median(call_s)), "max": max(call_s), "each": call_s},
               "counts": d, "n": int(self.cfg["n_neurons"])}
        if "p1" in traced:
            tp0, tp1 = traced["p0"], traced["p1"]
            out["traced"] = {
                "rounds": float(tp1["rounds"]) - float(tp0["rounds"]),
                "bio_ms": float(tp1["gvt"]) - float(tp0["gvt"]),
                "wall_s": traced["t1"] - traced["t0"]}
        return out

    def _call_failed(self, prev, p) -> bool:
        """A call fails on a dropped event, a solver failure, a non-finite
        state, a record overflow, or a GVT that did not move."""
        return bool(int(p["dropped"]) > int(prev["dropped"])
                    or bool(p["failed"]) or not bool(p["finite"])
                    or int(p["overflow"]) > int(prev["overflow"])
                    or not float(p["gvt"]) > float(prev["gvt"]))

    # -- correctness ---------------------------------------------------------------
    def check(self) -> dict:
        """Compare the window's final state with the plain reference."""
        sc = self.run.pack(self.carry)
        count = np.asarray(sc.rec.count)
        k = max(1, int(count.max()))
        times = np.asarray(sc.rec.times[:, :k])
        clock = np.asarray(sc.sts.t)
        g_ampa, g_gaba = (np.asarray(sc.sts.zn[:, 0, i])
                          for i in self.syn_index)
        n_ev = int(sc.counters["n_ev"])
        p = self.last
        del sc
        self.carry = None                 # free the program's state
        t_cut = float(clock.min())
        r_count, r_times, r_over = reference.simulate(
            self.net, self.iinj, t_cut + checks.margin_ms(t_cut),
            drive=checks.padded(count, times),
            diam_um=self.cfg["soma_diam_um"])
        driven = self.iinj >= gen.SOMA_CALIBRATION["i_threshold"]
        cmp = checks.compare_trains(count, times, r_count, r_times, t_cut,
                                    tail=driven)
        cmp["syn_err_max"] = checks.conductance_gap(
            count, times, self.net, clock, g_ampa, g_gaba,
            (reference.TAU_AMPA, reference.TAU_GABA))
        implied = checks.implied_events(count, times, self.net["pre"],
                                        self.net["post"], self.net["delay"],
                                        clock)
        faults = {"dropped": int(p["dropped"]), "failed": int(p["failed"]),
                  "nonfinite": int(not p["finite"]),
                  "overflow": int(p["overflow"]) + r_over,
                  "failed_calls": self.failed,
                  "events_vs_implied": checks.events_off(n_ev, implied)}
        cmp.update({"t_cut_ms": t_cut, "n_events": n_ev,
                    "implied_events": implied, "faults": faults,
                    "exact_faults": sum(faults.values())})
        return cmp


Cell = LabCell
