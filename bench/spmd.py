"""The laboratory experiment at the paper's size on four chips: one
network sharded by neuron over a mesh of the configuration's shape,
advanced by ``fap_spmd.make_fap_spmd_runner``'s round, the round that
``run_fap_spmd`` drives.

It is ``bench/lab.py``'s cell on the four-chip runner.  Set-up builds the
runner from the configuration's keyword arguments, jits a loop of
``rounds_per_call`` rounds over ``run.cond`` / ``run.round_body`` with the
network, the drive and the carry as arguments placed by their
``NamedSharding``s (``hoist_on_mesh``: every seed runs one executable, and
``bench/stages.program_text`` lowers the very module that ran), and warms
up with calls to GVT ``window_from_ms``, past the onset's first sweep and
just before the first wave of spikes.  The window is ``LabCell.window``:
calls until ``seconds`` have passed, each ending in one host read of GVT
(the least neuron clock), the guarantees and the counters, parcel bytes
among them.

After the window the program runs on to GVT ``check_to_ms`` if the window
stopped short of it, so that the comparison holds that wave's spikes.
Then the spike trains, clocks and conductances come back to the host,
the program's state is freed, and the plain reference
(``bench/reference.py``) simulates all the neurons against the events the
program's spikes send, in one block of neurons a device
(``reference_by_blocks``); ``bench/checks.py`` compares.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import tree_util as tu
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from bench import checks, gen, reference
from bench.common import CompileClock, now
from bench.lab import LabCell, build_network, cell_model, program_network
from repro.distributed.fap_spmd import make_fap_spmd_runner


def mesh_of(cfg: dict):
    """The configuration's mesh over the first devices."""
    from repro.launch.mesh import make_mesh
    shape = tuple(cfg["mesh"])
    return make_mesh(shape, ("data", "model"),
                     devices=jax.devices()[:math.prod(shape)])


def make_runner(cfg: dict, g: dict, iinj, mesh, bdf_over=None):
    """The configuration's runner: ``make_fap_spmd_runner`` with its keyword
    arguments, the wheel, exchange and BDF options built from their
    dicts."""
    from repro import sched
    from repro.core import bdf
    from repro.distributed.exchange import ExchangeSpec
    kw = dict(cfg["runner"])
    kw["wheel"] = sched.WheelSpec(**kw["wheel"])
    kw["exchange"] = ExchangeSpec(**kw["exchange"])
    opts = bdf.BDFOptions(**{**cfg.get("bdf", {}), **(bdf_over or {})})
    return make_fap_spmd_runner(cell_model(cfg), program_network(g), iinj,
                                float(cfg["t_end_ms"]), mesh, opts=opts,
                                **kw)


def probe_of(sc):
    """Scalars the host reads after every call: GVT, the guarantees and
    the per-layer counters (sums over the shards)."""
    from repro.core import exec_common as xc
    sts, c = sc.sts, sc.counters
    stats = c["stats"]
    return {"gvt": sts.t.min(),
            "dropped": c["dropped"],
            "failed": sts.failed.any(),
            "finite": jnp.logical_and(jnp.isfinite(sts.zn).all(),
                                      jnp.isfinite(sts.t).all()),
            "overflow": sc.rec.overflow,
            "spikes": sc.rec.count.sum(),
            "rounds": stats.rounds,
            "stepped": stats.stepped,
            "lanes": stats.lanes,
            "runnable": stats.runnable,
            "nst": xc.solver_stats(sts)["nst"],
            "parcel_bytes": c["parcel_bytes"]}


def k_round_fn(round_body, cond, k: int):
    """``k`` rounds of ``round_body`` while ``cond`` holds, then the
    probe."""
    def k_rounds(carry):
        def c(s):
            return jnp.logical_and(s[1] < k, cond(s[0]))

        def b(s):
            return round_body(s[0]), s[1] + 1

        carry, _ = jax.lax.while_loop(c, b, (carry, jnp.int32(0)))
        return carry, probe_of(carry)
    return k_rounds


def hoist_on_mesh(fn, carry, mesh, *, donate: bool = False, jitted=None):
    """``bench.common.hoist`` for a program on a mesh: ``fn(carry)`` jitted
    with the arrays it closes over that are placed on the mesh (the
    network, the drive, the routing tables: all a seed changes) passed as
    arguments, and every argument and output given its sharding (the
    carry comes back as it went in).  Other constants (the cell model's)
    stay constants of the program, as in ``jax.jit(fn)``, whose numbers it
    then gives bit for bit.  Lowering from shapes (``call.lower_args``,
    which hold no buffers) gives the module that ran.  ``jitted``: another
    seed's ``call.jitted``."""
    cj, out_shape = jax.make_jaxpr(fn, return_shape=True)(carry)
    out_tree = tu.tree_structure(out_shape)
    placed = [isinstance(getattr(c, "sharding", None), NamedSharding)
              and c.sharding.mesh == mesh for c in cj.consts]
    consts = [c for c, p in zip(cj.consts, placed) if p]
    fixed = [c for c, p in zip(cj.consts, placed) if not p]
    leaves = tu.tree_leaves(carry)
    leaf_sh = [x.sharding for x in leaves]
    n_out = out_tree.num_leaves - len(leaves)      # the probe's scalars

    def flat_fn(consts, *leaves):
        it, fx = iter(consts), iter(fixed)
        every = [next(it) if p else next(fx) for p in placed]
        return jax.core.eval_jaxpr(cj.jaxpr, every, *leaves)

    jfn = jitted or jax.jit(
        flat_fn, in_shardings=([c.sharding for c in consts], *leaf_sh),
        out_shardings=leaf_sh + [NamedSharding(mesh, P())] * n_out,
        donate_argnums=tuple(range(1, 1 + len(leaves))) if donate else ())

    def call(*args):
        return tu.tree_unflatten(out_tree, jfn(consts, *tu.tree_leaves(args)))

    call.jitted = jfn
    call.consts = consts
    call.lower_args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       weak_type=a.weak_type),
        (consts, *leaves))
    return call


@functools.lru_cache(maxsize=None)
def _block_reference(devices: tuple, diam_um: float):
    """The jitted ``reference._run`` over one block of edges a device (one
    compile a process for every seed)."""
    dt = reference.DT_MS
    ring = int(math.ceil((reference.MAX_DELAY_MS + 2 * dt) / dt)) + 2

    def block(pre, post, delay, w_a, w_g, iinj, drive, n_steps, dt):
        c, t, o = reference._run(pre[0], post[0], delay[0], w_a[0], w_g[0],
                                 iinj, drive, n_steps, dt,
                                 rec_cap=reference.REC_CAP, ring=ring,
                                 dtype=jnp.float64, diam_um=diam_um)
        return c[None], t[None], o[None]

    return jax.jit(jax.shard_map(
        block, mesh=Mesh(np.asarray(devices), ("b",)),
        in_specs=(P("b", None),) * 5 + (P(),) * 4,
        out_specs=(P("b", None), P("b", None, None), P("b")),
        check_vma=False))


def reference_by_blocks(g: dict, iinj, t_stop: float, drive, diam_um: float,
                        devices):
    """``reference.simulate(g, iinj, t_stop, drive)``, one block of neurons
    a device, all at once: each device integrates every neuron against the
    in-edges of its block only (the network is grouped by postsynaptic
    neuron, so a block's edges are one slice) and keeps its block's trains.
    A block's neurons see all their events, in the edges' own order.
    Returns (count, times, record overflow) as ``simulate`` does."""
    n, nb = int(g["n"]), len(devices)
    pre, post = np.asarray(g["pre"]), np.asarray(g["post"])
    e_b, n_b = pre.size // nb, n // nb
    if n % nb or pre.size % nb or not np.array_equal(
            post.reshape(nb, e_b) // n_b,
            np.broadcast_to(np.arange(nb)[:, None], (nb, e_b))):
        raise ValueError("the network is not grouped by postsynaptic neuron "
                         "in equal blocks")
    dt = reference.DT_MS
    fn = _block_reference(tuple(devices), float(diam_um))

    def by_block(a, dtype):
        return jnp.asarray(np.asarray(a).reshape(nb, e_b), dtype)

    count, times, _ = fn(
        by_block(pre, jnp.int32), by_block(post, jnp.int32),
        by_block(g["delay"], jnp.float64), by_block(g["w_ampa"], jnp.float64),
        by_block(g["w_gaba"], jnp.float64),
        jnp.asarray(np.broadcast_to(np.asarray(iinj, np.float64), (n,))),
        jnp.asarray(drive, jnp.float64),
        jnp.int32(int(math.ceil(t_stop / dt))), jnp.asarray(dt, jnp.float64))
    rows = np.arange(n)
    count = np.asarray(count)[rows // n_b, rows]
    times = np.asarray(times, np.float64)[rows // n_b, rows]
    # a block counts every spike past the record's width, kept or not
    over = int(np.maximum(count - reference.REC_CAP, 0).sum())
    return count, times, over


class SpmdCell:
    """One four-chip lab cell at one seed: ``setup()``, ``window(seconds)``,
    ``check()``, each returning what it measured (``bench/lab.py``'s
    contract).  ``round_fn(run, fn)``, for tests, returns a broken device
    call in place of ``fn``."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, *,
                 bdf_over=None, round_fn=None):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.bdf_over = bdf_over
        self.round_fn = round_fn
        self.t_end = float(cfg["t_end_ms"])
        model = cell_model(cfg)       # where the state keeps g_AMPA, g_GABA
        self.syn_index = (model.idx_g_ampa, model.idx_g_gaba)

    # -- set-up -------------------------------------------------------------
    def setup(self, like=None):
        """Build, compile (or load) and warm up to GVT ``window_from_ms``.
        ``like``: another seed's set-up cell whose executable this one runs
        on its own arrays."""
        cfg = self.cfg
        self.net = build_network(cfg, self.seed)
        self.iinj = gen.drive(self.traffic, cfg["n_neurons"], self.seed)
        self.mesh = mesh_of(cfg)
        self.run = make_runner(cfg, self.net, self.iinj, self.mesh,
                               self.bdf_over)
        k = int(cfg["rounds_per_call"])
        with CompileClock() as cc:
            carry = self.run.init_carry()
            fn = k_round_fn(self.run.round_body, self.run.cond, k)
            if self.round_fn is not None:
                fn = self.round_fn(self.run, fn)
            self.kcall = hoist_on_mesh(fn, carry, self.mesh, donate=True,
                                       jitted=like and like.kcall.jitted)
            carry, p = self.kcall(carry)
            p = jax.device_get(p)
            carry, p, _ = self._run_to(carry, p,
                                       float(cfg["window_from_ms"]))
        self.carry, self.last = carry, p
        self.restarts = 0
        return {"compile": cc.as_dict(), "warmup_gvt_ms": float(p["gvt"]),
                "warmup_rounds": int(p["rounds"]),
                "n": int(cfg["n_neurons"]), "n_local": int(self.run.n_local),
                "mesh": list(cfg["mesh"]),
                "batch_cap": int(self.run.batch_cap),
                "spike_cap": int(self.run.spike_cap)}

    _call_failed = LabCell._call_failed

    def _run_to(self, carry, p, t_ms: float):
        """Calls until GVT is past 0 and at least ``t_ms``.  Every neuron
        starts at t = 0 and a shard's batch takes ``batch_cap`` of them a
        round, so GVT stays 0 over the first ceil(n_local / batch_cap)
        rounds; a call after those that fails (``_call_failed``) stops the
        run.  Returns the carry, the last probe and the calls that failed
        (0 or 1)."""
        sweep = math.ceil(self.run.n_local / self.run.batch_cap)
        while float(p["gvt"]) <= 0.0 or float(p["gvt"]) < t_ms:
            prev = p
            carry, p = self.kcall(carry)
            p = jax.device_get(p)
            if int(prev["rounds"]) >= sweep and self._call_failed(prev, p):
                return carry, p, 1
        return carry, p, 0

    # -- the window -----------------------------------------------------------
    def window(self, seconds: float, trace_s: float = 0.0, tracer=None):
        """``LabCell.window`` on this cell, whose ``t_end`` no window
        reaches; ``counts`` also holds the parcel bytes each device
        shipped."""
        p0 = self.last
        out = LabCell.window(self, seconds, trace_s, tracer)
        if out["counts"]:
            out["counts"]["parcel_bytes"] = (float(self.last["parcel_bytes"])
                                             - float(p0["parcel_bytes"]))
        return out

    # -- correctness --------------------------------------------------------
    def check(self) -> dict:
        """Run on from the window's end to GVT ``check_to_ms``, if the
        window stopped short of it, then compare the state with the plain
        reference."""
        t0 = now()
        self.carry, self.last, run_on_failed = self._run_to(
            self.carry, self.last, float(self.cfg["check_to_ms"]))
        run_on_s = now() - t0
        sc = self.carry
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
        r_count, r_times, r_over = reference_by_blocks(
            self.net, self.iinj, t_cut + checks.margin_ms(t_cut),
            checks.padded(count, times), self.cfg["soma_diam_um"],
            list(self.mesh.devices.flat))
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
                  "run_on_failed_calls": run_on_failed,
                  "events_vs_implied": checks.events_off(n_ev, implied)}
        cmp.update({"t_cut_ms": t_cut, "run_on_s": run_on_s,
                    "n_events": n_ev,
                    "implied_events": implied, "faults": faults,
                    "exact_faults": sum(faults.values())})
        return cmp


Cell = SpmdCell
