"""Spike-parcel transport benchmark: dense all-gather vs sparse parcels.

The optimized SPMD FAP round owns exactly two channels (stepping
notifications + spike parcels; ``repro.distributed.exchange``).  This
benchmark compiles the round for both transports on a forced-host-device
mesh and reports, per channel, the *measured* collective bytes from the
compiled HLO (``launch.hlo_analysis.collective_channel_bytes``) plus the
per-round wall time — across parcel caps sized for a low (quiet, 0.25 Hz)
and a high (burst, 55.8 Hz) firing regime.  The sparse transport's parcel
bytes must be identical across network sizes (they are a function of
n_shards * parcel_cap only) while the dense transport's grow with N; the
worker asserts this, so a transport regression fails the bench (and
``scripts/check.sh``, which runs it in quick mode).

A topology axis (``repro.core.topology``) covers the notify channel's
structural lever: on block-structured wiring the boundary frontier, and
hence notify bytes, must drop vs the uniform-random worst case by ~the
measured frontier ratio (asserted; see also ``benchmarks/placement.py``
for the placement-recovery grid on label-shuffled nets).

Runs in a subprocess (jax device counts lock at first init):
  quick (REPRO_BENCH_QUICK=1): 2x2 mesh,   N in {256, 1024},   soma model
  full:                        16x16 mesh, N in {64k, 1M},     soma model
Wall time is measured at N <= 64k only (the 1M cell is bytes-only — a 1M
neuron round on an emulated 256-device CPU mesh is compile-and-analyse
territory; an explicit "skipped" line records the omission).
"""
from __future__ import annotations

import os
import subprocess
import sys

REGIME_RATES = {"low": 0.25, "high": 55.8}     # Hz (quiet / burst, paper §4)
HORIZON_CAP = 2.0                              # ms advanced per round


def parcel_cap_for(rate_hz: float, n_local: int, k_in: int,
                   n_shards: int) -> int:
    """Static per-(src,dst) parcel cap for a firing regime: expected spikes
    per shard per round, fanned over destination shards, x4 headroom."""
    spikes = n_local * rate_hz * HORIZON_CAP * 1e-3
    per_dest = spikes * min(k_in, n_shards) / n_shards
    return max(4, int(4 * per_dest + 0.5))


def run() -> None:
    """Orchestrator entry (run.py / check.sh): spawn the forced-host-device
    worker, stream its CSV through, record it for the JSON dump."""
    from benchmarks.common import cpu_mesh_env, dump_json, record_csv

    quick = os.environ.get("REPRO_BENCH_QUICK") == "1"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = cpu_mesh_env(root, 4 if quick else 256)
    res = subprocess.run(
        [sys.executable, "-m", "benchmarks.exchange", "--worker"],
        env=env, capture_output=True, text=True, cwd=root,
        timeout=(900 if quick else 7200))
    sys.stdout.write(res.stdout)
    record_csv(res.stdout)
    if res.returncode != 0:
        raise RuntimeError(f"exchange worker failed:\n{res.stderr[-3000:]}")
    dump_json("exchange")


def _worker() -> None:
    import jax
    import numpy as np

    jax.config.update("jax_enable_x64", True)

    from benchmarks.common import emit, timeit
    from repro.core import bdf, morphology, network
    from repro.core import exec_common as xc
    from repro.core.cell import CellModel
    from repro.distributed.exchange import ExchangeSpec
    from repro.distributed.fap_spmd import PaperNeuroSpec, build_fap_round
    from repro.launch.hlo_analysis import collective_channel_bytes
    from repro.launch.mesh import make_mesh

    import jax.numpy as jnp

    quick = os.environ.get("REPRO_BENCH_QUICK") == "1"
    shape = (2, 2) if quick else (16, 16)
    sizes = [256, 1024] if quick else [65536, 1 << 20]
    wall_max_n = 1024 if quick else 65536
    k_in = 4 if quick else 16
    mesh = make_mesh(shape, ("data", "model"))
    # HLO bytes on an emulated CPU mesh, by design (JAX_PLATFORMS=cpu)
    emit("exchange/platform", 0.0,
         f"platform={jax.devices()[0].platform};count={len(jax.devices())};"
         "emulated_cpu_mesh=True")
    n_shards = int(np.prod(shape))
    model = CellModel(morphology.soma_only())
    parcel = {}                    # (transport, regime, n) -> bytes
    notify = {}                    # (transport, regime, n) -> bytes

    def concrete_args(net, spec, targs):
        n = int(net.n)
        iinj = jnp.zeros((n,), jnp.float64)
        Y = xc.batch_init(model, n)
        sts = jax.vmap(lambda y, i: bdf.reinit(model, 0.0, y, i,
                                               bdf.BDFOptions()))(Y, iinj)
        dnet = xc.to_device(net)
        f8 = jnp.float64
        eq = (jnp.full((n, spec.ev_cap), jnp.inf, f8),
              jnp.zeros((n, spec.ev_cap), f8), jnp.zeros((n, spec.ev_cap), f8))
        return (sts, *eq, dnet.pre, dnet.post, dnet.delay, dnet.w_ampa,
                dnet.w_gaba, iinj) + targs

    for n in sizes:
        net = network.make_network(n, k_in=k_in, seed=0)
        n_local = n // n_shards
        cells = [("allgather", "any", 0)]
        for regime, rate in REGIME_RATES.items():
            cells.append(("sparse", regime,
                          parcel_cap_for(rate, n_local, k_in, n_shards)))
        for transport, regime, cap in cells:
            spec = PaperNeuroSpec(n_neurons=n, k_in=k_in, ev_cap=16,
                                  t_end=100.0)
            fn, args, sh = build_fap_round(
                model, spec, mesh, optimized=True, transport=transport,
                exchange=ExchangeSpec(parcel_cap=cap), net=net)
            compiled = jax.jit(fn, in_shardings=sh).lower(*args).compile()
            ch = collective_channel_bytes(compiled.as_text())
            parcel[(transport, regime, n)] = ch["exchange_parcel"]
            notify[(transport, regime, n)] = ch["exchange_notify"]
            tag = f"exchange/bytes/{transport}/{regime}/n{n}"
            emit(tag, 0.0,
                 f"parcel={ch['exchange_parcel']};"
                 f"notify={ch['exchange_notify']};other={ch['other']};"
                 f"cap={cap};n_shards={n_shards}")
            if n > wall_max_n:
                emit(f"exchange/round_wall/{transport}/{regime}/n{n}", 0.0,
                     "skipped=1M-round-on-emulated-mesh;bytes-only")
                continue
            cargs = jax.device_put(concrete_args(net, spec, args[10:]), sh)
            _, s = timeit(lambda: compiled(*cargs),
                          repeats=2 if quick else 3)
            emit(f"exchange/round_wall/{transport}/{regime}/n{n}", s * 1e6,
                 f"cap={cap}")

    # the activity-not-N contract, asserted (check.sh gate)
    n0, n1 = sizes
    for regime in REGIME_RATES:
        lo = parcel[("sparse", regime, n0)]
        hi = parcel[("sparse", regime, n1)]
        # caps may differ across N (they scale with n_local): compare
        # bytes *per cap slot*, which must be N-invariant
        cap0 = parcel_cap_for(REGIME_RATES[regime], n0 // n_shards, k_in,
                              n_shards)
        cap1 = parcel_cap_for(REGIME_RATES[regime], n1 // n_shards, k_in,
                              n_shards)
        ok = lo * cap1 == hi * cap0
        emit(f"exchange/scaling/{regime}", 0.0,
             f"sparse_bytes_per_slot_n_invariant={ok}")
        if not ok:
            raise AssertionError(
                f"sparse parcel bytes not cap-proportional: {lo}/{cap0} vs "
                f"{hi}/{cap1}")
    ag = [parcel[("allgather", "any", n)] for n in sizes]
    if not ag[1] > 2 * ag[0]:
        raise AssertionError(f"allgather parcel bytes did not grow with N: {ag}")
    emit("exchange/scaling/allgather", 0.0,
         f"bytes_grow_with_N={ag[1] > 2 * ag[0]}")

    # --- topology axis: block-structured wiring vs the uniform worst case --
    # The notify channel gathers the shard_frontier boundary set, so its
    # bytes must drop ~by the measured frontier ratio (the block locality
    # factor) on block wiring, while the uniform nets above stay ~N.
    from repro.core import topology
    from repro.distributed import placement as plc

    for n in sizes:
        net_b = network.make_network(
            n, k_in=k_in, seed=0,
            topology=topology.TopologyConfig("block", n_blocks=n_shards,
                                             p_in=0.99))
        cap = parcel_cap_for(REGIME_RATES["low"], n // n_shards, k_in,
                             n_shards)
        spec = PaperNeuroSpec(n_neurons=n, k_in=k_in, ev_cap=16, t_end=100.0)
        fn, args, sh = build_fap_round(
            model, spec, mesh, optimized=True, transport="sparse",
            exchange=ExchangeSpec(parcel_cap=cap), net=net_b)
        ch = collective_channel_bytes(
            jax.jit(fn, in_shardings=sh).lower(*args).compile().as_text())
        net_u = network.make_network(n, k_in=k_in, seed=0)
        f_u = plc.frontier_stats(net_u, n_shards)["F"]
        f_b = plc.frontier_stats(net_b, n_shards)["F"]
        base = notify[("sparse", "low", n)]
        b_ratio = base / max(1, ch["exchange_notify"])
        f_ratio = f_u / max(1, f_b)
        emit(f"exchange/bytes/sparse_block/n{n}", 0.0,
             f"notify={ch['exchange_notify']};parcel={ch['exchange_parcel']};"
             f"notify_uniform={base};byte_ratio={b_ratio:.2f};"
             f"frontier_ratio={f_ratio:.2f};F_uniform={f_u};F_block={f_b}")
        if not b_ratio >= max(2.0, 0.8 * f_ratio):
            raise AssertionError(
                f"block topology did not cut notify bytes by the locality "
                f"factor at n={n}: byte ratio {b_ratio:.2f} vs frontier "
                f"ratio {f_ratio:.2f}")

    # --- ragged transport axis (ISSUE 5): two-phase classed parcels -------
    # per-class payloads measured from the lowered module (each class
    # branch's sized all_to_all carries its own exchange_parcel_c<cap>
    # scope): every class but the last must sit strictly below the static
    # cap's bytes, and the last must equal them (ragged never ships more);
    # then a driven quiet-regime run must realize strictly fewer parcel
    # bytes than the static transport round-for-round.
    from repro.distributed.fap_spmd import run_fap_spmd

    from benchmarks.common import regime_iinj

    n_r = sizes[0]
    net_r = network.make_network(n_r, k_in=k_in, seed=0)
    cap = parcel_cap_for(REGIME_RATES["high"], n_r // n_shards, k_in,
                         n_shards)
    xspec = ExchangeSpec(parcel_cap=cap)
    spec = PaperNeuroSpec(n_neurons=n_r, k_in=k_in, ev_cap=16, t_end=100.0)
    fn, args, sh = build_fap_round(model, spec, mesh, optimized=True,
                                   transport="sparse_ragged", exchange=xspec,
                                   net=net_r)
    txt = jax.jit(fn, in_shardings=sh).lower(*args).compile().as_text()
    from repro.distributed.exchange import class_tag
    ladder = xspec.class_ladder()
    by_class = collective_channel_bytes(
        txt, tags=tuple(class_tag(c) for c in ladder))
    per_class = [by_class[class_tag(c)] for c in ladder]
    static = parcel[("sparse", "high", n_r)]
    emit(f"exchange/bytes/ragged_classes/n{n_r}", 0.0,
         f"ladder={list(ladder)};per_class={per_class};static={static}")
    if not (per_class == sorted(per_class) and per_class[-1] == static
            and all(b < static for b in per_class[:-1]) and per_class[0] > 0):
        raise AssertionError(
            f"ragged class ladder bytes malformed: {per_class} vs static "
            f"{static}")
    # driven quiet run: the counts phase must route ~every round through
    # the smallest class
    iinj_q = regime_iinj(n_r, "quiet", seed=1)
    kw = dict(mesh=mesh, optimized=True, exchange=xspec, max_rounds=8,
              ev_cap=16)
    res_s, r_s = run_fap_spmd(model, net_r, iinj_q, 4.0, transport="sparse",
                              **kw)
    res_g, r_g = run_fap_spmd(model, net_r, iinj_q, 4.0,
                              transport="sparse_ragged", **kw)
    sb = res_s.comm["parcel_bytes"]
    rb = res_g.comm["parcel_bytes"]
    emit("exchange/ragged/realized_quiet", 0.0,
         f"ragged={rb};static={sb};rounds={r_g};"
         f"tightening={sb / max(1, rb):.2f}x")
    if not (r_s == r_g and 0 < rb < sb):
        raise AssertionError(
            f"ragged transport did not tighten quiet-run parcel bytes: "
            f"{rb} vs static {sb} ({r_g}/{r_s} rounds)")
    if rb > r_g * per_class[-1]:
        raise AssertionError(
            f"ragged realized bytes exceed the static cap: {rb} > "
            f"{r_g * per_class[-1]}")


if __name__ == "__main__":
    if "--worker" in sys.argv:
        _worker()
    else:
        run()
