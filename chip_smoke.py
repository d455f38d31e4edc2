"""Drive the FAP simulator's main path once on a TPU and check what comes out.

    python chip_smoke.py                  # one chip: phases 1-4
    python chip_smoke.py --chips 4        # the SPMD round on a 4-chip mesh only

Phases, all in this process on ``jax.devices()[0]``:

1. dense    ``exec_fap.make_fap_vardt_runner`` with its defaults (dense batch,
            dense queue, sort select, scatter horizon) on the laboratory
            experiment: a 65,536-neuron soma-only network with 16 synapses per
            neuron, driven by the paper's Fig. 8/10 regime mix.
2. compact  The same network and drive through the activity-proportional
            stack: ``batch="compact"``, ``batch_cap=1024``,
            ``fanout="compact"``, ``queue="wheel"`` — every main-path Pallas
            kernel.  Its frontier overflows the cap, so work rolls to later
            rounds and the trains agree with phase 1 within the solver's
            envelope (see ``ENV_P99``).
3. anchor   At N=256: the default runner on the device and on the host CPU,
            and the compact stack on the device with ``batch_cap`` = N.
            The compact run must equal the dense one exactly (nothing
            rolls), which holds the compiled kernels to an exact check.
            The TPU emulates f64 with f32 pairs, so device and CPU agree
            within the envelope; the deviation is printed.
4. service  The CLI's own entry point, ``repro.launch.serve.main(["--sim",
            ...])``: 8 tenants of 4,096 neurons on 4 lanes, to completion.

Every run must drop no event, fail no integration, and count exactly the
events its own spike train implies (``implied_events``).

``--chips 4`` runs only ``distributed.fap_spmd.run_fap_spmd`` on a 2x2 mesh
of the first four devices: the sparse_ragged transport with the compact
batch, compact fan-out and incremental horizon, against the allgather
transport on the same mesh, event for event.

Each phase prints one ``phase {json}`` line: compile and run seconds (timed
to ``block_until_ready``), rounds, spikes, the solver and scheduler counts,
the device's peak bytes in use, and which implementation each kernel
dispatch picked.  The last line of standard output is
``{"ok": true, "device": {...}}``; a failed phase raises, and the script then
exits non-zero without that line.  Without a TPU it refuses to run.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# Spike-train agreement between two runs of one network (times in ms).
# Same schedule and arithmetic, different executables: XLA fuses them
# differently, and a last bit can move an adaptive step, so hold them to
# 1e-6 ms, far below the 0.1 ms a train is read at.
T_TOL_SAME = 1e-6
# Two correct integrations that stop the BDF at other points: a compact
# batch whose frontier exceeds batch_cap (the overflow rolls to later rounds,
# so lanes stop at other horizons), or the TPU's f64-as-f32-pairs against
# the CPU's IEEE f64.  At atol = 1e-3 such runs differ as far as the
# solver's accuracy allows, and a neuron driven close to threshold may
# even fire once more in one of them.  Measured on the CPU (20 ms of the
# lab mix): against a 100x tighter atol the default run's paired spikes
# move by p99 0.10 ms, max 0.75 ms, with 1 of 851 spikes unpaired
# (N=4096); a rolled schedule (batch_cap = N/64) against the dense one by
# p99 0.17 / 0.13 ms, max 0.72 / 0.70 ms, 0 / 2 unpaired (N=1024 / 4096).
# On a TPU v5e at N=65,536 (batch_cap 1,024) the rolled schedule moved
# paired spikes by p99 0.126 ms, max 1.39 ms, with 43 of 13,171 (0.33 %)
# unpaired, none before 9.7 ms.  Bounds: p99 of paired spikes <= 0.25 ms,
# and at most 0.5 % of spikes (or 2) unpaired.
ENV_P99 = 0.25
ENV_UNPAIRED = 0.005


class CompileClock:
    """Seconds the XLA backend spends compiling inside a block (tracing
    and lowering not included)."""

    def __init__(self):
        self.seconds = 0.0

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def require_tpu(count: int = 1):
    """The devices to run on; exits non-zero unless JAX sees ``count`` TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX finds no TPU (platform "
                         f"{devs[0].platform!r}); this smoke runs only on the "
                         "chip and does not fall back to the CPU")
    if len(devs) < count:
        raise SystemExit(f"chip_smoke: {count} TPU devices wanted, "
                         f"{len(devs)} found")
    return devs


def lab_network(n: int, seed: int):
    """The laboratory experiment: k_in=16 uniform wiring and the paper's
    Fig. 8/10 regime mix (``benchmarks.lab_experiment_fig8``)."""
    from benchmarks.lab_experiment_fig8 import mixture_currents
    from repro.core import network
    net = network.make_network(n, k_in=16, seed=seed)
    iinj, _ = mixture_currents(n, seed=seed)
    return net, iinj


def soma_model():
    from repro.core import morphology
    from repro.core.cell import CellModel
    return CellModel(morphology.soma_only())


def peak_bytes(device=None):
    import jax
    stats = (device or jax.devices()[0]).memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def trains(res):
    """Per-neuron spike counts and recorded times (host arrays)."""
    import numpy as np
    return np.asarray(res.rec.count), np.asarray(res.rec.times)


def implied_events(res, net, t_end: float) -> int:
    """Events the run's own spike train sends to arrive before t_end —
    exactly what its ``n_events`` must count once every clock is at
    t_end."""
    import numpy as np
    counts, times = trains(res)
    pre, delay = np.asarray(net.pre), np.asarray(net.delay)
    sent = counts[pre] > 0
    return int((times[pre[sent]] + delay[sent][:, None] < t_end).sum())


def agreement(a, b) -> dict:
    """Spike-by-spike comparison of two runs: spikes are paired in order
    per neuron; the rest are unpaired (``first_unpaired_ms`` says whether
    they sit mid-run or at t_end)."""
    import numpy as np
    ca, ta = trains(a)
    cb, tb = trains(b)
    k = np.minimum(ca, cb)[:, None]
    slot = np.arange(ta.shape[1])[None, :]
    d = np.abs(ta[slot < k] - tb[slot < k])
    extra = (slot >= k) & (slot < np.maximum(ca, cb)[:, None])
    t_extra = np.where(slot < ca[:, None], ta, tb)[extra]
    return {"paired": int(d.size),
            "max_dt_ms": float(d.max()) if d.size else 0.0,
            "p99_dt_ms": float(np.percentile(d, 99)) if d.size else 0.0,
            "p50_dt_ms": float(np.median(d)) if d.size else 0.0,
            "unpaired": int(extra.sum()),
            "first_unpaired_ms": float(t_extra.min()) if t_extra.size
            else None}


def check_agreement(ag: dict, same: bool, what: str):
    if same:
        if ag["unpaired"] or ag["max_dt_ms"] > T_TOL_SAME:
            raise AssertionError(f"{what}: trains differ {ag}")
        return
    allowed = max(2, int(ENV_UNPAIRED * (ag["paired"] + ag["unpaired"])))
    if ag["p99_dt_ms"] > ENV_P99 or ag["unpaired"] > allowed:
        raise AssertionError(f"{what}: trains outside the envelope {ag}")


def check_run(res, rounds, net, t_end: float):
    import numpy as np
    if int(res.dropped) != 0:
        raise AssertionError(f"{int(res.dropped)} events dropped")
    if bool(res.failed):
        raise AssertionError("integrator failure")
    if int(res.rec.overflow) != 0:
        raise AssertionError(f"spike record overflowed by "
                             f"{int(res.rec.overflow)}")
    if not np.isfinite(np.asarray(res.y_final)).all():
        raise AssertionError("non-finite final state")
    if int(rounds) <= 0:
        raise AssertionError("no round ran")
    want = implied_events(res, net, t_end)
    if int(res.n_events) != want:
        raise AssertionError(f"n_events {int(res.n_events)} != {want} "
                             "implied by the spike train")


def summary(res, rounds) -> dict:
    from repro.core import exec_common as xc
    out = {"rounds": int(rounds),
           "spikes": int(res.rec.count.sum()),
           "n_events": int(res.n_events),
           "n_resets": int(res.n_resets),
           "dropped": int(res.dropped),
           "failed": bool(res.failed)}
    if res.solver is not None:
        out["solver"] = {k: int(v) for k, v in res.solver.items()}
    if res.sched is not None:
        out["sched"] = {k: (float(v) if isinstance(v, float) else v)
                        for k, v in xc.sched_metrics(res.sched).items()}
    if res.comm is not None:
        out["comm"] = {k: int(v) for k, v in res.comm.items()}
    return out


def report(phase: str, rec: dict):
    print("phase " + json.dumps({"phase": phase, **rec}), flush=True)


def timed_runner(run):
    """Compile a runner's jitted fast path ahead of time, then run it once.
    ``compile_s`` covers tracing, lowering and compiling (``xla_compile_s``
    the backend's share of it); ``run_s`` the executable alone, to
    ``block_until_ready``."""
    import jax
    with CompileClock() as cc:
        t0 = time.perf_counter()
        compiled = run.jitted.lower().compile()
        compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    res, rounds = jax.block_until_ready(compiled())
    run_s = time.perf_counter() - t0
    return res, rounds, {"compile_s": compile_s, "xla_compile_s": cc.seconds,
                         "run_s": run_s}


def kernel_report() -> dict:
    from repro.kernels import use_interpret
    from repro.kernels.event_wheel import ops as ew_ops
    return {"kernels": ew_ops.auto_impls(), "pallas_interpret": use_interpret()}


def phase_dense(model, net, iinj, t_end: float):
    """Phase 1: the default runner.  Returns its RunResult."""
    from repro.core import exec_fap
    run = exec_fap.make_fap_vardt_runner(model, net, iinj, t_end)
    res, rounds, times = timed_runner(run)
    check_run(res, rounds, net, t_end)
    report("dense", {"n": int(net.n), "t_end_ms": t_end, **times,
                     **summary(res, rounds), "kernels": "none (jnp path)",
                     "peak_bytes": peak_bytes()})
    return res


def phase_compact(model, net, iinj, t_end: float, ref, batch_cap: int = 1024):
    """Phase 2: the compact stack, checked against phase 1's result:
    identical trains where ``batch_cap`` covers the network, the solver's
    envelope where the frontier overflows the cap and work rolls."""
    from repro import sched
    from repro.core import exec_fap
    run = exec_fap.make_fap_vardt_runner(
        model, net, iinj, t_end, batch="compact", batch_cap=batch_cap,
        fanout="compact", queue="wheel", wheel=sched.WheelSpec.auto(net))
    res, rounds, times = timed_runner(run)
    same = run.batch_cap >= int(net.n)
    ag = agreement(ref, res)
    report("compact", {"n": int(net.n), "t_end_ms": t_end,
                       "batch_cap": run.batch_cap, "spike_cap": run.spike_cap,
                       **times, **summary(res, rounds), **kernel_report(),
                       "vs_dense": ag, "rolled": not same,
                       "peak_bytes": peak_bytes()})
    check_run(res, rounds, net, t_end)
    check_agreement(ag, same, "compact vs dense")
    return res


def phase_anchor(model, t_end: float, seed: int, n: int = 256):
    """Phase 3, on a network small enough for the host CPU: the default
    runner on the device, the compact stack on the device with a
    ``batch_cap`` that covers the network (so no work rolls and its trains
    must equal the dense ones exactly: the compiled kernels' exact check),
    and the default runner built and run on the host CPU, compared with the
    device within the envelope.  Returns the device-vs-CPU agreement."""
    import jax
    from repro import sched
    from repro.core import exec_fap
    net, iinj = lab_network(n, seed)
    res, rounds, times = timed_runner(
        exec_fap.make_fap_vardt_runner(model, net, iinj, t_end))
    res_k, rounds_k, times_k = timed_runner(exec_fap.make_fap_vardt_runner(
        model, net, iinj, t_end, batch="compact", batch_cap=n,
        fanout="compact", queue="wheel", wheel=sched.WheelSpec.auto(net)))
    with jax.default_device(jax.devices("cpu")[0]):
        res_c, rounds_c, times_c = timed_runner(
            exec_fap.make_fap_vardt_runner(model, net, iinj, t_end))
    ag_k = agreement(res, res_k)
    ag = agreement(res_c, res)
    report("anchor", {"n": n, "t_end_ms": t_end, **times,
                      **summary(res, rounds),
                      "compact": {"batch_cap": n, **times_k,
                                  **summary(res_k, rounds_k),
                                  **kernel_report(), "vs_dense": ag_k},
                      "cpu": {**times_c, **summary(res_c, rounds_c),
                              "vs_device": ag},
                      "peak_bytes": peak_bytes()})
    for r, nr in ((res, rounds), (res_k, rounds_k), (res_c, rounds_c)):
        check_run(r, nr, net, t_end)
    check_agreement(ag_k, True, "compact vs dense (cap covers N)")
    if int(res_k.n_events) != int(res.n_events):
        raise AssertionError(f"compact n_events {int(res_k.n_events)} != "
                             f"dense {int(res.n_events)}")
    check_agreement(ag, False, "device vs CPU")
    return ag


_SERVED = re.compile(r"served (\d+) tenants in (\d+) rounds .*?: (\d+) "
                     r"completed, (\d+) evicted, (\d+) rejected")


def phase_service(n: int = 4096, tenants: int = 8, lanes: int = 4,
                  t_end: float = 6.0):
    """Phase 4: the simulation service through its CLI entry point.  The
    service asserts its own accounting; every tenant must complete."""
    from repro.launch import serve
    argv = ["--sim", "--n", str(n), "--tenants", str(tenants),
            "--lanes", str(lanes), "--t-end", str(t_end)]
    buf = io.StringIO()
    with CompileClock() as cc, contextlib.redirect_stdout(buf):
        t0 = time.perf_counter()
        rc = serve.main(argv)
        wall = time.perf_counter() - t0
    out = buf.getvalue()
    sys.stdout.write(out)
    m = _SERVED.search(out)
    if rc != 0 or m is None:
        raise AssertionError(f"service exited {rc}: {out!r}")
    submitted, rounds, completed, evicted, rejected = map(int, m.groups())
    report("service", {"n_per_tenant": n, "tenants": tenants, "lanes": lanes,
                       "t_end_ms": t_end, "xla_compile_s": cc.seconds,
                       "run_s": wall - cc.seconds, "wall_s": wall,
                       "rounds": rounds, "completed": completed,
                       "evicted": evicted, "rejected": rejected,
                       "peak_bytes": peak_bytes()})
    if completed != tenants or submitted != tenants:
        raise AssertionError(f"{completed} of {tenants} tenants completed")


def phase_spmd(model, net, iinj, t_end: float, devices,
               spike_cap: int = 1024):
    """``--chips 4``: the SPMD round with the sparse_ragged transport, the
    compact batch and fan-out and the incremental horizon, against the
    allgather transport on the same mesh.  ``batch_cap`` is left at the
    shard width, so no work rolls and the trains must be identical.

    Each round program takes minutes to compile for v5e, so both are first
    compiled side by side into the persistent compile cache
    (``precompile_spmd_rounds``); the runs, one after the other, then read
    them back."""
    import jax
    from repro.distributed.exchange import ExchangeSpec
    from repro.distributed.fap_spmd import run_fap_spmd
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2), ("data", "model"), devices=devices[:4])
    n_local = int(net.n) // 4
    configs = {
        "allgather": dict(transport="allgather"),
        # a (source, dest) parcel row holds at most n_local spikes, so a
        # parcel_cap of n_local can never drop; the ragged classes ship
        # n_local/8 or n_local/2 in the rounds where that fits
        "sparse_ragged": dict(
            transport="sparse_ragged",
            exchange=ExchangeSpec(parcel_cap=n_local),
            batch="compact", fanout="compact", spike_cap=spike_cap,
            horizon="incremental")}

    with CompileClock() as cc:
        t0 = time.perf_counter()
        precompile_spmd_rounds(model, net, t_end, mesh, configs)
        report("spmd_precompile", {"wall_s": time.perf_counter() - t0,
                                   "xla_compile_s": cc.seconds})
    runs = {}
    for name, kw in configs.items():
        with CompileClock() as cc:
            t0 = time.perf_counter()
            res, rounds = run_fap_spmd(model, net, iinj, t_end, mesh,
                                       max_rounds=1_000_000, **kw)
            jax.block_until_ready(res)
            wall = time.perf_counter() - t0
        runs[name] = res
        report(f"spmd_{name}", {
            "n": int(net.n), "t_end_ms": t_end, "mesh": "2x2",
            "xla_compile_s": cc.seconds, "run_s": wall - cc.seconds,
            "wall_s": wall, **summary(res, rounds), **kernel_report(),
            "memory_per_device": [
                {"id": d.id, **{k: (d.memory_stats() or {}).get(k)
                                for k in ("bytes_in_use",
                                          "peak_bytes_in_use")}}
                for d in devices[:4]]})
        check_run(res, rounds, net, t_end)
    ag = agreement(runs["allgather"], runs["sparse_ragged"])
    report("spmd_compare", ag)
    check_agreement(ag, True, "sparse_ragged vs allgather")


def precompile_spmd_rounds(model, net, t_end: float, mesh, configs: dict):
    """Compile the SPMD round of each ``run_fap_spmd`` configuration, all
    on their own threads, into the persistent compile cache, from which
    ``run_fap_spmd``'s own compile of the same round then reads it.  The
    round is built as ``run_fap_spmd`` builds it with its defaults
    (``optimized``, ``ev_cap`` 32, ``horizon_cap`` 2.0); nothing runs
    here."""
    from concurrent.futures import ThreadPoolExecutor

    import jax
    from repro import sched
    from repro.distributed.fap_spmd import PaperNeuroSpec, build_fap_round
    spec = PaperNeuroSpec(n_neurons=int(net.n), k_in=sched.grouped_k(net),
                          ev_cap=32, t_end=t_end, horizon_cap=2.0)

    def compile_round(kw):
        fn, args, shardings = build_fap_round(model, spec, mesh,
                                              optimized=True, net=net, **kw)
        jax.jit(fn, in_shardings=shardings).lower(*args).compile()

    with ThreadPoolExecutor(len(configs)) as pool:
        for f in [pool.submit(compile_round, kw) for kw in configs.values()]:
            f.result()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--n", type=int, default=65536,
                    help="neurons in the phase 1/2 (or SPMD) network")
    ap.add_argument("--t-end", type=float, default=20.0,
                    help="biological ms simulated")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = require_tpu(args.chips)
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    d0 = devices[0]
    print(f"device platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)} compile_cache={cache}", flush=True)

    model = soma_model()
    net, iinj = lab_network(args.n, args.seed)
    if args.chips == 4:
        phase_spmd(model, net, iinj, args.t_end, devices)
    else:
        ref = phase_dense(model, net, iinj, args.t_end)
        phase_compact(model, net, iinj, args.t_end, ref)
        phase_anchor(model, args.t_end, args.seed)
        phase_service()
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
