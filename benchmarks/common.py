"""Shared benchmark utilities: timed runs, CSV emission, cached calibration.

Scaling note (DESIGN.md §7): these suites were sized for one CPU core;
networks are scaled (64-512 neurons, 50-250 ms biological time) with the
paper's regime structure preserved.  Reported quantities are step counts,
event counts and wall-clock ratios — the same quantities the paper
reports.  Their wall times are CPU times, never device metrics.
"""
from __future__ import annotations

import functools
import json
import os
import time
import zlib

import numpy as np

from repro.core import morphology
from repro.core.calibrate import current_for_rate, threshold_current
from repro.core.cell import CellModel

CACHE = os.path.join(os.path.dirname(__file__), "_calibration.json")

# the five regimes of paper §4
REGIMES = {"quiet": 0.25, "slow": 1.5, "moderate": 6.5, "fast": 38.0,
           "burst": 55.8}

# structured-record accumulator behind the CSV stream: each suite's run()
# calls dump_json at its end; with REPRO_BENCH_JSON set the records land in
# BENCH_<suite>.json (the nightly CI uploads these as artifacts, so the
# perf trajectory is recorded instead of lost in job logs).
_RECORDS: list = []
_FLUSHED = 0


def emit(name: str, us_per_call: float, derived: str):
    _RECORDS.append({"name": name, "us_per_call": float(us_per_call),
                     "derived": derived})
    print(f"{name},{us_per_call:.1f},{derived}")


def record_csv(text: str):
    """Fold a subprocess worker's CSV stdout into the record accumulator
    (the worker's emit() prints land in a pipe, not this process)."""
    for ln in text.splitlines():
        parts = ln.split(",", 2)
        if len(parts) != 3:
            continue
        try:
            us = float(parts[1])
        except ValueError:
            continue
        _RECORDS.append({"name": parts[0], "us_per_call": us,
                         "derived": parts[2]})


def cpu_mesh_env(root: str, n_devices: int) -> dict:
    """Environment for a worker process that measures HLO bytes on an
    emulated mesh of ``n_devices`` host CPU devices.  ``JAX_PLATFORMS=cpu``
    keeps the worker off any accelerator: on a TPU host the parent already
    holds the chip, and the host-platform flag does not hide it."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.pathsep.join(
        [root, os.path.join(root, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    return env


def dump_json(suite: str):
    """Write records accumulated since the previous dump to
    BENCH_<suite>.json (no-op unless REPRO_BENCH_JSON is set, or when no
    new records arrived — a suite that already dumped internally must not
    be clobbered by the orchestrator's per-suite dump)."""
    global _FLUSHED
    recs, _FLUSHED = _RECORDS[_FLUSHED:], len(_RECORDS)
    if not recs or not os.environ.get("REPRO_BENCH_JSON"):
        return
    out_dir = os.environ.get("REPRO_BENCH_DIR", ".")
    payload = {"suite": suite,
               "quick": os.environ.get("REPRO_BENCH_QUICK") == "1",
               "records": recs}
    with open(os.path.join(out_dir, f"BENCH_{suite}.json"), "w") as f:
        json.dump(payload, f, indent=2)


@functools.lru_cache(maxsize=None)
def soma_model() -> CellModel:
    return CellModel(morphology.soma_only())


@functools.lru_cache(maxsize=None)
def branched_model() -> CellModel:
    """Small L5-pyramidal-like tree (the paper's single-cell experiments)."""
    return CellModel(morphology.branched_tree(depth=2, seg_per_branch=2))


def calibration(model_kind: str = "soma") -> dict:
    """Threshold current, onset-rate current and measured onset rate.

    The classic HH soma is type-II excitable: under DC drive it cannot fire
    below the ~50 Hz onset rate, so low *network* regimes (0.25-6.5 Hz mean)
    are realised as population mixtures — a fraction of neurons at onset
    rate, the rest just below threshold (recorded in DESIGN.md §8)."""
    cache = {}
    if os.path.exists(CACHE):
        cache = json.load(open(CACHE))
    if model_kind in cache:
        return cache[model_kind]
    from repro.core.calibrate import _n_spikes
    model = soma_model() if model_kind == "soma" else branched_model()
    i_th = threshold_current(model)
    i_active = current_for_rate(model, 45.0, i_th, t_end=1000.0)
    r_active = _n_spikes(model, i_active, 1000.0)
    i_burst = current_for_rate(model, 58.0, i_th, t_end=1000.0)
    r_burst = _n_spikes(model, i_burst, 1000.0)
    entry = {"i_threshold": i_th, "i_active": i_active,
             "r_active_hz": float(r_active), "i_burst": i_burst,
             "r_burst_hz": float(r_burst)}
    cache[model_kind] = entry
    json.dump(cache, open(CACHE, "w"), indent=2)
    return entry


def regime_iinj(n: int, regime: str, seed: int = 0,
                model_kind: str = "soma") -> np.ndarray:
    """Per-neuron currents whose population mean rate matches the regime.

    The regime name is folded into the rng seed with a *deterministic*
    hash (crc32): python's ``hash()`` is salted per process, which made
    spike counts differ across processes for the same arguments —
    cross-process benchmark comparisons (nightly BENCH json vs local
    runs, orchestrator vs worker) silently compared different networks.
    """
    cal = calibration(model_kind)
    rng = np.random.default_rng(seed + zlib.crc32(regime.encode()) % 1000)
    target = REGIMES[regime]
    if regime == "burst":
        base = np.full(n, cal["i_burst"])
        return base * (1.0 + 0.01 * rng.standard_normal(n))
    frac = min(1.0, target / max(cal["r_active_hz"], 1.0))
    active = rng.random(n) < frac
    iinj = np.where(active, cal["i_active"], 0.80 * cal["i_threshold"])
    return iinj * (1.0 + 0.01 * rng.standard_normal(n))


def timeit(fn, repeats: int = 1):
    """(result, seconds) with one warm-up call (compile excluded)."""
    import jax
    jax.block_until_ready(fn())
    t0 = time.time()
    for _ in range(repeats):
        out = jax.block_until_ready(fn())
    return out, (time.time() - t0) / repeats
