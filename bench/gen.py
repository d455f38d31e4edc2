"""Inputs of every cell, made from ``--seed``: networks and drive
currents.

These are the benchmark's own copies of the repository's generators (see
``bench/README.md`` for where each came from), so that a change to the
program or to ``benchmarks/`` cannot move the yardstick.  A traffic file
names a ``drive`` generator and its parameters; nothing here is specific
to one cell.
"""
from __future__ import annotations

import zlib

import numpy as np

# Soma-only HH calibration (threshold current, active and burst currents,
# their rates), as measured by ``repro.core.calibrate`` for the 20 um soma.
SOMA_CALIBRATION = {
    "i_threshold": 0.028076171875,
    "i_active": 0.07842135429382324,
    "r_active_hz": 28.0,
    "i_burst": 0.08555006980895996,
    "r_burst_hz": 58.0,
}

# The five regimes of the paper (arXiv:1907.00670 §4), mean rate in Hz.
REGIMES = {"quiet": 0.25, "slow": 1.5, "moderate": 6.5, "fast": 38.0,
           "burst": 55.8}

# Share of neurons in each regime in the laboratory experiment (Figs. 8/10).
MIX_SHARES = {"quiet": 0.3143, "slow": 0.3844, "moderate": 0.2702,
              "fast": 0.0310, "burst": 0.0001}

MIN_DELAY = 0.1      # ms
MAX_DELAY = 7.0      # ms


def rng_for(seed: int, salt: str = "") -> np.random.Generator:
    """A generator for ``seed`` (any non-negative integer, also above 2**32)
    and a named stream, so that the network, the drive and the requests
    of one seed are independent draws."""
    return np.random.default_rng([int(seed) % (1 << 63),
                                  zlib.crc32(salt.encode())])


def make_network(n: int, k_in: int, seed: int, *, pct_gaba: float = 0.2,
                 w_exc: float = 1.0e-4, w_inh: float = 3.0e-4,
                 max_out_degree: int = 0) -> dict:
    """Uniform random wiring grouped by postsynaptic neuron: edge
    ``j * k_in + e`` is the e-th in-edge of neuron j; presynaptic ids are
    uniform over the other neurons; delays lognormal(0, 0.75) clipped to
    [0.1, 7] ms; a share ``pct_gaba`` of edges inhibitory; weights
    exponential with means ``w_exc`` / ``w_inh`` (uS).

    ``max_out_degree`` > 0 pins the largest out-degree, which is a shape of
    the compact fan-out's out-edge table: edges of a neuron above it are
    drawn again, and the neuron with most out-edges then takes edges from
    random others until it has exactly that many.  Set well above the
    natural maximum, this moves a handful of the network's edges.
    Returns numpy arrays: pre, post, delay, w_ampa, w_gaba."""
    rng = rng_for(seed, "network")
    post = np.repeat(np.arange(n, dtype=np.int32), k_in)
    pre = rng.integers(0, n, size=n * k_in).astype(np.int32)
    clash = pre == post
    pre[clash] = (pre[clash] + 1) % n
    if max_out_degree > 0:
        pre = _pin_out_degree(pre, post, n, max_out_degree, rng)
    delay = np.clip(rng.lognormal(0.0, 0.75, size=n * k_in),
                    MIN_DELAY, MAX_DELAY)
    is_gaba = rng.random(n * k_in) < pct_gaba
    w = rng.exponential(1.0, size=n * k_in)
    return {"n": n, "pre": pre, "post": post, "delay": delay,
            "w_ampa": np.where(is_gaba, 0.0, w * w_exc),
            "w_gaba": np.where(is_gaba, w * w_inh, 0.0)}


def _pin_out_degree(pre, post, n, d_max, rng):
    pre = pre.copy()
    for _ in range(100):
        deg = np.bincount(pre, minlength=n)
        over = np.flatnonzero(deg > d_max)
        if not over.size:
            break
        for j in over:
            edges = np.flatnonzero(pre == j)[d_max:]
            new = rng.integers(0, n, size=edges.size).astype(np.int32)
            new[new == post[edges]] = (new[new == post[edges]] + 1) % n
            pre[edges] = new
    deg = np.bincount(pre, minlength=n)
    if deg.max() > d_max:
        raise ValueError(f"cannot hold out-degree at {d_max}")
    hub = int(deg.argmax())
    while deg[hub] < d_max:
        e = int(rng.integers(0, pre.size))
        if pre[e] != hub and post[e] != hub and deg[pre[e]] > 1:
            deg[pre[e]] -= 1
            pre[e] = hub
            deg[hub] += 1
    return pre


def regime_iinj(n: int, regime: str, seed: int) -> np.ndarray:
    """Per-neuron currents (nA) whose population mean rate is the regime's:
    a share rate/28 Hz of neurons at the active current, the rest at 0.8 x
    threshold; the burst regime drives every neuron at the burst current.
    1 % multiplicative jitter."""
    cal = SOMA_CALIBRATION
    rng = rng_for(seed, "regime:" + regime)
    if regime == "burst":
        return cal["i_burst"] * (1.0 + 0.01 * rng.standard_normal(n))
    frac = min(1.0, REGIMES[regime] / cal["r_active_hz"])
    active = rng.random(n) < frac
    iinj = np.where(active, cal["i_active"], 0.80 * cal["i_threshold"])
    return iinj * (1.0 + 0.01 * rng.standard_normal(n))


def mixture_currents(n: int, seed: int, shares: dict = None) -> np.ndarray:
    """The laboratory experiment's drive: each neuron is assigned a regime
    with the Fig. 8/10 shares and driven by that regime's current."""
    shares = shares or MIX_SHARES
    rng = rng_for(seed, "mix")
    names = list(shares)
    p = np.array([shares[k] for k in names], np.float64)
    assign = rng.choice(len(names), size=n, p=p / p.sum())
    cur = np.empty(n)
    for i, name in enumerate(names):
        mask = assign == i
        if mask.any():
            cur[mask] = regime_iinj(int(mask.sum()), name, seed * 8 + i)
    return cur


def drive(traffic: dict, n: int, seed: int) -> np.ndarray:
    """The per-neuron constant current a traffic file asks for."""
    kind = traffic["drive"]
    if kind == "regime_mix":
        return mixture_currents(n, seed, traffic.get("shares"))
    if kind == "regime":
        return regime_iinj(n, traffic["regime"], seed)
    raise ValueError(f"unknown drive generator {kind!r}")
