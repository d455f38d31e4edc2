"""The scheduler round names its stages: every ``exec_common.ROUND_STAGES``
scope reaches the compiled module's ``op_name`` metadata of the compact lab
round (where the benchmark joins device time to stages); and the round
counters of ``SchedStats`` equal what the carry says on the host, and
``sched_metrics`` reports them per round."""
import re

import jax
import numpy as np
import pytest

from repro.core import exec_common as xc
from repro.core import exec_fap, morphology, network
from repro.core.cell import CellModel
from repro import sched

_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_components(hlo_text: str) -> set:
    """Every ``/``-delimited component of every ``op_name`` in the text."""
    return {c for op in _OP_NAME.findall(hlo_text) for c in op.split("/")}


@pytest.fixture(scope="module")
def model():
    return CellModel(morphology.soma_only())


def _round_text(run):
    carry = run.init_carry()
    return jax.jit(run.round_body).lower(carry).compile().as_text()


def test_round_stages_named_once():
    assert len(set(xc.ROUND_STAGES)) == len(xc.ROUND_STAGES)
    assert all(re.fullmatch(r"(fap|bdf)\.[a-z_]+", s)
               for s in xc.ROUND_STAGES)


def test_compact_lab_round_carries_every_stage(model):
    """The lab's stack at N = 512: compact batch, wheel queue, incremental
    horizon, compact fan-out."""
    n = 512
    net = network.make_network(n, k_in=16, seed=3)
    run = exec_fap.make_fap_vardt_runner(
        model, net, 0.16, 5.0, batch="compact", batch_cap=64,
        fanout="compact", queue="wheel",
        wheel=sched.WheelSpec.auto(net))
    have = scope_components(_round_text(run))
    missing = [s for s in xc.ROUND_STAGES if s not in have]
    assert not missing, missing


def test_round_counters_match_the_carry(model):
    """Over host-stepped rounds, what the incremental horizon touches (the
    batch's lanes and the out-edge slots of moved clocks) and the rounds
    that ran the spike insert equal what the carry before and after each
    round gives on the host."""
    n, cap = 64, 16
    net = network.make_network(n, k_in=4, seed=3)
    iinj = np.linspace(0.15, 0.6, n)
    run = exec_fap.make_fap_vardt_runner(model, net, iinj, 30.0,
                                         batch="compact", batch_cap=cap,
                                         fanout="compact")
    out_deg = np.bincount(np.asarray(net.pre), minlength=n)
    step = jax.jit(run.round_body)
    carry = run.init_carry()
    want = {"horizon_live": 0, "fanout_rounds": 0}
    batch_live = 0
    for _ in range(40):
        sts, rec, horizon = carry[0], carry[2], run.horizon_of(carry)
        t0 = np.asarray(sts.t)
        runnable = int((t0 < np.asarray(horizon) - 1e-12).sum())
        count0 = int(np.asarray(rec.count).sum())
        carry = step(carry)
        moved = np.asarray(carry[0].t) != t0
        batch_live += min(runnable, cap)       # the cap binds: ties fill
        want["horizon_live"] += min(runnable, cap) + int(
            out_deg[moved].sum())
        want["fanout_rounds"] += int(
            int(np.asarray(carry[2].count).sum()) > count0)
    stats = jax.device_get(run.pack(carry).counters["stats"])
    got = {k: int(getattr(stats, k)) for k in want}
    assert got == want
    assert int(stats.stepped) == batch_live
    assert 0 < got["fanout_rounds"] < 40       # some rounds spiked, not all
    m = xc.sched_metrics(stats)
    assert m["horizon_live_per_round"] == got["horizon_live"] / 40
    assert m["fanout_round_frac"] == got["fanout_rounds"] / 40


def test_dense_round_leaves_the_compact_counters_at_zero(model):
    """The dense round inserts every round and recomputes the horizon
    whole: the compact path's counters stay 0."""
    net = network.make_network(32, k_in=4, seed=3)
    run = exec_fap.make_fap_vardt_runner(model, net, 0.3, 1.0)
    step = jax.jit(run.round_body)
    carry = run.init_carry()
    for _ in range(5):
        carry = step(carry)
    stats = run.pack(carry).counters["stats"]
    assert int(stats.rounds) == 5
    assert int(stats.fanout_rounds) == int(stats.horizon_live) == 0
