"""Preemption tolerance, single-host: round-boundary SimCarry
checkpoint/restore, kill/resume identity, the health watchdog's
quarantine-and-rollback, and the detected-never-silent escalation ladder.

The identity contract: every robust run shares ONE compiled round per
runner (``run(**knobs)`` selects the host-stepped driver at call time),
so a killed-and-resumed or poisoned-and-rolled-back run is bit-identical
to the uninterrupted host-stepped run.  The jitted ``while_loop`` fast
path agrees to floating-point ulp only (XLA fuses the standalone round
differently) — asserted as such, not as bitwise.
"""
import numpy as np
import pytest

from repro.checkpoint import FaultPlan, SimulatedFailure
from repro.core import exec_bsp, exec_fap, morphology, network
from repro.core import exec_common as xc
from repro.core.cell import CellModel

N = 12
T_END = 12.0

# (label, runner kwargs) — dense/wheel queues x dense/compact batch; the
# compact config also exercises the incremental horizon hcarry leg of the
# SimCarry snapshot
CONFIGS = {
    "dense": dict(queue="dense"),
    "wheel": dict(queue="wheel"),
    "compact": dict(queue="dense", batch="compact", batch_cap=8),
}


@pytest.fixture(scope="module")
def setup():
    model = CellModel(morphology.soma_only())
    net = network.make_network(N, k_in=4, seed=3)
    rng = np.random.default_rng(1)
    iinj = 0.16 + 0.004 * rng.standard_normal(N)
    return model, net, iinj


@pytest.fixture(scope="module")
def runners(setup):
    """One runner (one compiled round) per config — every scenario below
    reuses these, so the whole module pays 3 compiles."""
    model, net, iinj = setup
    return {k: exec_fap.make_fap_vardt_runner(model, net, iinj, T_END, **kw)
            for k, kw in CONFIGS.items()}


@pytest.fixture(scope="module")
def baselines(runners):
    """Uninterrupted host-stepped run per config (the identity target)."""
    out = {}
    for k, run in runners.items():
        res, rounds = run(watchdog=True)
        assert not bool(res.failed) and int(res.dropped) == 0
        out[k] = (np.asarray(res.rec.times), np.asarray(res.rec.count),
                  int(rounds))
    return out


def _train(res):
    return np.asarray(res.rec.times), np.asarray(res.rec.count)


def test_fast_path_unchanged_and_close(runners, baselines):
    """run() with no knobs still takes the jitted while_loop (no health
    field) and agrees with the host-stepped driver to fp ulp."""
    run = runners["dense"]
    res, _ = run()
    assert res.health is None
    t0, c0, _ = baselines["dense"]
    t1, c1 = _train(res)
    assert np.array_equal(c0, c1)
    for i in range(N):
        np.testing.assert_allclose(np.sort(t0[i][:c0[i]]),
                                   np.sort(t1[i][:c1[i]]), atol=1e-6)


def test_health_populated(runners, baselines):
    res, rounds = runners["dense"](watchdog=True)
    h = res.health
    assert h["watchdog"] and h["checks"] == int(rounds)
    assert h["nonfinite_rounds"] == 0 and h["clock_regressions"] == 0
    assert h["horizon_violations"] == 0 and h["rollbacks"] == 0
    assert h["dropped_events"] == 0 and not h["rollback_exhausted"]
    assert h["straggler"]["recorded"] == int(rounds)


@pytest.mark.parametrize("cfg", list(CONFIGS))
def test_kill_resume_identity(runners, baselines, cfg, tmp_path):
    """SimulatedFailure mid-run; relaunch with resume=True -> the spike
    train is bit-identical to the uninterrupted run, across queue impls
    and the compact/incremental-horizon path."""
    run = runners[cfg]
    t0, c0, rounds0 = baselines[cfg]
    kill = max(2, rounds0 // 2)
    with pytest.raises(SimulatedFailure):
        run(checkpoint_every=4, ckpt_dir=str(tmp_path),
            fault=FaultPlan(fail_at_round=kill))
    res, rounds = run(checkpoint_every=4, ckpt_dir=str(tmp_path),
                      resume=True)
    t1, c1 = _train(res)
    assert np.array_equal(c0, c1)
    assert np.array_equal(t0, t1)
    assert int(rounds) == rounds0
    assert res.health["resumed_from"] == (kill // 4) * 4


def test_poison_watchdog_rollback_identity(runners, baselines, tmp_path):
    """An injected non-finite lane is detected the round it appears,
    rolled back to the last checkpoint, and the completed run is
    bit-identical — never silently propagated."""
    run = runners["dense"]
    t0, c0, _ = baselines["dense"]
    res, _ = run(checkpoint_every=4, ckpt_dir=str(tmp_path),
                 fault=FaultPlan(poison_at_round=9, poison_lane=3))
    assert res.health["nonfinite_rounds"] >= 1
    assert res.health["rollbacks"] >= 1
    assert not bool(res.failed)
    t1, c1 = _train(res)
    assert np.array_equal(c0, c1) and np.array_equal(t0, t1)


def test_rollback_exhaustion_escalates(runners, tmp_path):
    """A persistent fault (every-round poison) exhausts the bounded
    retries and escalates to RunResult.failed + health, never loops."""
    run = runners["dense"]
    res, _ = run(checkpoint_every=4, ckpt_dir=str(tmp_path),
                 max_rollbacks=1,
                 fault=FaultPlan(mutate=lambda r, c: xc.poison_lane(c, 0)))
    assert bool(res.failed)
    assert res.health["rollback_exhausted"]
    assert res.health["rollbacks"] == 1


def test_simcarry_roundtrip_every_field(runners, tmp_path):
    """Every SimCarry leaf — BDFState incl. the PR 6 Jacobian-cache
    fields, queue arrays, the horizon carry, spike cursor and counters —
    survives save/restore bitwise (mirrors the BDFState round-trip
    tests)."""
    run = runners["compact"]
    sc = run.pack(run.init_carry())
    # sanity: the snapshot really carries the PR 6 solver-state fields
    for f in ("gamma_saved", "nstlp", "factors", "zn", "t", "h"):
        assert hasattr(sc.sts, f)
    assert len(sc.hcarry) == 1          # in-edge clock table leg present
    xc.save_sim_checkpoint(str(tmp_path), 7, sc, extras={"k": 1})
    sc2, extras, skipped = xc.restore_sim_checkpoint(str(tmp_path), 7, sc)
    assert extras == {"k": 1} and skipped == []
    import jax
    flat = jax.tree_util.tree_flatten_with_path(sc)[0]
    flat2 = jax.tree_util.tree_flatten_with_path(sc2)[0]
    assert len(flat) == len(flat2)
    for (kp, a), (_, b) in zip(flat, flat2):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"leaf {jax.tree_util.keystr(kp)} not bitwise equal")


def test_simcarry_elastic_skip_reports_hcarry_only(runners, tmp_path):
    """A restore target whose hcarry width changed (elastic resume) keeps
    the like-value and reports the path; every other leaf restores."""
    run = runners["compact"]
    sc = run.pack(run.init_carry())
    xc.save_sim_checkpoint(str(tmp_path), 1, sc)
    import jax.numpy as jnp
    like = sc._replace(hcarry=(jnp.zeros(sc.hcarry[0].shape[0] // 2),))
    sc2, _, skipped = xc.restore_sim_checkpoint(str(tmp_path), 1, like)
    assert len(skipped) == 1 and "hcarry" in skipped[0]
    np.testing.assert_array_equal(np.asarray(sc2.sts.zn),
                                  np.asarray(sc.sts.zn))


def test_fallback_ladder_composes_with_checkpointing(setup, tmp_path):
    """spike_cap=1 forces the compact fan-out onto its dense fallback
    (identical events, never a drop) — kill/resume under that ladder must
    still be bit-identical and drop-free."""
    model, net, iinj = setup
    run = exec_fap.make_fap_vardt_runner(model, net, iinj, T_END,
                                         fanout="compact", spike_cap=1)
    res0, rounds0 = run(watchdog=True)
    assert int(res0.dropped) == 0 and not bool(res0.failed)
    with pytest.raises(SimulatedFailure):
        run(checkpoint_every=4, ckpt_dir=str(tmp_path),
            fault=FaultPlan(fail_at_round=max(2, int(rounds0) // 2)))
    res1, _ = run(checkpoint_every=4, ckpt_dir=str(tmp_path), resume=True)
    assert int(res1.dropped) == 0
    assert res1.health["dropped_events"] == 0
    assert np.array_equal(np.asarray(res0.rec.times),
                          np.asarray(res1.rec.times))


def test_bsp_kill_resume_identity(setup, tmp_path):
    """The BSP vardt runner shares the same driver: kill at a window
    boundary, resume, bit-identical spike train."""
    model, net, iinj = setup
    run = exec_bsp.make_bsp_vardt_runner(model, net, iinj, 6.0)
    res0 = run(watchdog=True)
    assert not bool(res0.failed)
    with pytest.raises(SimulatedFailure):
        run(checkpoint_every=10, ckpt_dir=str(tmp_path),
            fault=FaultPlan(fail_at_round=25))
    res1 = run(checkpoint_every=10, ckpt_dir=str(tmp_path), resume=True)
    assert res1.health["resumed_from"] == 20
    assert np.array_equal(np.asarray(res0.rec.times),
                          np.asarray(res1.rec.times))
    assert np.array_equal(np.asarray(res0.rec.count),
                          np.asarray(res1.rec.count))


def test_kill_resume_property(runners, baselines):
    """Hypothesis: kill at a RANDOM round under a random config; resume
    is bit-identical to the uninterrupted run.  All examples reuse the
    module's compiled runners (call-time knobs — no recompiles)."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")
    import shutil
    import tempfile

    @hyp.settings(max_examples=8, deadline=None,
                  suppress_health_check=list(hyp.HealthCheck))
    @hyp.given(cfg=st.sampled_from(sorted(CONFIGS)),
               frac=st.floats(0.1, 0.9), every=st.integers(2, 7))
    def prop(cfg, frac, every):
        run = runners[cfg]
        t0, c0, rounds0 = baselines[cfg]
        kill = max(1, int(rounds0 * frac))
        d = tempfile.mkdtemp()
        try:
            with pytest.raises(SimulatedFailure):
                run(checkpoint_every=every, ckpt_dir=d,
                    fault=FaultPlan(fail_at_round=kill))
            res, rounds = run(checkpoint_every=every, ckpt_dir=d,
                              resume=True)
            assert np.array_equal(c0, np.asarray(res.rec.count))
            assert np.array_equal(t0, np.asarray(res.rec.times))
            assert int(rounds) == rounds0
        finally:
            shutil.rmtree(d, ignore_errors=True)

    prop()
