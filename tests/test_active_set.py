"""Active-set compaction (ISSUE 4): the ``batch="compact"`` execution path
steps only the runnable frontier — compact -> gather -> advance -> scatter —
and must be *event-for-event identical* to the dense path:

  * FAP vardt: identical on all five topologies x both queue impls when the
    frontier fits ``batch_cap``; a forced overflow rolls work to later
    rounds (more rounds, zero drops, same physics to scheduler tolerance),
  * BSP vardt: identical at ANY cap (window chunks share the barrier
    horizon, so chunking never changes a lane's step sequence),
  * the gather-id compaction kernel matches its jnp oracle,
  * SchedStats telemetry rides RunResult and accounts every lane.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import exec_bsp, exec_common as xc, exec_fap
from repro.core import morphology, network
from repro.core.cell import CellModel
from repro.core.topology import TOPOLOGIES, TopologyConfig
from repro.kernels.event_wheel import event_wheel as ew_kernel
from repro.kernels.event_wheel import ops as ew_ops
from repro.kernels.event_wheel import ref as ew_ref

N, K, T_END = 16, 4, 8.0       # square N: grid2d needs one

TOPOS = {
    "uniform": "uniform",
    "block": TopologyConfig("block", n_blocks=4, p_in=0.9),
    "ring": TopologyConfig("ring", sigma=3.0),
    "grid2d": TopologyConfig("grid2d", n_blocks=4, sigma=2.0),
    "smallworld": TopologyConfig("smallworld", p_rewire=0.1),
}


@pytest.fixture(scope="module")
def model():
    return CellModel(morphology.soma_only())


@pytest.fixture(scope="module")
def iinj():
    rng = np.random.default_rng(1)
    return 0.16 + 0.004 * rng.standard_normal(N)


# Final-state tolerance: spike trains stay identical event for event, but
# XLA fuses the compact and dense executables differently, and a last-bit
# difference can move an adaptive step.  Both runs still meet the BDF
# error control (atol = 1e-3 mV / gate units), so their states may differ
# by a few atol; 10 * atol bounds it.
Y_FINAL_ATOL = 1e-2


def _exact_same(a, b):
    assert np.array_equal(np.asarray(a.rec.times), np.asarray(b.rec.times))
    assert np.array_equal(np.asarray(a.rec.count), np.asarray(b.rec.count))
    np.testing.assert_allclose(np.asarray(a.y_final), np.asarray(b.y_final),
                               rtol=0, atol=Y_FINAL_ATOL)
    assert int(a.n_events) == int(b.n_events)
    assert int(a.dropped) == int(b.dropped) == 0
    assert not bool(a.failed) and not bool(b.failed)


def _trains(res):
    ts, c = np.asarray(res.rec.times), np.asarray(res.rec.count)
    return [np.sort(ts[i][: c[i]]) for i in range(len(c))]


# ---------------------------------------------------------------------------
# gather-id compaction kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,cap", [(16, 4), (64, 16), (300, 32), (256, 300)])
def test_compact_ids_pallas_matches_ref(n, cap):
    rng = np.random.default_rng(n + cap)
    for frac in (0.0, 0.1, 0.5, 1.0):
        mask = jnp.asarray(rng.random(n) < frac)
        ia, ca = ew_ref.compact_ids_ref(mask, cap)
        ib, cb = ew_ops.compact_ids(mask, cap, impl="pallas")
        assert int(ca) == int(cb) == int(mask.sum())
        assert np.array_equal(np.asarray(ia), np.asarray(ib))
        # ids are the first `cap` set lanes in index order, sentinel-padded
        want = np.flatnonzero(np.asarray(mask))[:cap]
        got = np.asarray(ia)
        assert np.array_equal(got[: len(want)], want)
        assert np.all(got[len(want):] == n)


@pytest.mark.parametrize("n,cap,block_cap", [(300, 37, 8), (1024, 600, 64)])
def test_compact_ids_pallas_tiled_cap_matches_ref(n, cap, block_cap):
    """Caps above ``block_cap`` tile over the kernel's second grid axis,
    with a last cap block that is only partly used: fill fractions leave
    cap blocks full, partly filled and empty."""
    block_n = 128
    rng = np.random.default_rng(n + cap)
    for frac in (0.05, 0.3, 1.0):
        mask = jnp.asarray(rng.random(n) < frac)
        padded = jnp.concatenate([mask, jnp.zeros((-n) % block_n, bool)])
        ib, cb = ew_kernel.compact_ids_pallas(
            padded, cap=cap, block_n=block_n, block_cap=block_cap,
            interpret=True)
        ia, ca = ew_ref.compact_ids_ref(mask, cap)
        assert int(ca) == int(cb) == int(mask.sum())
        got = np.asarray(ib)
        # the kernel's sentinel is the padded width; the ref's is n
        np.testing.assert_array_equal(np.where(got >= n, n, got),
                                      np.asarray(ia))


def test_select_active_keeps_frontier_when_under_cap():
    rng = np.random.default_rng(0)
    t = jnp.asarray(rng.uniform(0.0, 5.0, 64))
    runnable = jnp.asarray(rng.random(64) < 0.3)
    sel = xc.select_active(runnable, t, 48)
    assert np.array_equal(np.asarray(sel), np.asarray(runnable))


def test_select_active_overflow_keeps_earliest():
    rng = np.random.default_rng(2)
    t = jnp.asarray(rng.uniform(0.0, 5.0, 64))
    runnable = jnp.ones((64,), bool)
    sel = np.asarray(xc.select_active(runnable, t, 8))
    assert 8 <= sel.sum() <= 9            # ties within bisection resolution
    # kept clocks are exactly the smallest ones
    kept = np.sort(np.asarray(t)[sel])
    assert np.all(kept[:8] == np.sort(np.asarray(t))[:8])


# ---------------------------------------------------------------------------
# FAP vardt: compact == dense event-for-event
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("topo", sorted(TOPOS))
@pytest.mark.parametrize("queue", ["dense", "wheel"])
def test_fap_compact_equals_dense(model, iinj, topo, queue):
    """Both compact knobs at once (ISSUE 4 batch + ISSUE 5 fan-out): the
    fully activity-proportional round == the dense round, event for
    event, on every topology x queue."""
    assert set(TOPOS) == set(TOPOLOGIES)
    net = network.make_network(N, k_in=K, seed=3, topology=TOPOS[topo])
    kw = dict(queue=queue)
    r_d, rounds_d = exec_fap.make_fap_vardt_runner(
        model, net, iinj, T_END, **kw)()
    r_c, rounds_c = exec_fap.make_fap_vardt_runner(
        model, net, iinj, T_END, batch="compact", fanout="compact", **kw)()
    assert int(r_d.rec.count.sum()) > 0        # network actually active
    _exact_same(r_d, r_c)
    assert int(rounds_d) == int(rounds_c)


def test_fap_compact_overflow_rolls_not_drops(model, iinj):
    """batch_cap far below the frontier: every round advances only the
    earliest lanes, overflow lanes roll to later rounds — no event is ever
    dropped and the physics stays within the scheduler-restriction
    tolerance of the k_select tests (different horizon sequences)."""
    net = network.make_network(N, k_in=K, seed=3)
    r_d, rounds_d = exec_fap.make_fap_vardt_runner(model, net, iinj, T_END)()
    r_c, rounds_c = exec_fap.make_fap_vardt_runner(
        model, net, iinj, T_END, batch="compact", batch_cap=4)()
    assert int(r_c.dropped) == 0
    assert int(r_c.rec.overflow) == 0
    assert not bool(r_c.failed)
    assert int(rounds_c) > int(rounds_d)       # work genuinely rolled
    td, tc = _trains(r_d), _trains(r_c)
    mismatched = sum(len(a) != len(b) for a, b in zip(td, tc))
    assert mismatched <= 1
    for a, b in zip(td, tc):
        if len(a) == len(b) and len(a):
            assert np.abs(a - b).max() < 0.25
    # occupancy telemetry: the capped batch is nearly always full
    m = xc.sched_metrics(r_c.sched)
    assert m["occupancy"] > 0.9
    assert int(r_c.sched.stepped) <= int(r_c.sched.lanes)


def test_fap_compact_composes_with_other_knobs(model, iinj):
    """compact x wheel queue x fused horizon x threshold k_select: the full
    sort-free stack stays event-for-event identical to its dense twin."""
    net = network.make_network(N, k_in=K, seed=3)
    kw = dict(queue="wheel", horizon_impl="fused", select="threshold",
              k_select=12)
    r_d, _ = exec_fap.make_fap_vardt_runner(model, net, iinj, T_END, **kw)()
    r_c, _ = exec_fap.make_fap_vardt_runner(
        model, net, iinj, T_END, batch="compact", **kw)()
    _exact_same(r_d, r_c)


def test_fap_dense_telemetry_measures_wasted_lanes(model, iinj):
    """The dense path dispatches N lanes per round; telemetry must report
    the wasted fraction the compact path exists to remove."""
    net = network.make_network(N, k_in=K, seed=3)
    r_d, rounds = exec_fap.make_fap_vardt_runner(model, net, iinj, T_END)()
    s = r_d.sched
    assert int(s.rounds) == int(rounds)
    assert int(s.lanes) == N * int(rounds)
    assert 0 <= int(s.stepped) <= int(s.lanes)
    assert int(s.runnable) == int(s.stepped)   # dense: all runnable step
    m = xc.sched_metrics(s)
    assert 0.0 <= m["wasted_lane_frac"] <= 1.0


# ---------------------------------------------------------------------------
# BSP vardt: compact == dense at ANY cap (chunks share the barrier horizon)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cap", [0, 5, 16])
def test_bsp_compact_identical_any_cap(model, iinj, cap):
    net = network.make_network(N, k_in=K, seed=3)
    r_d = exec_bsp.run_bsp_vardt(model, net, iinj, T_END)
    r_c = exec_bsp.run_bsp_vardt(model, net, iinj, T_END, batch="compact",
                                 batch_cap=cap)
    assert int(r_d.rec.count.sum()) > 0
    _exact_same(r_d, r_c)
    # dispatch accounting: every behind-barrier lane stepped exactly once
    assert int(r_c.sched.stepped) == int(r_c.sched.runnable)


def test_bsp_compact_wheel_queue(model, iinj):
    net = network.make_network(N, k_in=K, seed=3)
    r_d = exec_bsp.run_bsp_vardt(model, net, iinj, T_END, queue="wheel")
    r_c = exec_bsp.run_bsp_vardt(model, net, iinj, T_END, queue="wheel",
                                 batch="compact", batch_cap=7)
    _exact_same(r_d, r_c)


def test_unknown_batch_mode_rejected(model, iinj):
    net = network.make_network(N, k_in=K, seed=3)
    with pytest.raises(ValueError, match="batch"):
        exec_fap.make_fap_vardt_runner(model, net, iinj, T_END, batch="x")
    with pytest.raises(ValueError, match="batch"):
        exec_bsp.make_bsp_vardt_runner(model, net, iinj, T_END, batch="x")
    with pytest.raises(ValueError, match="fanout"):
        exec_fap.make_fap_vardt_runner(model, net, iinj, T_END, fanout="x")
    with pytest.raises(ValueError, match="fanout"):
        exec_bsp.make_bsp_vardt_runner(model, net, iinj, T_END, fanout="x")


# ---------------------------------------------------------------------------
# compact fan-out (ISSUE 5): bursty-regime identity incl. the
# spike_cap-overflow fallback path — overflow falls back, never drops
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("queue", ["dense", "wheel"])
def test_fanout_compact_burst_identity(model, queue):
    """Forced synchronized burst: every neuron driven well above
    threshold fires nearly every round.  Compact fan-out must equal the
    dense fan-out event for event both when the spiking set fits
    spike_cap (compact branch) and when it overflows every round
    (spike_cap=1 -> dense fallback branch); nothing may ever drop."""
    net = network.make_network(N, k_in=K, seed=3)
    iinj_burst = np.full(N, 0.22)              # strong DC: all neurons burst
    kw = dict(queue=queue, ev_cap=128,
              wheel=exec_fap.sched.WheelSpec.auto(net))
    r_d, rounds_d = exec_fap.make_fap_vardt_runner(
        model, net, iinj_burst, T_END, **kw)()
    assert int(r_d.rec.count.sum()) >= N       # genuinely bursty
    for cap in (N, 1):                         # compact branch / fallback
        r_c, rounds_c = exec_fap.make_fap_vardt_runner(
            model, net, iinj_burst, T_END, fanout="compact", spike_cap=cap,
            **kw)()
        _exact_same(r_d, r_c)
        assert int(rounds_d) == int(rounds_c)


def test_fanout_compact_bsp_and_speculative(model, iinj):
    """The fan-out knob is wired through BSP vardt and the speculative
    runner too."""
    from repro.core import exec_speculative
    net = network.make_network(N, k_in=K, seed=3)
    r_d = exec_bsp.run_bsp_vardt(model, net, iinj, T_END)
    r_c = exec_bsp.run_bsp_vardt(model, net, iinj, T_END, fanout="compact",
                                 spike_cap=3)
    _exact_same(r_d, r_c)
    s_d, _, _ = exec_speculative.make_spec_runner(model, net, iinj, T_END)()
    s_c, _, _ = exec_speculative.make_spec_runner(
        model, net, iinj, T_END, fanout="compact", spike_cap=3)()
    _exact_same(s_d, s_c)


def test_batch_cap_auto_picks_from_telemetry(model, iinj):
    """batch_cap="auto" probes the frontier and picks a power-of-two cap
    in [floor, N]; the run stays event-for-event identical to dense."""
    net = network.make_network(N, k_in=K, seed=3)
    r_d, _ = exec_fap.make_fap_vardt_runner(model, net, iinj, T_END)()
    run = exec_fap.make_fap_vardt_runner(model, net, iinj, T_END,
                                         batch="compact", batch_cap="auto")
    assert isinstance(run.batch_cap, int) and 1 <= run.batch_cap <= N
    r_a, _ = run()
    _exact_same(r_d, r_a)
    # the picker itself: mean frontier * slack, pow2, clipped
    s = xc.SchedStats.zeros()._replace(runnable=jnp.asarray(1000, jnp.int64),
                                       rounds=jnp.asarray(10, jnp.int32))
    assert xc.auto_batch_cap(s, 1 << 16) == 256      # 2*100 -> 256
    assert xc.auto_batch_cap(s, 64) == 64            # clipped at n
    assert xc.auto_batch_cap(xc.SchedStats.zeros(), 1 << 16) == 32


# ---------------------------------------------------------------------------
# the compact round's jaxpr stays sort-free with the sort-free knob stack
# ---------------------------------------------------------------------------
def test_compact_round_jaxpr_sort_free(model, iinj):
    from repro import sched
    net = network.make_network(N, k_in=K, seed=3)
    run = exec_fap.make_fap_vardt_runner(
        model, net, iinj, T_END, batch="compact", batch_cap=8,
        queue="wheel", horizon_impl="fused", select="threshold")
    carry = run.init_carry()
    prims = sched.jaxpr_primitives(run.round_body, carry)
    assert "sort" not in prims
