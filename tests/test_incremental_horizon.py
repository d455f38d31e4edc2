"""The incremental horizon of the compact FAP round: the carry's in-edge
clock table ``tin`` (the [K, N] table ``t[pre_byk]``, row-major) takes each
round's moved clocks by a push into their out-edge slots, and the horizon
derived from it is bitwise the full scatter-min of ``xc.horizon_times``,
after every host-stepped round of the lab stack (compact batch, wheel
queue, compact fan-out).  A snapshot of the earlier carry, which held the
horizon itself, is reseeded from its clocks and resumes identically."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sched
from repro.checkpoint import FaultPlan, SimulatedFailure
from repro.core import exec_common as xc
from repro.core import exec_fap, morphology, network
from repro.core.cell import CellModel
from repro.kernels.event_wheel import ops as ew_ops

N, CAP, ROUNDS, HCAP = 512, 64, 60, 2.0


@pytest.fixture(scope="module")
def model():
    return CellModel(morphology.soma_only())


def _hub_net(n: int, width: int, seed: int):
    """``make_network`` with neuron 0 rewired to ``width`` out-edges, above
    every other neuron's out-degree: the out-edge table is padded to that
    width, so every other row ends in padding."""
    net = network.make_network(n, k_in=16, seed=seed)
    pre = np.asarray(net.pre).copy()
    post = np.asarray(net.post)
    have = np.flatnonzero(pre == 0)
    free = np.flatnonzero((pre != 0) & (post != 0))[:width - have.size]
    pre[free] = 0
    return net._replace(pre=pre)


NETS = {
    "natural": lambda: network.make_network(N, k_in=16, seed=3),
    "hub": lambda: _hub_net(N, 48, seed=5),
}


def _lab_runner(model, net, iinj, t_end=40.0):
    return exec_fap.make_fap_vardt_runner(
        model, net, iinj, t_end, batch="compact", batch_cap=CAP,
        fanout="compact", queue="wheel", wheel=sched.WheelSpec.auto(net),
        horizon_cap=HCAP)


def _drive(n: int):
    return np.linspace(0.12, 0.6, n)


def _assert_table_and_horizon(run, net, carry, t_end):
    pre_byk, _ = ew_ops.by_post_layout(net)
    t = carry[0].t
    np.testing.assert_array_equal(np.asarray(carry[3]),
                                  np.asarray(t[pre_byk]).reshape(-1))
    want = xc.horizon_times(xc.to_device(net), net.n, t, t_end,
                            horizon_cap=HCAP)
    np.testing.assert_array_equal(np.asarray(run.horizon_of(carry)),
                                  np.asarray(want))


@pytest.mark.parametrize("case", ["natural", "hub", "tenant_lane"])
def test_pushed_table_and_horizon_bitwise(model, case):
    """After every one of 60 rounds the carried table equals ``t[pre_byk]``
    and the horizon it implies equals the full scatter-min, bitwise; the
    rounds move clocks and fire spikes.  ``tenant_lane`` steps one lane of
    the vmapped ``tenant_round`` beside an idle one."""
    net = NETS["hub" if case == "hub" else "natural"]()
    if case == "hub":
        deg = np.bincount(np.asarray(net.pre), minlength=N)
        width = xc.out_tables(net)[0].shape[1]
        assert deg.max() == width == 48 and np.sort(deg)[-2] < width
    iinj = _drive(N)
    t_end = 40.0
    run = _lab_runner(model, net, iinj, t_end)
    if case == "tenant_lane":
        lanes = [run.init_carry(iinj), run.init_carry(0.0)]
        carry2 = jax.tree_util.tree_map(lambda *x: jnp.stack(x), *lanes)
        step = jax.jit(jax.vmap(run.tenant_round, in_axes=(0, 0, 0, 0)))
        args = (jnp.stack([jnp.asarray(iinj), jnp.zeros(N)]),
                jnp.array([True, False]), jnp.zeros(2, jnp.int32))
        for _ in range(ROUNDS):
            carry2 = step(carry2, *args)
            lane = jax.tree_util.tree_map(lambda x: x[0], carry2)
            _assert_table_and_horizon(run, net, lane, t_end)
        idle = jax.tree_util.tree_map(lambda x: x[1], carry2)
        np.testing.assert_array_equal(np.asarray(idle[3]),
                                      np.asarray(run.init_carry(0.0)[3]))
        carry = lane
    else:
        carry = run.init_carry()
        _assert_table_and_horizon(run, net, carry, t_end)
        step = jax.jit(run.round_body)
        for _ in range(ROUNDS):
            carry = step(carry)
            _assert_table_and_horizon(run, net, carry, t_end)
    t0 = np.asarray(run.init_carry()[0].t)
    assert (np.asarray(carry[0].t) > t0).all()
    assert int(np.asarray(carry[2].count).sum()) > 0
    stats = run.pack(carry).counters["stats"]
    assert int(stats.rounds) == ROUNDS and int(stats.horizon_live) > 0


def test_in_edge_slots_map_the_out_edge_table():
    """Each real entry of the out-edge table lands on the slot of the
    ``by_post_layout`` table that holds its pre; padding stays E."""
    net = _hub_net(64, 24, seed=2)
    k = sched.grouped_k(net)
    pre_byk, _ = ew_ops.by_post_layout(net)
    _, edge_t = xc.out_tables(net)
    slots = xc.in_edge_slots(edge_t, net.n, k)
    E = net.n * k
    real = edge_t < E
    assert np.array_equal(slots < E, real)
    flat = np.asarray(pre_byk).reshape(-1)
    rows = np.broadcast_to(np.arange(net.n)[:, None], slots.shape)
    assert np.array_equal(flat[slots[real]], rows[real])
    assert np.unique(slots[real]).size == E    # every slot exactly once


@pytest.mark.parametrize("k_in", [4, 1])
def test_resume_reseeds_a_horizon_snapshot(model, tmp_path, k_in):
    """A checkpoint whose horizon carry is the horizon [N] itself (the
    earlier layout) is reseeded from its clocks on resume, and the run
    ends with the spike trains of the uninterrupted run.  At ``k_in`` 1
    the two carries have one shape: the layout fingerprint tells them
    apart."""
    n = 48
    net = network.make_network(n, k_in=k_in, seed=3)
    iinj = _drive(n)
    run = exec_fap.make_fap_vardt_runner(model, net, iinj, 10.0,
                                         batch="compact", batch_cap=8,
                                         horizon_cap=HCAP)
    base, rounds0 = run(watchdog=True)
    new_dir, old_dir = tmp_path / "new", tmp_path / "old"
    kill = int(rounds0) // 2
    with pytest.raises(SimulatedFailure):
        run(checkpoint_every=4, ckpt_dir=str(new_dir),
            fault=FaultPlan(fail_at_round=kill))
    from repro.checkpoint import checkpoint as ck
    rnd = ck.latest_step(str(new_dir))
    like = run.pack(run.init_carry())
    sc, _, skipped = xc.restore_sim_checkpoint(str(new_dir), rnd, like)
    assert skipped == []
    old = sc._replace(hcarry=(xc.horizon_times(
        xc.to_device(net), n, sc.sts.t, 10.0, horizon_cap=HCAP),))
    xc.save_sim_checkpoint(str(old_dir), rnd, old)
    res, rounds = run(checkpoint_every=4, ckpt_dir=str(old_dir),
                      resume=True)
    assert res.health["resumed_from"] == rnd
    assert res.health["elastic_reseeded"]
    assert int(rounds) == int(rounds0)
    np.testing.assert_array_equal(np.asarray(res.rec.count),
                                  np.asarray(base.rec.count))
    np.testing.assert_array_equal(np.asarray(res.rec.times),
                                  np.asarray(base.rec.times))
