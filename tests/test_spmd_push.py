"""The incremental horizon of the SPMD round (4-device host-platform mesh,
subprocess — jax device count locks at first init).

Each shard carries an in-edge clock table, its [K, n_local] by-k table
``t[pre]`` of its in-edges stored row-major; every round pushes the moved
clocks into it (remote frontier clocks that changed, the shard's own
lanes that advanced last round) and the horizon is one dense fold over
it.  After every round of a uniform net and of a placed block net (where
some neurons are off the notify frontier, so only the own-lane push
reaches their slots) the carried table equals ``t[pre]`` of the clocks the
round started from and the horizon it implies equals the full
scatter-min bitwise; a moved set over ``move_cap`` rebuilds the table
whole, in the round after a reseed and mid-run; ``SchedStats.horizon_live``
counts the batch's lanes plus the slots written.  A checkpoint of the
earlier carry, the horizon [N] itself, is reseeded on resume.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")

SCRIPT = r"""
import os, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
import numpy as np

jax.config.update("jax_enable_x64", True)

from repro.checkpoint import FaultPlan, SimulatedFailure
from repro.checkpoint import checkpoint as ck
from repro.core import exec_common as xc
from repro.core import morphology, network
from repro.core.cell import CellModel
from repro.core.topology import TopologyConfig
from repro.distributed import placement as plc
from repro.distributed.exchange import ExchangeSpec
from repro.distributed.fap_spmd import make_fap_spmd_runner
from repro.distributed.sharding import shard_frontier
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 2), ("data", "model"))
model = CellModel(morphology.soma_only())
S, N, K, CAP, T, HCAP, ROUNDS = 4, 64, 4, 4, 6.0, 2.0, 40
N_LOC = N // S
rng = np.random.default_rng(1)
iinj = 0.2 + 0.01 * rng.standard_normal(N)
KW = dict(transport="sparse_ragged", exchange=ExchangeSpec(parcel_cap=8),
          batch="compact", batch_cap=CAP, fanout="compact", spike_cap=8,
          horizon="incremental", horizon_cap=HCAP)
uni = network.make_network(N, k_in=K, seed=3)
blk = network.make_network(N, k_in=K, seed=3,
                           topology=TopologyConfig("block", n_blocks=4,
                                                   p_in=0.95))
blk, iinj_blk = plc.place_inputs(blk, iinj,
                                 plc.compute_placement(blk, S, "greedy"))
NETS = {"uniform": (uni, iinj), "block": (blk, iinj_blk)}
out = {}


def slots_written(net, b_gid, t0, prev_bnd, moved_prev):
    # the in-edge slots the round's push writes: an edge whose pre is in
    # its own shard when that lane advanced last round, else when its
    # frontier clock changed
    pre = np.asarray(net.pre)
    own_edge = pre // N_LOC == np.arange(N * K) // (N_LOC * K)
    own_moved = np.zeros(N, bool)
    for s in range(S):
        ids = moved_prev[s][moved_prev[s] < N_LOC]
        own_moved[s * N_LOC + ids] = True
    changed = np.zeros(N, bool)
    real = b_gid < N
    changed[b_gid[real]] = t0[b_gid[real]] != prev_bnd[real]
    return int(np.where(own_edge, own_moved[pre], changed[pre]).sum())


def drive(net, drv, move_cap, reseed_at):
    run = make_fap_spmd_runner(model, net, drv, T, mesh, move_cap=move_cap,
                               **KW)
    b_gid = shard_frontier(net.pre, net.post, N, S).boundary_gid.reshape(-1)
    byk = np.asarray(net.pre).reshape(S, N_LOC, K).transpose(0, 2, 1)
    byk = byk.reshape(-1)
    dnet = xc.to_device(net)
    step = jax.jit(run.round_body)
    c = run.init_carry()
    r = {"table_ok": [], "horizon_ok": [], "live_ok": [], "fallback": []}
    for i in range(ROUNDS):
        if i == reseed_at:
            c = run.reseed(c)
        t0 = np.asarray(c.sts.t)
        prev_bnd = np.asarray(c.hcarry[1])
        moved_prev = np.asarray(c.hcarry[2]).reshape(S, CAP)
        c2 = step(c)
        r["table_ok"].append(bool(np.array_equal(np.asarray(c2.hcarry[0]),
                                                 t0[byk])))
        # the horizon the table implies at the clocks the round used
        h = run.horizon_of(c2._replace(sts=c.sts))
        want = xc.horizon_times(dnet, N, c.sts.t, T, horizon_cap=HCAP)
        r["horizon_ok"].append(bool(np.array_equal(np.asarray(h),
                                                   np.asarray(want))))
        mcnt = int(((b_gid < N)
                    & (t0[np.minimum(b_gid, N - 1)] != prev_bnd)).sum())
        fallback = mcnt > run.move_cap
        slots = N * K if fallback else slots_written(net, b_gid, t0,
                                                     prev_bnd, moved_prev)
        s0, s1 = c.counters["stats"], c2.counters["stats"]
        live = int(s1.horizon_live - s0.horizon_live)
        r["live_ok"].append(live == int(s1.stepped - s0.stepped) + slots)
        r["fallback"].append(bool(fallback))
        c = c2
    t_end = np.asarray(c.sts.t)
    off = np.setdiff1d(np.arange(N), b_gid[b_gid < N])
    r.update(move_cap=run.move_cap, frontier=int((b_gid < N).sum()),
             spikes=int(np.asarray(c.rec.count).sum()),
             horizon_live=int(c.counters["stats"].horizon_live),
             off_frontier=int(off.size),
             off_frontier_moved=int((t_end[off] > 0).sum()))
    return r


for tag, (net, drv) in NETS.items():
    out[tag] = {"seeded": drive(net, drv, 0, 20),
                "small_cap": drive(net, drv, 2, None)}

# resume: a checkpoint of the earlier carry (the horizon [N] itself, under
# the earlier layout fingerprint) is reseeded from its clocks
for k_in in (4, 1):
    n = 32
    net = network.make_network(n, k_in=k_in, seed=3)
    drv = iinj[:n]
    run = make_fap_spmd_runner(model, net, drv, 4.0, mesh, max_rounds=400,
                               **KW)
    base, r0 = run(watchdog=True)
    new_dir, old_dir = tempfile.mkdtemp(), tempfile.mkdtemp()
    try:
        run(checkpoint_every=4, ckpt_dir=new_dir,
            fault=FaultPlan(fail_at_round=r0 // 2))
        raise RuntimeError("SimulatedFailure did not fire")
    except SimulatedFailure:
        pass
    rnd = ck.latest_step(new_dir)
    sc, extras, skipped = xc.restore_sim_checkpoint(new_dir, rnd,
                                                    run.init_carry())
    assert skipped == []
    hor = xc.horizon_times(xc.to_device(net), n, sc.sts.t, 4.0,
                           horizon_cap=HCAP)
    fp = {k: v for k, v in extras["fingerprint"].items() if k != "hcarry"}
    xc.save_sim_checkpoint(old_dir, rnd,
                           sc._replace(hcarry=(hor,) + sc.hcarry[1:]),
                           extras={"fingerprint": fp})
    res, r1 = run(checkpoint_every=4, ckpt_dir=old_dir, resume=True)
    out[f"resume_k{k_in}"] = {
        "rounds": [int(r0), int(r1)], "resumed_from": res.health[
            "resumed_from"], "round": int(rnd),
        "reseeded": res.health["elastic_reseeded"],
        "spikes": int(np.asarray(base.rec.count).sum()),
        "same_shape": int(sc.hcarry[0].shape[0]) == n,
        "identical": bool(
            np.array_equal(np.asarray(res.rec.count),
                           np.asarray(base.rec.count))
            and np.array_equal(np.asarray(res.rec.times),
                               np.asarray(base.rec.times))),
        "dropped": int(res.dropped), "failed": bool(res.failed)}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def push_out():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=ROOT)
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


pytestmark = pytest.mark.slow

NETS = ["uniform", "block"]


@pytest.mark.parametrize("net", NETS)
@pytest.mark.parametrize("run", ["seeded", "small_cap"])
def test_pushed_table_and_horizon_bitwise(push_out, net, run):
    """After every round each shard's table is ``t[pre]`` of its in-edges
    in the by-k layout and the horizon it implies is the full
    scatter-min, bitwise; the rounds fire spikes."""
    r = push_out[net][run]
    assert len(r["table_ok"]) == 40 and all(r["table_ok"]), r["table_ok"]
    assert all(r["horizon_ok"]), r["horizon_ok"]
    assert r["spikes"] > 0


@pytest.mark.parametrize("net", NETS)
def test_overflowing_moved_set_rebuilds_the_table(push_out, net):
    """The round after a reseed (all-zero previous boundary clocks) sees
    every frontier clock as moved: on the uniform net more than
    ``move_cap`` (16), so it rebuilds the table; the placed block net's
    frontier fits and is pushed whole.  A ``move_cap`` of 2 falls back
    mid-run, on the block net between rounds that push."""
    seeded = push_out[net]["seeded"]
    fb = seeded["fallback"]
    assert seeded["move_cap"] == 16
    assert fb[20] == (net == "uniform")
    assert fb[20] == (seeded["frontier"] > seeded["move_cap"])
    assert not any(fb[:20] + fb[21:]), fb
    small = push_out[net]["small_cap"]
    assert small["move_cap"] == 2
    fb = small["fallback"]
    assert any(fb[1:]), fb
    assert all(fb[1:]) == (net == "uniform"), fb


@pytest.mark.parametrize("net", NETS)
def test_horizon_live_counts_lanes_and_slots(push_out, net):
    """``SchedStats.horizon_live`` grows each round by the batch's lanes
    plus the table slots the push wrote (all of them in a rebuild)."""
    for run in ("seeded", "small_cap"):
        r = push_out[net][run]
        assert r["horizon_live"] > 0
        assert all(r["live_ok"]), (run, r["live_ok"])


def test_off_frontier_neurons_take_the_own_lane_push(push_out):
    """The placed block net has neurons on no notify frontier; they
    advance, and only the push of the shard's own lanes reaches their
    slots, which the table checks above cover."""
    r = push_out["block"]["seeded"]
    assert r["off_frontier"] > 0
    assert r["off_frontier_moved"] == r["off_frontier"]
    assert push_out["uniform"]["seeded"]["off_frontier"] < r["off_frontier"]


@pytest.mark.parametrize("k_in", [4, 1])
def test_resume_reseeds_a_horizon_snapshot(push_out, k_in):
    """A checkpoint whose horizon carry holds the horizon [N] itself (the
    earlier layout and fingerprint) is reseeded from its clocks on resume
    and ends with the uninterrupted run's spike trains.  At ``k_in`` 1 the
    two carries have one shape: the fingerprint tells them apart."""
    r = push_out[f"resume_k{k_in}"]
    assert r["spikes"] > 0
    assert r["same_shape"] == (k_in == 1)
    assert r["resumed_from"] == r["round"] > 0
    assert r["reseeded"]
    assert r["rounds"][0] == r["rounds"][1]
    assert r["identical"], r
    assert r["dropped"] == 0 and not r["failed"]
