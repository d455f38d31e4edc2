"""The four-chip lab cell (``bench/spmd.py``) and the SPMD runner under it
(``fap_spmd.make_fap_spmd_runner``), at a tiny size on four host devices
(a subprocess: the device count locks when JAX starts):

- the cell's device loop of K rounds over ``run.round_body`` and
  ``run_fap_spmd`` on the same knobs give the same spike trains and
  counters;
- ``SchedStats`` and the parcel bytes, round by round, equal what the
  carry before each round gives on the host: runnable and stepped summed
  over the shards, lanes = 4 x ``batch_cap`` a round, one ragged class's
  payload a round;
- the compiled round names every stage, the channels among them, and
  ``bench/stages.program_text`` lowers the module that ran;
- the cell is correct against the plain reference, whose blocks of
  neurons give ``reference.simulate``'s trains; one shard's parcels
  dropped, or one shard's clocks held back, is not correct;
- the warm-up runs the onset's first sweep, after which every call moves
  GVT, and on to ``window_from_ms``; the check runs on to
  ``check_to_ms`` before it compares.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from bench.tests.cells import ROOT

SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, time
T0 = time.time()
import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_enable_x64", True)

from bench import checks, reference, stages
from bench import run as R
from bench import spmd
from bench.lab import program_network
from bench.tests.cells import BIG_SEED
from repro import sched
from repro.checkpoint import FaultPlan
from repro.core import bdf
from repro.core import exec_common as xc
from repro.distributed.exchange import ExchangeSpec
from repro.distributed.fap_spmd import run_fap_spmd

spec = R.load_json(os.path.join(sys.argv[1], "BENCHMARK.json"))
_, cfg, traffic = R.find_cell(spec, "spmd4.mix", sys.argv[1])
N, CAP, K = 256, 16, 80
# the window from the first call, so that two calls make the 2K rounds
# that run_fap_spmd runs below; the check compares at the window's end
cfg.update(n_neurons=N, rounds_per_call=K, window_from_ms=0.0,
           check_to_ms=0.0)
cfg["runner"] = dict(cfg["runner"], batch_cap=CAP, spike_cap=64,
                     exchange={"parcel_cap": CAP})
out = {}


def trains(count, times):
    count, times = np.asarray(count), np.asarray(times)
    return [[float(t) for t in times[i, :count[i]]] for i in range(N)]


# -- the cell ---------------------------------------------------------------
cell = spmd.Cell(cfg, traffic, BIG_SEED)
info = cell.setup()
win = cell.window(0.0)
sc = cell.carry
out["info"] = info
out["counts"] = win["counts"]
out["cell_trains"] = trains(sc.rec.count, sc.rec.times)
stats = jax.device_get(sc.counters["stats"])
out["cell_stats"] = {k: int(getattr(stats, k)) for k in stats._fields}
out["cell_parcel_bytes"] = int(sc.counters["parcel_bytes"])
# the module lowered from the arrays of the call that ran, and from shapes
real = jax.tree_util.tree_leaves(sc)
out["same_module"] = (
    cell.kcall.jitted.lower(cell.kcall.consts, *real).as_text()
    == cell.kcall.jitted.lower(*cell.kcall.lower_args).as_text())
text = stages.program_text(cell)
scopes = stages.op_scopes(text)
out["stages_named"] = sorted({c for op in scopes.values()
                              for c in op.split("/")}
                             & set(xc.ROUND_STAGES + xc.CHANNEL_STAGES))
out["live_cell"] = stages.live_cell({"config": cfg}) is cell

# -- the reference in blocks is the reference ---------------------------------
t_ref = 12.0
drive = checks.padded(np.asarray(sc.rec.count), np.asarray(sc.rec.times))
whole = reference.simulate(cell.net, cell.iinj, t_ref, drive=drive)
blocks = spmd.reference_by_blocks(cell.net, cell.iinj, t_ref, drive, 20.0,
                                  jax.devices()[:4])
out["blocks_equal"] = bool(np.array_equal(whole[0], blocks[0])
                           and np.array_equal(whole[1], blocks[1])
                           and whole[2] == blocks[2])
out["blocks_spikes"] = int(blocks[0].sum())

cmp = cell.check()
out["check"] = {"ok": checks.verdict(cmp)[0], "paired": cmp["paired"],
                "exact_faults": cmp["exact_faults"],
                "gap_p50_ms": cmp["gap_p50_ms"]}

# -- run_fap_spmd on the same knobs, round by round --------------------------
seen = []
kw = dict(cfg["runner"])
kw["wheel"] = sched.WheelSpec(**kw["wheel"])
kw["exchange"] = ExchangeSpec(**kw["exchange"])
res, rounds = run_fap_spmd(
    spmd.cell_model(cfg), program_network(cell.net), cell.iinj,
    cfg["t_end_ms"], cell.mesh, opts=bdf.BDFOptions(**cfg["bdf"]),
    max_rounds=2 * K, watchdog=False, fault=FaultPlan(mutate=lambda r, c: seen.append(c)
                                      or c), **kw)
out["rfs_rounds"] = rounds
out["rfs_trains"] = trains(res.rec.count, res.rec.times)
out["rfs_stats"] = {k: int(getattr(res.sched, k)) for k in res.sched._fields}
out["rfs_parcel_bytes"] = res.comm["parcel_bytes"]
out["max_rounds_reached"] = res.health["max_rounds_reached"]

g = cell.net
dnet = xc.DeviceNet(jnp.asarray(g["pre"]), jnp.asarray(g["post"]),
                    jnp.asarray(g["delay"]), jnp.asarray(g["w_ampa"]),
                    jnp.asarray(g["w_gaba"]))
ladder = ExchangeSpec(parcel_cap=CAP).class_ladder()
want, got = [], []
for a, b in zip(seen, seen[1:]):
    t0 = jnp.asarray(np.asarray(a.sts.t))
    hor = xc.horizon_times(dnet, N, t0, cfg["t_end_ms"], horizon_cap=2.0)
    per_shard = np.asarray(xc.runnable_mask(t0, hor)).reshape(4, -1).sum(1)
    sa, sb = (jax.device_get(x.counters["stats"]) for x in (a, b))
    spiked = int(np.asarray(b.rec.count).sum() - np.asarray(a.rec.count).sum())
    pb = int(b.counters["parcel_bytes"]) - int(a.counters["parcel_bytes"])
    got.append([int(sb.runnable - sa.runnable), int(sb.stepped - sa.stepped),
                int(sb.lanes - sa.lanes), int(sb.rounds - sa.rounds), pb,
                spiked])
    want.append([int(per_shard.sum()), int(np.minimum(per_shard, CAP).sum()),
                 4 * CAP, 1])
out["per_round_got"], out["per_round_want"] = got, want
out["ladder_bytes"] = [4 * c * 12 for c in ladder]

# -- the warm-up takes every neuron off t = 0, then runs on to its GVT; ------
# -- the check runs on to its own ----------------------------------------------
# one round a call: the 64 neurons of a shard leave t = 0 over 4 rounds; the
# first spikes fall at 2.3-2.6 ms
first = None
for t_from, t_to in ((0.0, 0.0), (0.5, 4.0)):
    warm = spmd.Cell(dict(cfg, rounds_per_call=1, window_from_ms=t_from,
                          check_to_ms=t_to), traffic, 5)
    w_info = warm.setup(like=first)
    w_win = warm.window(0.0)
    w = out[f"warmup_{t_from}"] = {"gvt": w_info["warmup_gvt_ms"],
                                   "rounds": w_info["warmup_rounds"],
                                   "failed_calls": w_win["failed_calls"],
                                   "gvt_after_window": w_win["gvt_end_ms"]}
    if t_to:
        cmp = warm.check()
        w["check"] = {"ok": checks.verdict(cmp)[0], "paired": cmp["paired"],
                      "t_cut_ms": cmp["t_cut_ms"],
                      "exact_faults": cmp["exact_faults"]}
    first = first or warm
del warm, first

# -- planted faults ------------------------------------------------------------
def parcels_dropped(run, fn):
    # shard 0's rows of the destination map cleared: its spikes go into no
    # parcel, so no event of theirs reaches any shard
    st = list(run.static)
    i = 8             # after the edges, the drive, b_rel and b_gid
    st[i] = jax.device_put(st[i].at[:run.n_local].set(False), st[i].sharding)
    return spmd.k_round_fn(lambda c: run.round_with(c, tuple(st)), run.cond,
                           K)


def clocks_held_back(run, fn):
    # shard 0's neurons put back as they were after every round
    keep = jnp.arange(N) < run.n_local

    def body(c):
        new = run.round_body(c)
        sts = jax.tree_util.tree_map(
            lambda x, y: jnp.where(keep.reshape((-1,) + (1,) * (x.ndim - 1)),
                                   y, x), new.sts, c.sts)
        return new._replace(sts=sts)
    return spmd.k_round_fn(body, run.cond, K)


for name, plant, like in (("parcels_dropped", parcels_dropped, cell),
                          ("clocks_held_back", clocks_held_back, None)):
    bad = spmd.Cell(cfg, traffic, 21, round_fn=plant)
    bad.setup(like=like)
    bad.window(0.0)
    cmp = bad.check()
    out[name] = {"ok": checks.verdict(cmp)[0], "faults": cmp["faults"],
                 "t_cut_ms": cmp["t_cut_ms"],
                 "unpaired_share": cmp["unpaired_share"]}
    del bad
out["seconds"] = time.time() - T0
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def spmd_cell():
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT]))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", SCRIPT, ROOT], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=ROOT)
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_device_loop_matches_run_fap_spmd(spmd_cell):
    """Two calls of the cell's K-round loop and ``run_fap_spmd`` cut at
    the same 2K rounds: the same trains, counters and parcel bytes."""
    out = spmd_cell
    assert out["rfs_rounds"] == 160 and out["max_rounds_reached"]
    assert out["cell_stats"]["rounds"] == 160
    assert out["cell_stats"] == out["rfs_stats"]
    assert out["cell_parcel_bytes"] == out["rfs_parcel_bytes"] > 0
    a, b = out["cell_trains"], out["rfs_trains"]
    assert sum(map(len, a)) > 0
    assert [len(t) for t in a] == [len(t) for t in b]
    assert a == b                     # one program's numbers, bit for bit


def test_counters_summed_over_shards(spmd_cell):
    """Round by round: runnable and stepped are the four shards' sums,
    lanes 4 x batch_cap, one round, and one ragged class's payload (the
    smallest in a round without spikes)."""
    out = spmd_cell
    got, want = out["per_round_got"], out["per_round_want"]
    assert len(got) == 159
    assert [g[:4] for g in got] == want
    assert all(g[1] <= g[2] for g in got)
    ladder = out["ladder_bytes"]
    assert all(g[4] in ladder for g in got)
    assert all(g[4] == ladder[0] for g in got if g[5] == 0)
    assert any(g[5] > 0 for g in got)
    c = out["counts"]
    assert c["lanes"] == 4 * 16 * c["rounds"] and c["stepped"] <= c["lanes"]


def test_round_names_every_stage_and_program_text_is_what_ran(spmd_cell):
    from repro.core import exec_common as xc
    out = spmd_cell
    assert out["stages_named"] == sorted(xc.ROUND_STAGES + xc.CHANNEL_STAGES)
    assert out["live_cell"] and out["same_module"]


def test_cell_is_correct_and_reference_blocks_are_the_reference(spmd_cell):
    out = spmd_cell
    info = out["info"]
    assert info["n_local"] == 64 and info["batch_cap"] == 16
    assert not info["compile"]["cache_hits"]
    assert out["check"]["ok"] and out["check"]["exact_faults"] == 0
    assert out["check"]["paired"] > 0
    assert out["blocks_equal"] and out["blocks_spikes"] > 0


def test_warmup_runs_the_first_sweep(spmd_cell):
    """GVT stays 0 until every neuron has left t = 0 (n_local / batch_cap
    rounds): the warm-up runs those calls, then on to ``window_from_ms``,
    so the window's calls all move GVT.  The check runs on from the
    window's end to ``check_to_ms``, past the first spikes, and compares
    them."""
    w = spmd_cell["warmup_0.0"]
    assert w["rounds"] == 64 // 16 and w["gvt"] > 0
    assert w["failed_calls"] == 0
    w = spmd_cell["warmup_0.5"]
    assert w["rounds"] > 64 // 16 and w["gvt"] >= 0.5
    assert w["failed_calls"] == 0
    c = w["check"]
    assert w["gvt_after_window"] < 4.0 <= c["t_cut_ms"]
    assert c["ok"] and c["exact_faults"] == 0 and c["paired"] > 0


@pytest.mark.parametrize("fault", ["parcels_dropped", "clocks_held_back"])
def test_spmd_fault_is_not_correct(spmd_cell, fault):
    assert spmd_cell[fault]["ok"] is False, spmd_cell[fault]
