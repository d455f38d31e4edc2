"""Preemption tolerance on the SPMD driver (4-device host-platform mesh,
subprocess — jax device count locks at first init).

Acceptance (ISSUE 9): a ``run_fap_spmd`` run checkpointed every k rounds,
killed mid-run via ``SimulatedFailure`` and resumed produces a spike
train bit-identical to the uninterrupted run, across >= 2 topologies x
both queue implementations; an injected non-finite lane is detected by
the watchdog, rolled back and reported on ``RunResult.health`` — never
silently propagated; elastic resume onto a different mesh shape reseeds
the shard-shaped horizon carry and stays bit-identical; parcel-cap drops
escalate onto health.
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
from repro.core import morphology, network
from repro.core.cell import CellModel
from repro.core.topology import TopologyConfig
from repro.distributed.exchange import ExchangeSpec
from repro.distributed.fap_spmd import run_fap_spmd
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 2), ("data", "model"))
model = CellModel(morphology.soma_only())
n = 16
net_u = network.make_network(n, k_in=4, seed=3)
net_b = network.make_network(n, k_in=4, seed=3,
                             topology=TopologyConfig("block", n_blocks=4,
                                                     p_in=0.95))
rng = np.random.default_rng(1)
iinj = 0.16 + 0.004 * rng.standard_normal(n)
T = 6.0
out = {}


def ident(a, b):
    return (bool(np.array_equal(np.asarray(a.rec.times),
                                np.asarray(b.rec.times)))
            and bool(np.array_equal(np.asarray(a.rec.count),
                                    np.asarray(b.rec.count))))


def kill_resume(tag, netx, mesh2=None, **kw):
    # baseline -> kill at ~60% of the rounds -> resume (optionally on a
    # different mesh); report identity + health
    base, r0 = run_fap_spmd(model, netx, iinj, T, mesh, max_rounds=80, **kw)
    d = tempfile.mkdtemp()
    kill = max(2, int(r0 * 0.6))
    every = 5
    try:
        run_fap_spmd(model, netx, iinj, T, mesh, max_rounds=80,
                     checkpoint_every=every, ckpt_dir=d,
                     fault=FaultPlan(fail_at_round=kill), **kw)
        raise RuntimeError("SimulatedFailure did not fire")
    except SimulatedFailure:
        pass
    res, r1 = run_fap_spmd(model, netx, iinj, T, mesh2 or mesh,
                           max_rounds=80, ckpt_dir=d, resume=True, **kw)
    out[tag] = {
        "identical": ident(base, res), "rounds": [r0, r1],
        "spikes": int(np.asarray(base.rec.count).sum()),
        "dropped": int(res.dropped), "failed": bool(res.failed),
        "resumed_from": res.health["resumed_from"],
        "elastic_reseeded": res.health["elastic_reseeded"],
        "checks": res.health["checks"],
    }
    return base


sp = dict(optimized=True, transport="sparse",
          exchange=ExchangeSpec(parcel_cap=8))
spw = dict(optimized=True, transport="sparse", queue="wheel",
           exchange=ExchangeSpec(parcel_cap=8))
base_ud = kill_resume("uniform/dense", net_u, **sp)
kill_resume("uniform/wheel", net_u, **spw)
kill_resume("block/dense", net_b, **sp)
kill_resume("block/wheel", net_b, **spw)

# elastic resume: kill on the (2,2) mesh, resume on (4,1) — the
# incremental-horizon carry is shard-relative and must be reseeded
mesh41 = make_mesh((4, 1), ("data", "model"))
kill_resume("elastic", net_u, mesh2=mesh41, batch="compact", batch_cap=8,
            horizon="incremental", **sp)

# watchdog: poison one lane's BDF history mid-run -> detected the same
# round, rolled back to the last checkpoint, completed identically
d = tempfile.mkdtemp()
res_p, _ = run_fap_spmd(model, net_u, iinj, T, mesh, max_rounds=80,
                        checkpoint_every=5, ckpt_dir=d,
                        fault=FaultPlan(poison_at_round=12, poison_lane=5),
                        **sp)
out["poison"] = {
    "identical": ident(base_ud, res_p), "failed": bool(res_p.failed),
    "nonfinite_rounds": res_p.health["nonfinite_rounds"],
    "rollbacks": res_p.health["rollbacks"],
    "rollback_exhausted": res_p.health["rollback_exhausted"],
}

# injected parcel-cap overflow: hot network + cap=1 -> the drop counter
# fires AND is escalated onto RunResult.health (detected, never silent)
iinj_hot = 0.20 + 0.004 * rng.standard_normal(n)
res_of, _ = run_fap_spmd(model, net_u, iinj_hot, T, mesh, max_rounds=80,
                         transport="sparse",
                         exchange=ExchangeSpec(parcel_cap=1))
out["overflow"] = {"dropped": int(res_of.dropped),
                   "health_dropped": res_of.health["dropped_events"]}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def rob_out():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=ROOT)
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


pytestmark = pytest.mark.slow

MATRIX = ["uniform/dense", "uniform/wheel", "block/dense", "block/wheel"]


@pytest.mark.parametrize("tag", MATRIX)
def test_kill_resume_bit_identical(rob_out, tag):
    """Acceptance: kill/resume spike-train identity across 2 topologies x
    both queue implementations."""
    r = rob_out[tag]
    assert r["spikes"] > 0
    assert r["identical"], r
    assert r["rounds"][0] == r["rounds"][1]
    assert r["dropped"] == 0 and not r["failed"]
    assert r["resumed_from"] is not None and r["resumed_from"] > 0


@pytest.mark.parametrize("tag", MATRIX)
def test_watchdog_ran_every_round(rob_out, tag):
    """The resumed leg's watchdog checked every round it drove."""
    r = rob_out[tag]
    assert r["checks"] == r["rounds"][1] - r["resumed_from"]


def test_elastic_mesh_resume(rob_out):
    """Resume onto a different mesh shape reseeds the shard-relative
    horizon carry (fingerprint mismatch) and stays bit-identical."""
    r = rob_out["elastic"]
    assert r["identical"], r
    assert r["elastic_reseeded"]
    assert r["dropped"] == 0 and not r["failed"]


def test_poison_detected_rolled_back_never_silent(rob_out):
    """Acceptance: the injected non-finite lane is detected by the health
    watchdog, rolled back, reported on RunResult.health, and the
    completed run is bit-identical — never silently propagated."""
    p = rob_out["poison"]
    assert p["nonfinite_rounds"] >= 1
    assert p["rollbacks"] >= 1
    assert not p["rollback_exhausted"] and not p["failed"]
    assert p["identical"], p


def test_parcel_drops_escalate_to_health(rob_out):
    """Queue/parcel overflow rides RunResult.health, not only the raw
    dropped counter."""
    o = rob_out["overflow"]
    assert o["dropped"] > 0
    assert o["health_dropped"] == o["dropped"]
