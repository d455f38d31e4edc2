"""Optimized SPMD FAP round: transport-layer coverage on a 4-device
host-platform mesh (subprocess — jax device count locks at first init).

Acceptance (ISSUE 2): sparse and allgather transports produce event-for-event
identical spike trains when no parcel overflows; the sparse transport's
spike-parcel collective bytes are a function of the static parcel cap, NOT of
N (asserted at two values of N from the compiled HLO's per-channel
attribution); parcel-cap overflow fires the drop counter, never silent.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
import numpy as np

jax.config.update("jax_enable_x64", True)

from repro.core import morphology, network
from repro.core.cell import CellModel
from repro.distributed.exchange import ExchangeSpec
from repro.distributed.fap_spmd import (PaperNeuroSpec, build_fap_round,
                                        run_fap_spmd)
from repro.launch.hlo_analysis import collective_channel_bytes
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 2), ("data", "model"))
model = CellModel(morphology.soma_only())
n = 32
net = network.make_network(n, k_in=4, seed=3)
rng = np.random.default_rng(1)
iinj = 0.16 + 0.004 * rng.standard_normal(n)
out = {}


def trains(res):
    ts, c = np.asarray(res.rec.times), np.asarray(res.rec.count)
    return [sorted(float(t) for t in ts[i][: c[i]]) for i in range(len(c))]


runs = {
    "global": dict(optimized=False),
    "allgather": dict(optimized=True, transport="allgather"),
    "sparse": dict(optimized=True, transport="sparse",
                   exchange=ExchangeSpec(parcel_cap=8)),
    "sparse_wheel": dict(optimized=True, transport="sparse", queue="wheel",
                         exchange=ExchangeSpec(parcel_cap=8)),
    # active-set compaction (ISSUE 4): shard-local compact -> step ->
    # scatter composed with the sparse transport; full shard width
    # (batch_cap=0) must be event-for-event identical to the dense batch
    "sparse_compact": dict(optimized=True, transport="sparse",
                           exchange=ExchangeSpec(parcel_cap=8),
                           batch="compact"),
    # activity-proportional delivery (ISSUE 5): two-phase ragged parcels
    "sparse_ragged": dict(optimized=True, transport="sparse_ragged",
                          exchange=ExchangeSpec(parcel_cap=8)),
    # ... and the full stack: compact batch + compact fan-out +
    # incremental SPMD horizon + ragged parcels, all at once
    "full_stack": dict(optimized=True, transport="sparse_ragged",
                       exchange=ExchangeSpec(parcel_cap=8),
                       batch="compact", fanout="compact", spike_cap=8,
                       horizon="incremental"),
}
for name, kw in runs.items():
    res, rounds = run_fap_spmd(model, net, iinj, 6.0, mesh, max_rounds=60,
                               **kw)
    out[name] = {"trains": trains(res), "dropped": int(res.dropped),
                 "failed": bool(res.failed), "rounds": rounds,
                 "comm": res.comm}

# independent anchor: the single-host FAP runner (exec_fap) with matching
# knobs — catches driver-level bugs that would cancel out of the pairwise
# SPMD comparisons
from repro.core import exec_fap
res_ref = exec_fap.run_fap_vardt(model, net, iinj, 6.0, step_budget=8,
                                 ev_cap=32)
out["single_host"] = {"trains": trains(res_ref),
                      "dropped": int(res_ref.dropped)}

# forced parcel overflow: hot network + cap=1
iinj_hot = 0.20 + 0.004 * rng.standard_normal(n)
res_of, _ = run_fap_spmd(model, net, iinj_hot, 6.0, mesh, transport="sparse",
                         exchange=ExchangeSpec(parcel_cap=1), max_rounds=60)
out["overflow_dropped"] = int(res_of.dropped)

# ragged overflow: the largest class == the static cap, so a hot network
# over cap must fire the same drop counter through the classed exchange
res_rof, _ = run_fap_spmd(model, net, iinj_hot, 6.0, mesh,
                          transport="sparse_ragged",
                          exchange=ExchangeSpec(parcel_cap=2), max_rounds=60)
out["ragged_overflow_dropped"] = int(res_rof.dropped)

# locality-aware placement (ISSUE 3): a block-structured net run through the
# sparse transport with the greedy placement permutation — spike trains must
# come back in the caller's neuron order, identical to the single-host
# exec_fap anchor on the unpermuted net
from repro.core.topology import TopologyConfig
from repro.distributed import placement as plc

net_blk = network.make_network(n, k_in=4, seed=3,
                               topology=TopologyConfig("block", n_blocks=4,
                                                       p_in=0.95))
res_blk_ref = exec_fap.run_fap_vardt(model, net_blk, iinj, 6.0,
                                     step_budget=8, ev_cap=32)
res_blk, _ = run_fap_spmd(model, net_blk, iinj, 6.0, mesh, transport="sparse",
                          exchange=ExchangeSpec(parcel_cap=8),
                          placement="greedy", max_rounds=60)
out["placed_anchor"] = {"trains": trains(res_blk_ref),
                        "dropped": int(res_blk_ref.dropped)}
out["placed"] = {"trains": trains(res_blk), "dropped": int(res_blk.dropped),
                 "failed": bool(res_blk.failed)}

# per-channel bytes: block+placement vs uniform at the same N — the notify
# frontier (and its gather) must shrink by ~the measured frontier ratio
nn = 256
net_u = network.make_network(nn, k_in=4, seed=5)
net_b = network.make_network(nn, k_in=4, seed=5,
                             topology=TopologyConfig("block", n_blocks=4,
                                                     p_in=0.98))
pl = plc.compute_placement(net_b, 4, method="greedy")
spec = PaperNeuroSpec(n_neurons=nn, k_in=4, ev_cap=8, t_end=6.0)
for tag, netx in (("uniform", net_u), ("block_placed",
                                       plc.place_network(net_b, pl))):
    fn, args, sh = build_fap_round(model, spec, mesh, optimized=True,
                                   transport="sparse",
                                   exchange=ExchangeSpec(parcel_cap=8),
                                   net=netx)
    txt = jax.jit(fn, in_shardings=sh).lower(*args).compile().as_text()
    out[f"bytes/topo/{tag}"] = collective_channel_bytes(txt)
out["frontier_ratio"] = (plc.frontier_stats(net_u, 4)["F"]
                         / max(1, plc.frontier_stats(net_b, 4, pl)["F"]))

# per-channel collective bytes of the compiled round at two values of N
cap = 8
for nn in (64, 256):
    netn = network.make_network(nn, k_in=4, seed=5)
    spec = PaperNeuroSpec(n_neurons=nn, k_in=4, ev_cap=8, t_end=6.0)
    for tr in ("sparse", "allgather"):
        fn, args, sh = build_fap_round(
            model, spec, mesh, optimized=True, transport=tr,
            exchange=ExchangeSpec(parcel_cap=cap), net=netn)
        txt = jax.jit(fn, in_shardings=sh).lower(*args).compile().as_text()
        out[f"bytes/{tr}/n{nn}"] = collective_channel_bytes(txt)

# ragged per-class attribution: each class branch's sized all_to_all is
# separately scoped (exchange_parcel_c<cap>), so its bytes are measured
# from the lowered module; the class ladder's payloads must sit strictly
# below the static cap's except the last, which equals it
from repro.distributed.exchange import class_tag

xspec = ExchangeSpec(parcel_cap=cap)
fn, args, sh = build_fap_round(model, spec, mesh, optimized=True,
                               transport="sparse_ragged", exchange=xspec,
                               net=netn)
txt = jax.jit(fn, in_shardings=sh).lower(*args).compile().as_text()
ladder = xspec.class_ladder()
tags = tuple(class_tag(c) for c in ladder)
out["ragged_classes"] = list(ladder)
out["bytes/ragged_by_class"] = collective_channel_bytes(txt, tags=tags)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def spmd_out():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=ROOT)
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


pytestmark = pytest.mark.slow


def _assert_same_trains(a, b):
    assert len(a) == len(b)
    for ta, tb in zip(a, b):
        assert len(ta) == len(tb)
        if ta:
            assert max(abs(x - y) for x, y in zip(ta, tb)) < 1e-9


def test_optimized_matches_global_path(spmd_out):
    """optimized=True (shard_map + explicit channels) reproduces the
    GSPMD-lowered global round event for event."""
    assert spmd_out["global"]["dropped"] == 0
    assert sum(len(t) for t in spmd_out["global"]["trains"]) > 0
    _assert_same_trains(spmd_out["global"]["trains"],
                        spmd_out["allgather"]["trains"])


def test_spmd_driver_matches_single_host_runner(spmd_out):
    """run_fap_spmd anchored against the independent exec_fap runner (same
    knobs) — the SPMD paths must reproduce the single-host spike trains,
    not merely agree with each other."""
    assert spmd_out["single_host"]["dropped"] == 0
    _assert_same_trains(spmd_out["single_host"]["trains"],
                        spmd_out["global"]["trains"])


def test_sparse_matches_allgather(spmd_out):
    """Acceptance: with no parcel overflow the sparse transport delivers the
    identical event stream — including through the wheel queue."""
    for name in ("sparse", "sparse_wheel"):
        assert spmd_out[name]["dropped"] == 0, name
        assert not spmd_out[name]["failed"]
        _assert_same_trains(spmd_out["allgather"]["trains"],
                            spmd_out[name]["trains"])


def test_compact_batch_matches_dense(spmd_out):
    """Acceptance (ISSUE 4): the shard-local active-set compaction
    (batch="compact") composed with the sparse transport reproduces the
    dense batch's event stream exactly, with nothing dropped."""
    assert spmd_out["sparse_compact"]["dropped"] == 0
    assert not spmd_out["sparse_compact"]["failed"]
    _assert_same_trains(spmd_out["allgather"]["trains"],
                        spmd_out["sparse_compact"]["trains"])
    assert spmd_out["sparse_compact"]["rounds"] == \
        spmd_out["sparse"]["rounds"]


def test_ragged_matches_allgather(spmd_out):
    """Acceptance (ISSUE 5): the two-phase ragged transport delivers the
    identical event stream — class sizing is pure capacity, never
    semantics."""
    assert spmd_out["sparse_ragged"]["dropped"] == 0
    assert not spmd_out["sparse_ragged"]["failed"]
    _assert_same_trains(spmd_out["allgather"]["trains"],
                        spmd_out["sparse_ragged"]["trains"])


def test_full_stack_matches_allgather(spmd_out):
    """Acceptance (ISSUE 5): compact batch + compact fan-out + incremental
    SPMD horizon + ragged parcels, all composed, reproduce the dense
    reference event-for-event in the same number of rounds."""
    assert spmd_out["full_stack"]["dropped"] == 0
    assert not spmd_out["full_stack"]["failed"]
    _assert_same_trains(spmd_out["allgather"]["trains"],
                        spmd_out["full_stack"]["trains"])
    assert spmd_out["full_stack"]["rounds"] == spmd_out["sparse"]["rounds"]


def test_ragged_bytes_below_static_cap(spmd_out):
    """Acceptance (ISSUE 5): realized ragged parcel bytes on the (quiet)
    driven run sit strictly below the static-cap transport's and never
    exceed them; the per-class HLO attribution confirms every class but
    the last is strictly smaller than the static exchange and the last is
    exactly it."""
    sp = spmd_out["sparse"]["comm"]["parcel_bytes"]
    rg = spmd_out["sparse_ragged"]["comm"]["parcel_bytes"]
    assert spmd_out["sparse_ragged"]["rounds"] == spmd_out["sparse"]["rounds"]
    assert 0 < rg < sp
    # per-class lowered bytes: ascending, last == static sparse
    ladder = spmd_out["ragged_classes"]
    by_class = spmd_out["bytes/ragged_by_class"]
    static = spmd_out["bytes/sparse/n256"]["exchange_parcel"]
    per_class = [by_class[f"exchange_parcel_c{c}/"] for c in ladder]
    assert per_class == sorted(per_class)
    assert all(b < static for b in per_class[:-1])
    assert per_class[-1] == static
    # telemetry cross-check: realized bytes bounded by whole rounds of the
    # HLO-measured class payloads (parcel bytes are cap-sized and N-free,
    # so the n=256 lowering prices the driven n=32 run's classes too)
    rounds = spmd_out["sparse_ragged"]["rounds"]
    lo, hi = per_class[0], per_class[-1]
    assert rounds * lo <= rg <= rounds * hi


def test_parcel_overflow_detected_never_silent(spmd_out):
    """cap=1 on a hot network must fire the drop counter — through the
    static-cap transport and through the ragged classed exchange alike."""
    assert spmd_out["overflow_dropped"] > 0
    assert spmd_out["ragged_overflow_dropped"] > 0


def test_parcel_bytes_scale_with_cap_not_n(spmd_out):
    """Acceptance: the sparse spike-parcel channel's collective bytes are a
    function of (n_shards, parcel_cap) only — identical at N=64 and N=256 —
    while the dense reference transport's grow with N."""
    sp64 = spmd_out["bytes/sparse/n64"]["exchange_parcel"]
    sp256 = spmd_out["bytes/sparse/n256"]["exchange_parcel"]
    ag64 = spmd_out["bytes/allgather/n64"]["exchange_parcel"]
    ag256 = spmd_out["bytes/allgather/n256"]["exchange_parcel"]
    assert sp64 > 0 and sp64 == sp256
    assert ag256 >= 3 * ag64                    # ~linear in N (4x neurons)
    # and the cap-sized parcels beat the dense channel already at N=256
    assert sp256 < ag256


def test_notify_channel_attributed(spmd_out):
    """Both transports tag their clock-notification collectives."""
    for tr in ("sparse", "allgather"):
        assert spmd_out[f"bytes/{tr}/n256"]["exchange_notify"] > 0


def test_placement_roundtrip_matches_single_host_anchor(spmd_out):
    """Acceptance (ISSUE 3): the SPMD round on a greedy-placed block net
    returns spike trains event-for-event identical to the single-host
    exec_fap anchor on the unpermuted net — the placement permutation is
    applied before sharding and inverted on outputs."""
    assert spmd_out["placed"]["dropped"] == 0
    assert not spmd_out["placed"]["failed"]
    assert sum(len(t) for t in spmd_out["placed_anchor"]["trains"]) > 0
    _assert_same_trains(spmd_out["placed_anchor"]["trains"],
                        spmd_out["placed"]["trains"])


def test_placement_cuts_notify_bytes_by_locality_factor(spmd_out):
    """Acceptance (ISSUE 3): on the 4-shard mesh the block-structured net
    under greedy placement cuts the notify-channel collective bytes vs
    uniform-random by at least ~the measured frontier (locality) ratio;
    parcel bytes stay cap-sized for both."""
    uni = spmd_out["bytes/topo/uniform"]
    blk = spmd_out["bytes/topo/block_placed"]
    f_ratio = spmd_out["frontier_ratio"]
    assert f_ratio >= 2.0
    ratio = uni["exchange_notify"] / max(1, blk["exchange_notify"])
    assert ratio >= max(2.0, 0.8 * f_ratio), (ratio, f_ratio)
    assert blk["exchange_parcel"] == uni["exchange_parcel"]
