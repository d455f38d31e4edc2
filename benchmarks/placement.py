"""Locality-aware placement benchmark: notify bytes vs topology/placement.

The sparse transport's clock-notification channel gathers each shard's
boundary set, so its bytes track the notify frontier F
(``sharding.shard_frontier``).  This benchmark builds the optimized SPMD
round on a forced-host-device mesh across a (topology, placement) grid —
uniform-random (the documented worst case: every neuron is boundary) vs
block-structured wiring with label-shuffled ids recovered by the
contiguous-block and greedy edge-cut placement passes
(``distributed.placement``) — and reports, per cell, the measured
``exchange_notify`` / ``exchange_parcel`` bytes from the compiled HLO plus
the counted cut edges and frontier sizes.

The locality claim is *asserted*, not assumed (a regression fails this
bench, and ``scripts/check.sh``, which runs it in quick mode as the local
placement smoke):

  * placed block nets cut the notify bytes vs uniform-random by at least
    ~the measured frontier ratio (the block locality factor), and by >= 2x
    outright, while parcel bytes stay cap-sized for both;
  * greedy edge-cut <= contiguous-block cut <= identity cut on the
    shuffled net (the passes never lose locality);
  * uniform-random notify bytes are unchanged by placement (worst case
    stays worst).

Runs in a subprocess (jax device counts lock at first init):
  quick (REPRO_BENCH_QUICK=1): 2x2 mesh,  N=256,  k_in=4
  full:                        16x16 mesh, N=65536, k_in=16
"""
from __future__ import annotations

import os
import subprocess
import sys

PARCEL_CAP = 8
P_IN = 0.99          # block wiring: in-block probability of each in-edge


def run() -> None:
    """Orchestrator entry (run.py / check.sh): spawn the forced-host-device
    worker, stream its CSV through, record it for the JSON dump."""
    from benchmarks.common import cpu_mesh_env, dump_json, record_csv

    quick = os.environ.get("REPRO_BENCH_QUICK") == "1"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = cpu_mesh_env(root, 4 if quick else 256)
    res = subprocess.run(
        [sys.executable, "-m", "benchmarks.placement", "--worker"],
        env=env, capture_output=True, text=True, cwd=root,
        timeout=(900 if quick else 7200))
    sys.stdout.write(res.stdout)
    record_csv(res.stdout)
    if res.returncode != 0:
        raise RuntimeError(f"placement worker failed:\n{res.stderr[-3000:]}")
    dump_json("placement")


def _worker() -> None:
    import jax
    import numpy as np

    jax.config.update("jax_enable_x64", True)

    from benchmarks.common import emit
    from repro.core import network, topology
    from repro.core.cell import CellModel
    from repro.core import morphology
    from repro.distributed import placement as plc
    from repro.distributed.exchange import ExchangeSpec
    from repro.distributed.fap_spmd import PaperNeuroSpec, build_fap_round
    from repro.launch.hlo_analysis import collective_channel_bytes
    from repro.launch.mesh import make_mesh

    quick = os.environ.get("REPRO_BENCH_QUICK") == "1"
    shape = (2, 2) if quick else (16, 16)
    n = 256 if quick else 65536
    k_in = 4 if quick else 16
    mesh = make_mesh(shape, ("data", "model"))
    # HLO bytes on an emulated CPU mesh, by design (JAX_PLATFORMS=cpu)
    emit("placement/platform", 0.0,
         f"platform={jax.devices()[0].platform};count={len(jax.devices())};"
         "emulated_cpu_mesh=True")
    n_shards = int(np.prod(shape))
    model = CellModel(morphology.soma_only())

    def channel_bytes(net):
        spec = PaperNeuroSpec(n_neurons=int(net.n), k_in=k_in, ev_cap=8,
                              t_end=100.0)
        fn, args, sh = build_fap_round(
            model, spec, mesh, optimized=True, transport="sparse",
            exchange=ExchangeSpec(parcel_cap=PARCEL_CAP), net=net)
        txt = jax.jit(fn, in_shardings=sh).lower(*args).compile().as_text()
        return collective_channel_bytes(txt)

    net_u = network.make_network(n, k_in=k_in, seed=0)
    net_b = network.make_network(
        n, k_in=k_in, seed=0,
        topology=topology.TopologyConfig("block", n_blocks=n_shards,
                                         p_in=P_IN))
    # scatter the block net's labels: placement must *recover* locality,
    # not inherit it from the generator's already-contiguous ids
    shuffle = np.random.default_rng(1).permutation(n)
    net_s = plc.place_network(
        net_b, plc.from_order(shuffle, n_shards, net_b, "shuffle"))

    cells = [("uniform", net_u, "identity"),
             ("block_shuffled", net_s, "identity"),
             ("block_shuffled", net_s, "block"),
             ("block_shuffled", net_s, "greedy")]
    bytes_of, stats_of, cut_of = {}, {}, {}
    for topo_name, net, method in cells:
        pl = plc.compute_placement(net, n_shards, method=method)
        placed = plc.place_network(net, pl)
        ch = channel_bytes(placed)
        st = plc.frontier_stats(net, n_shards, pl)
        key = (topo_name, method)
        bytes_of[key], stats_of[key], cut_of[key] = ch, st, pl.cut
        emit(f"placement/bytes/{topo_name}/{method}", 0.0,
             f"notify={ch['exchange_notify']};"
             f"parcel={ch['exchange_parcel']};F={st['F']};"
             f"cut={pl.cut};cut_frac={st['cut_frac']:.4f};"
             f"boundary_frac={st['boundary_frac']:.4f};n={n};"
             f"n_shards={n_shards}")

    # --- the locality claim, asserted --------------------------------------
    uni = bytes_of[("uniform", "identity")]
    for method in ("block", "greedy"):
        blk = bytes_of[("block_shuffled", method)]
        f_ratio = stats_of[("uniform", "identity")]["F"] / max(
            1, stats_of[("block_shuffled", method)]["F"])
        b_ratio = uni["exchange_notify"] / max(1, blk["exchange_notify"])
        ok = b_ratio >= max(2.0, 0.8 * f_ratio)
        emit(f"placement/locality_factor/{method}", 0.0,
             f"notify_byte_ratio={b_ratio:.2f};frontier_ratio={f_ratio:.2f};"
             f"ok={ok}")
        if not ok:
            raise AssertionError(
                f"placed block net did not cut notify bytes by the locality "
                f"factor: bytes ratio {b_ratio:.2f} vs frontier ratio "
                f"{f_ratio:.2f} ({method})")
        if blk["exchange_parcel"] != uni["exchange_parcel"]:
            raise AssertionError(
                "parcel bytes must stay cap-sized across topologies: "
                f"{blk['exchange_parcel']} vs {uni['exchange_parcel']}")
    if not (cut_of[("block_shuffled", "greedy")]
            <= cut_of[("block_shuffled", "block")]
            <= cut_of[("block_shuffled", "identity")]):
        raise AssertionError(f"placement passes lost locality: {cut_of}")
    # worst case stays worst: placement cannot manufacture locality on
    # uniform wiring (greedy may shave a sliver; the frontier stays ~N)
    pl_u = plc.compute_placement(net_u, n_shards, method="greedy")
    st_u = plc.frontier_stats(net_u, n_shards, pl_u)
    emit("placement/uniform_worst_case", 0.0,
         f"boundary_frac_placed={st_u['boundary_frac']:.4f}")
    if st_u["boundary_frac"] < 0.5:
        raise AssertionError(
            "uniform wiring should stay ~all-boundary under placement, got "
            f"boundary_frac={st_u['boundary_frac']:.4f}")


if __name__ == "__main__":
    if "--worker" in sys.argv:
        _worker()
    else:
        run()
