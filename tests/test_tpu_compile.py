"""Compile-only checks that the main path's Pallas kernels lower for a TPU
v5e, at the widths ``chip_smoke.py`` runs them: N = 65,536 neurons,
``batch_cap`` 1,024, k_out 16, and the SPMD round's 16,384-neuron shards.

Nothing runs: the chip is described (``v5e:2x2``), not attached, and the
compiler refuses here what it would refuse on the chip.  The topology is
described inside a fixture, so every pytest worker collects the same tests
and only the worker that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.event_wheel import ops as ew_ops

N, CAP, K_OUT, SHARDS = 65536, 1024, 16, 4


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def tpu_dispatch(monkeypatch):
    """Make the ops take their TPU branch (compiled kernels)."""
    monkeypatch.setattr(ew_ops, "use_interpret", lambda: False)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


CASES = {
    # the compact round's active set: frontier mask -> [batch_cap] ids
    "compact_ids": (lambda m: ew_ops.compact_ids(m, CAP, impl="pallas"),
                    [((N,), jnp.bool_)]),
    # a cap above the kernel's cap block: tiled over a second grid axis
    "compact_ids_wide_cap": (
        lambda m: ew_ops.compact_ids(m, 4 * CAP, impl="pallas"),
        [((N,), jnp.bool_)]),
    # the compact fan-out: spiking lanes -> their out-edge rows
    "compact_gather": (
        lambda m, t: ew_ops.compact_gather(m, t, CAP, fill=N * K_OUT,
                                           impl="pallas"),
        [((N,), jnp.bool_), ((N, K_OUT), jnp.int32)]),
    # the wheel's batch insert ranks [spike_cap * k_out] events
    "segment_rank": (
        lambda k: ew_ops.segment_rank(k, N * 16, 20, impl="pallas"),
        [((CAP * K_OUT,), jnp.int32)]),
    # the sparse transports' parcel packer: [shards, shard width] rows,
    # f64 spike times gathered outside the kernel
    "spike_compact": (
        lambda m, v: ew_ops.spike_compact(m, v, N // SHARDS, impl="pallas"),
        [((SHARDS, N // SHARDS), jnp.bool_),
         ((SHARDS, N // SHARDS), jnp.float64)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_main_path_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache,
                                           tpu_dispatch):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()
