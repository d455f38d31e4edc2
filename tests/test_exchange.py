"""Exchange-layer units that need no multi-device mesh: the sort-free
spike compaction, the static shard-frontier builder, and the
per-channel HLO byte attribution."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sched
from repro.distributed.sharding import shard_frontier
from repro.kernels.event_wheel import ops as ew_ops
from repro.launch.hlo_analysis import collective_channel_bytes


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("cap", [4, 16, 64])
def test_compact_pallas_matches_ref(seed, cap):
    """The Pallas prefix-rank compaction == the jnp scatter oracle, for
    under- and over-full rows."""
    rng = np.random.default_rng(seed)
    D, M = 6, 41
    mask = jnp.asarray(rng.random((D, M)) < 0.4)
    vals = jnp.asarray(rng.uniform(0.0, 10.0, (D, M)))
    i1, v1, c1 = ew_ops.spike_compact(mask, vals, cap, impl="pallas")
    i2, v2, c2 = ew_ops.spike_compact(mask, vals, cap, impl="jnp")
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    # semantic oracle: row d packs the first min(cap, kept) masked columns
    m_np = np.asarray(mask)
    for d in range(D):
        cols = np.flatnonzero(m_np[d])
        assert int(c1[d]) == len(cols)               # count NOT capped
        kept = cols[:cap]
        got = np.asarray(i1[d])
        assert (got[: len(kept)] == kept).all()
        assert (got[len(kept):] == M).all()          # sentinel pads
        np.testing.assert_allclose(np.asarray(v1[d])[: len(kept)],
                                   np.asarray(vals[d])[kept])


def test_compact_pallas_tiled_cap_matches_ref():
    """A parcel cap above the kernel's cap block (``BCAP_DEFAULT``) tiles
    over several cap blocks; one row overflows the cap, one does not."""
    from repro.kernels.event_wheel.event_wheel import BCAP_DEFAULT
    rng = np.random.default_rng(3)
    D, M = 2, BCAP_DEFAULT + 276
    cap = BCAP_DEFAULT + 26
    mask = jnp.asarray(rng.random((D, M)) < np.array([[0.98], [0.5]]))
    vals = jnp.asarray(rng.uniform(0.0, 10.0, (D, M)))
    i1, v1, c1 = ew_ops.spike_compact(mask, vals, cap, impl="pallas")
    i2, v2, c2 = ew_ops.spike_compact(mask, vals, cap, impl="jnp")
    assert int(c1[0]) > cap > int(c1[1])
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


def test_compact_jaxpr_sort_free():
    """Acceptance wiring: the spike-parcel packer lowers without any sort
    primitive on either implementation."""
    mask = jnp.asarray(np.random.default_rng(0).random((4, 32)) < 0.3)
    vals = jnp.ones((4, 32))
    for impl in ("pallas", "jnp"):
        prims = sched.jaxpr_primitives(
            lambda m, v: ew_ops.spike_compact(m, v, 8, impl=impl), mask, vals)
        assert "sort" not in prims, (impl, prims)


def test_shard_frontier_tables():
    """Boundary sets and destination map against a brute-force oracle."""
    rng = np.random.default_rng(3)
    n, k, n_shards = 24, 3, 4
    n_local = n // n_shards
    post = np.repeat(np.arange(n, dtype=np.int32), k)
    pre = rng.integers(0, n, n * k).astype(np.int32)
    fr = shard_frontier(pre, post, n, n_shards)
    assert fr.dest_map.shape == (n, n_shards)
    for i in range(n):
        dests = set(post[pre == i] // n_local)
        assert set(np.flatnonzero(fr.dest_map[i])) == dests
    for s in range(n_shards):
        own = (pre // n_local == s)
        cross = own & (post // n_local != s)
        expect = set(pre[cross])
        gids = fr.boundary_gid[s]
        assert set(gids[gids < n]) == expect
        rel = fr.boundary_rel[s][gids < n]
        assert (rel == gids[gids < n] - s * n_local).all()


def test_shard_frontier_rejects_indivisible():
    with pytest.raises(ValueError):
        shard_frontier(np.zeros(4, np.int32), np.zeros(4, np.int32), 10, 4)


FAKE_HLO = """\
ENTRY %main (p: f64[8]) -> f64[8] {
  %ag = f64[64]{0} all-gather(f64[16]{0} %a), channel_id=1, metadata={op_name="jit(f)/shmap/exchange_notify/all_gather" source_file="x.py"}
  %a2a = (s32[1,8]{1,0}, s32[1,8]{1,0}) all-to-all(s32[1,8]{1,0} %b, s32[1,8]{1,0} %c), channel_id=2, metadata={op_name="jit(f)/shmap/exchange_parcel/all_to_all"}
  %ar = f64[4]{0} all-reduce(f64[4]{0} %d), channel_id=3, metadata={op_name="jit(f)/psum"}
  ROOT tuple = (f64[8]) tuple(%p)
}
"""


def test_collective_channel_bytes_attribution():
    """named_scope tags in op_name metadata route collective bytes to their
    channel; untagged collectives land in "other"."""
    got = collective_channel_bytes(FAKE_HLO)
    assert got["exchange_notify"] == 64 * 8
    assert got["exchange_parcel"] == 2 * 8 * 4     # tuple components summed
    assert got["other"] == 4 * 8
