"""Named-sharding rules for every parameter / activation in the framework.

Scheme (DESIGN.md §6):
  * TP  — attention/FFN output features, MoE expert dim, vocab over ``model``
  * FSDP — the complementary feature dim over ('pod','data') (ZeRO-3;
    optimizer state shards identically)
  * SP  — residual-stream sequence dim over ``model`` between layers
  * batch — over ('pod','data')
  * decode KV caches — sequence dim over ``model`` (kv-head counts are not
    generally divisible by the TP degree; sequence always is)

Rules are name-based over the parameter tree; anything unmatched replicates
(and is asserted to be small).  jax.jit tolerates non-divisible dims by
padding, so e.g. vocab=151655 shards fine over 16.

The neuro workload's static routing tables (``shard_frontier``) also live
here: the sparse spike-parcel transport (``distributed.exchange``) consumes
per-shard boundary sets and destination maps derived host-side from the
by-post edge layout.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig

# parameter-name classes
_COL = {"wq", "wk", "wv", "w_gate", "w_up", "cm_k", "w_in", "w1",
        "w_lora_a", "wr", "wg", "cm_r"}          # [d_in, d_out]: shard d_out
_ROW = {"wo", "w_down", "cm_v", "w2", "w_out", "w_lora_b", "wv_rwkv"}
_EMB = {"emb", "enc_pos"}
_MOE_COL = {"w_gate", "w_up"}                     # under "moe": [E, d, ff]
_MOE_ROW = {"w_down"}                             # under "moe": [E, ff, d]
_REPL_SMALL = {"ln1", "ln2", "ln", "lnx", "ln_f", "ln_x", "ln_y", "b1", "b2",
               "bx", "bn_f", "bq", "bk", "bv", "conv_b", "A_log", "D",
               "dt_bias", "w_bias", "bonus_u", "mu_r", "mu_k", "mu_v", "mu_w",
               "mu_g", "mu_ck", "mu_cr", "router", "conv_w", "patch_proj"}


def mesh_axes(mesh) -> Tuple[tuple, str]:
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    return dp, "model"


def _leaf_spec(path, leaf, dp, tp):
    names = [p.key for p in path if hasattr(p, "key")]
    name = names[-1] if names else ""
    in_moe = "moe" in names
    stacked = any(n in ("layers", "dec", "enc") for n in names)
    nd = leaf.ndim
    pre = (None,) if stacked else ()

    def spec(*dims):
        full = pre + dims
        if len(full) != nd:
            return P()                            # fallback: replicate
        return P(*full)

    if in_moe and name in _MOE_COL:
        return spec(tp, dp, None)
    if in_moe and name in _MOE_ROW:
        return spec(tp, None, dp)
    if name == "router":
        return spec(dp, None)
    if name in _EMB:
        return P(tp, dp)
    if name in _COL:
        return spec(dp, tp)
    if name in _ROW:
        return spec(tp, dp)
    if name in ("wq", "wk", "wv", "wo"):
        return spec(dp, tp)
    return P()                                     # norms, biases, scalars


def param_specs(cfg: ArchConfig, params_tree, mesh):
    """PartitionSpec tree matching a params (or grads/adam-moment) tree.

    ``params_tree`` may be real arrays or ShapeDtypeStructs.
    """
    dp, tp = mesh_axes(mesh)
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _leaf_spec(path, leaf, dp, tp), params_tree)


def opt_specs(cfg: ArchConfig, opt_state, mesh):
    """AdamWState(step, mu, nu): moments shard like params."""
    from repro.optim import AdamWState
    return AdamWState(step=P(),
                      mu=param_specs(cfg, opt_state.mu, mesh),
                      nu=param_specs(cfg, opt_state.nu, mesh))


def batch_specs(cfg: ArchConfig, shape: ShapeConfig, mesh):
    """Input shardings for a train/prefill batch dict."""
    dp, tp = mesh_axes(mesh)
    specs = {"tokens": P(dp, None), "labels": P(dp, None)}
    if cfg.family == "audio":
        specs["frames"] = P(dp, None, None)
    if cfg.family == "vlm":
        specs["patches"] = P(dp, None, None)
    return specs


def decode_state_specs(cfg: ArchConfig, shape: ShapeConfig, mesh):
    """Shardings for the decode cache / recurrent state."""
    dp, tp = mesh_axes(mesh)
    ndp = int(np.prod([mesh.shape[a] for a in dp]))
    bdim = dp if shape.global_batch >= ndp else None   # tiny batches replicate
    if cfg.family in ("dense", "vlm", "moe"):
        # [L, B, S, Hkv, hd]: sequence over model (SP decode)
        kv = P(None, bdim, tp, None, None)
        return {"k": kv, "v": kv}
    if cfg.family == "ssm":
        return {
            "tm_x": P(None, bdim, tp),
            "cm_x": P(None, bdim, tp),
            "S": P(None, bdim, tp, None, None),
        }
    if cfg.family == "hybrid":
        kv = P(None, bdim, tp, None, None)
        return {
            "conv": P(None, bdim, None, tp),
            "h": P(None, bdim, tp, None, None),
            "k": kv, "v": kv,
        }
    if cfg.family == "audio":
        kv = P(None, bdim, tp, None, None)
        return {"k": kv, "v": kv,
                "ek": P(None, bdim, None, None, None),
                "ev": P(None, bdim, None, None, None)}
    raise ValueError(cfg.family)


def token_spec(cfg: ArchConfig, shape: ShapeConfig, mesh):
    dp, _ = mesh_axes(mesh)
    ndp = int(np.prod([mesh.shape[a] for a in dp]))
    bdim = dp if shape.global_batch >= ndp else None
    return P(bdim, None)


def _fit_one(spec: P, shape, mesh) -> P:
    """Drop axis assignments whose size does not divide the dim (jit
    in_shardings require exact divisibility; e.g. vocab=151655 vs 16)."""
    if len(spec) > len(shape):
        return P()
    out = []
    for dim, entry in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        kept = []
        prod = 1
        for a in axes:
            if dim % (prod * mesh.shape[a]) == 0:
                kept.append(a)
                prod *= mesh.shape[a]
        out.append(tuple(kept) if len(kept) > 1 else (kept[0] if kept else None))
    return P(*out)


def fit_specs(spec_tree, sds_tree, mesh):
    """Apply _fit_one leafwise: spec_tree parallel to sds_tree."""
    return jax.tree_util.tree_map(
        lambda s, leaf: _fit_one(s, leaf.shape, mesh),
        spec_tree, sds_tree,
        is_leaf=lambda x: isinstance(x, P))


def to_named(tree_of_specs, mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree_of_specs,
        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# neuro workload: static shard-frontier routing tables (sparse transport)
# ---------------------------------------------------------------------------
LANES = 128          # a frontier wider than this is padded to whole rows


class ShardFrontier(NamedTuple):
    """Static routing tables for the sparse exchange, one row per shard.

    boundary_rel: i32[n_shards, F] — shard-relative indices of each shard's
        boundary neurons (local neurons with at least one cross-shard
        out-edge); pad slots hold 0 and are neutralised by the gid sentinel.
    boundary_gid: i32[n_shards, F] — the same neurons as global ids; pad
        slots hold the sentinel ``n`` (parked out of range on scatter).
    dest_map: bool[N, n_shards] — dest_map[i, d] iff neuron i has at least
        one out-edge into shard d (self-shard included, so parcels
        self-deliver through the same path).
    """
    boundary_rel: np.ndarray
    boundary_gid: np.ndarray
    dest_map: np.ndarray

    @property
    def frontier_size(self) -> int:
        return int(self.boundary_rel.shape[1])

    @property
    def sizes(self) -> np.ndarray:
        """True per-shard boundary counts (pad sentinels excluded)."""
        n = int(self.dest_map.shape[0])
        return (self.boundary_gid < n).sum(axis=1)


def shard_frontier(pre: np.ndarray, post: np.ndarray, n: int,
                   n_shards: int, perm: np.ndarray = None) -> ShardFrontier:
    """Derive the per-shard cross-shard in-edge frontier from the edge list.

    Neurons are block-sharded (shard of gid g = g // n_local, matching the
    round's ``P(flat)`` row sharding); edges are read host-side once at
    build time, so the returned tables are static for the whole run.

    ``perm`` (optional, from ``distributed.placement``): a neuron-id
    relabeling applied to both endpoints before deriving the tables — the
    frontier, and hence the sparse transport's notify bytes, then shrink
    with whatever locality the placement realizes.  The returned tables
    are in *new* (placed) ids, matching a run on the placed network.

    A frontier wider than one 128-lane row is padded with sentinel slots
    to whole rows (never past the shard's ``n_local``), so networks of one
    size whose widest boundary sets differ by a few neurons share the
    round's shapes, and so one executable.
    """
    if n % n_shards:
        raise ValueError(f"n={n} not divisible by n_shards={n_shards}")
    n_local = n // n_shards
    pre = np.asarray(pre, np.int64)
    post = np.asarray(post, np.int64)
    if perm is not None:
        perm = np.asarray(perm, np.int64)
        pre, post = perm[pre], perm[post]
    src_shard = pre // n_local
    dst_shard = post // n_local

    # destination map: unique (pre, dst_shard) pairs
    dest_map = np.zeros((n, n_shards), bool)
    dest_map[pre, dst_shard] = True

    # boundary set of shard s: its neurons appearing as pre on cross edges
    cross = src_shard != dst_shard
    bnd = np.unique(np.stack([src_shard[cross], pre[cross]], axis=1), axis=0)
    sizes = np.bincount(bnd[:, 0], minlength=n_shards) if bnd.size else \
        np.zeros(n_shards, np.int64)
    F = max(1, int(sizes.max()) if sizes.size else 1)
    if F > LANES:
        F = min(n_local, -(-F // LANES) * LANES)
    boundary_gid = np.full((n_shards, F), n, np.int32)
    boundary_rel = np.zeros((n_shards, F), np.int32)
    for s in range(n_shards):
        gids = bnd[bnd[:, 0] == s, 1]
        boundary_gid[s, : len(gids)] = gids
        boundary_rel[s, : len(gids)] = gids - s * n_local
    return ShardFrontier(boundary_rel, boundary_gid, dest_map)
