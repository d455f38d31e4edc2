"""Activation-sharding context.

Model code calls ``constrain(x, "residual")`` etc.; outside a sharding
context (CPU smoke tests, single device) this is a no-op, inside the dry-run
/ launcher it applies ``with_sharding_constraint`` with the mesh-appropriate
PartitionSpec (sequence parallelism on the residual stream, batch sharding on
token streams, expert sharding on MoE buffers).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
from jax.sharding import PartitionSpec as P

_state = threading.local()


def _specs(dp, tp):
    """dp: tuple of data axes (('pod','data') or ('data',)); tp: 'model'."""
    return {
        # [B, S, d] residual stream: batch over dp, sequence over tp (SP)
        "residual": P(dp, tp, None),
        # [B, S, d] without SP (pre-attention gathered form)
        "tokens3d": P(dp, None, None),
        # [B, S] token ids
        "tokens": P(dp, None),
        # [B, 1, d] decode hidden
        "decode_hidden": P(dp, None, None),
        # MoE dispatch buffer [G, E, C, d]: groups over data, experts over
        # model — the transition between the two IS the MoE all-to-all
        "moe_buf": P(dp, tp, None, None),
        # attention heads [B, S, H, hd]
        "heads": P(dp, None, tp, None),
    }


@contextlib.contextmanager
def sharding_ctx(mesh):
    """Enable activation constraints for a (pod,)data,model mesh.
    Also installs the mesh as jax's context mesh so PartitionSpec-based
    ``with_sharding_constraint`` resolves."""
    axes = mesh.axis_names
    dp = tuple(a for a in axes if a in ("pod", "data"))
    specs = _specs(dp, "model")
    prev = getattr(_state, "specs", None)
    _state.specs = specs
    try:
        with jax.set_mesh(mesh):
            yield
    finally:
        _state.specs = prev


def constrain(x, kind: str):
    specs = getattr(_state, "specs", None)
    if specs is None or kind not in specs:
        return x
    spec = specs[kind]
    if len(spec) > x.ndim:
        return x
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:
        return x
