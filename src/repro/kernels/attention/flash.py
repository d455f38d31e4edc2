"""Pallas TPU kernel: causal GQA flash attention (online softmax).

The LM-zoo compute hot-spot.  Canonical TPU pattern: 3-axis grid
(batch*head, q_block, kv_block) with the (acc, m, l) running state in VMEM
scratch that persists across the innermost kv axis; the output tile is
finalised on the last kv block.  GQA is handled in the K/V index maps
(query head h reads kv head h // group).

Block sizes default to (128, 128): MXU-aligned, VMEM per block =
q(128xD) + k,v(128xD) + acc(128xD) + stats — ~0.4 MiB at D=128 fp32.

Supports q_len != kv_len (decode: q_len=1..few at the *end* of the causal
timeline, offset = kv_len - q_len).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1.0e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                  *, scale, causal, offset, kv_len, bq, bk, nk):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]                                  # [bq, D]
    k = k_ref[0]                                  # [bk, D]
    v = v_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    if causal:
        iq = pl.program_id(1)
        rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + offset
        s = jnp.where(cols <= rows, s, NEG_INF)
    s = jnp.where(cols < kv_len, s, NEG_INF)      # mask padded kv columns

    m_prev = m_ref[...]                           # [bq, 1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                        # [bq, bk]
    alpha = jnp.exp(m_prev - m_new)               # [bq, 1]
    l_new = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           bq: int = 128, bk: int = 128,
                           offset: int | None = None, kv_len: int | None = None,
                           interpret: bool = False):
    """q: [B, H, Sq, D]; k, v: [B, Hkv, Skv, D] -> [B, H, Sq, D].

    offset/kv_len describe the *real* (pre-padding) causal geometry:
    offset = real_kv_len - real_q_len; kv_len = real kv length.
    """
    B, H, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    assert H % Hkv == 0
    group = H // Hkv
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    assert Sq % bq == 0 and Skv % bk == 0
    nq, nk = Sq // bq, Skv // bk
    if kv_len is None:
        kv_len = Skv
    if offset is None:
        offset = kv_len - Sq
    scale = 1.0 / (D ** 0.5)
    qs = q.reshape(B * H, Sq, D)
    ks = k.reshape(B * Hkv, Skv, D)
    vs = v.reshape(B * Hkv, Skv, D)

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               offset=offset, kv_len=kv_len, bq=bq, bk=bk, nk=nk)

    def kv_map(bh, iq, ik):
        # query head -> its GQA kv head within the same batch element
        return ((bh // H) * Hkv + (bh % H) // group, ik, 0)

    from jax.experimental.pallas import tpu as pltpu

    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, D), kv_map),
            pl.BlockSpec((1, bk, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),     # acc
            pltpu.VMEM((bq, 1), jnp.float32),     # m
            pltpu.VMEM((bq, 1), jnp.float32),     # l
        ],
        interpret=interpret,
    )(qs, ks, vs)
    return out.reshape(B, H, Sq, D)
