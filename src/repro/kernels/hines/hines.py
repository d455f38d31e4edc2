"""Pallas TPU kernel: batched Hines tree-tridiagonal solve.

TPU adaptation (DESIGN.md §3): the GPU/CPU formulation walks one neuron's
tree serially.  On TPU we transpose the problem — the *batch* of neurons
lies along the 128-wide lane dimension and the compartment index along
sublanes, so every elimination/substitution step is a full-width VPU
operation over ``BN`` neurons at once.  All neurons in a block share one
topology (networks are built from morphology classes), so ``parent`` is a
scalar (SMEM) array driving dynamic sublane indexing.

Layout:  d, b, out x : [C, BN]  (compartments x neurons), g_axial: [C],
parent: int32[C].  VMEM footprint per block = 3 * C * BN * 4B (+2 vectors);
with C = 64, BN = 256 that is ~196 KiB — comfortably inside the ~16 MiB
v5e VMEM while keeping lanes full (BN multiple of 128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BN_DEFAULT = 256          # neurons per block (lane multiples)


def _hines_kernel(parent_ref, gax_ref, d_ref, b_ref, x_ref, *, n_comp):
    C = n_comp
    idx_t = jnp.arange(1).dtype      # platform default int (int32 on TPU)

    def load_row(ref, i):
        return ref[pl.ds(i, 1), :]                                # [1, BN]

    def store_row(ref, i, val):
        ref[pl.ds(i, 1), :] = val

    # copy inputs into the output buffers we mutate in place
    x_ref[...] = b_ref[...]
    dwork = d_ref[...]

    # --- backward (child -> parent) elimination --------------------------
    def elim(idx, dwork):
        i = (C - 1 - idx).astype(idx_t)                           # C-1 .. 1
        p = parent_ref[i].astype(idx_t)
        a_i = gax_ref[i]
        d_i = jax.lax.dynamic_slice_in_dim(dwork, i, 1, axis=0)
        b_i = load_row(x_ref, i)
        f = a_i / d_i
        d_p = jax.lax.dynamic_slice_in_dim(dwork, p, 1, axis=0)
        b_p = load_row(x_ref, p)
        dwork = jax.lax.dynamic_update_slice_in_dim(dwork, d_p - f * a_i, p, axis=0)
        store_row(x_ref, p, b_p + f * b_i)
        return dwork

    dwork = jax.lax.fori_loop(0, C - 1, elim, dwork)

    # --- forward (parent -> child) substitution ---------------------------
    root = load_row(x_ref, 0) / jax.lax.dynamic_slice_in_dim(dwork, 0, 1, axis=0)
    store_row(x_ref, 0, root)

    def subst(i, _):
        i = i.astype(idx_t)
        p = parent_ref[i].astype(idx_t)
        a_i = gax_ref[i]
        d_i = jax.lax.dynamic_slice_in_dim(dwork, i, 1, axis=0)
        v = (load_row(x_ref, i) + a_i * load_row(x_ref, p)) / d_i
        store_row(x_ref, i, v)
        return 0

    jax.lax.fori_loop(1, C, subst, 0)


def hines_solve_pallas(parent, g_axial, d, b, *, block_n: int = BN_DEFAULT,
                       interpret: bool = False):
    """Solve the batched tree system.  d, b: [C, N] -> x: [C, N].

    parent: int32[C] shared topology; g_axial: [C] (same dtype as d).
    N must be a multiple of block_n (wrappers pad).
    """
    C, N = d.shape
    assert N % block_n == 0, (N, block_n)
    grid = (N // block_n,)
    kernel = functools.partial(_hines_kernel, n_comp=C)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((C,), lambda i: (0,)),                  # parent
            pl.BlockSpec((C,), lambda i: (0,)),                  # g_axial
            pl.BlockSpec((C, block_n), lambda i: (0, i)),        # d
            pl.BlockSpec((C, block_n), lambda i: (0, i)),        # b
        ],
        out_specs=pl.BlockSpec((C, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((C, N), d.dtype),
        interpret=interpret,
    )(parent, g_axial, d, b)


def _hines_factor_kernel(parent_ref, gax_ref, d_ref, de_ref, *, n_comp):
    """Backward elimination on the diagonal alone — the setup half of the
    split Newton solve.  The diagonal updates never read b, so the
    eliminated diagonal is an LU-style factor reusable across solves."""
    C = n_comp
    idx_t = jnp.arange(1).dtype

    de_ref[...] = d_ref[...]

    def elim(idx, _):
        i = (C - 1 - idx).astype(idx_t)                           # C-1 .. 1
        p = parent_ref[i].astype(idx_t)
        a_i = gax_ref[i]
        d_i = de_ref[pl.ds(i, 1), :]
        d_p = de_ref[pl.ds(p, 1), :]
        f = a_i / d_i
        de_ref[pl.ds(p, 1), :] = d_p - f * a_i
        return 0

    jax.lax.fori_loop(0, C - 1, elim, 0)


def _hines_solve_factored_kernel(parent_ref, gax_ref, de_ref, b_ref, x_ref,
                                 *, n_comp):
    """The solve half: two O(C) sweeps against a stored eliminated
    diagonal.  Same FP op sequence on b as the fused kernel, so the
    composition factor-then-solve is bitwise-identical to one fused
    solve."""
    C = n_comp
    idx_t = jnp.arange(1).dtype

    def load_row(ref, i):
        return ref[pl.ds(i, 1), :]                                # [1, BN]

    def store_row(ref, i, val):
        ref[pl.ds(i, 1), :] = val

    x_ref[...] = b_ref[...]

    # --- forward (child -> parent) elimination of b ----------------------
    def fwd(idx, _):
        i = (C - 1 - idx).astype(idx_t)                           # C-1 .. 1
        p = parent_ref[i].astype(idx_t)
        a_i = gax_ref[i]
        f = a_i / load_row(de_ref, i)
        store_row(x_ref, p, load_row(x_ref, p) + f * load_row(x_ref, i))
        return 0

    jax.lax.fori_loop(0, C - 1, fwd, 0)

    # --- backward (parent -> child) substitution --------------------------
    store_row(x_ref, 0, load_row(x_ref, 0) / load_row(de_ref, 0))

    def subst(i, _):
        i = i.astype(idx_t)
        p = parent_ref[i].astype(idx_t)
        a_i = gax_ref[i]
        v = (load_row(x_ref, i) + a_i * load_row(x_ref, p)) / load_row(de_ref, i)
        store_row(x_ref, i, v)
        return 0

    jax.lax.fori_loop(1, C, subst, 0)


def hines_factor_pallas(parent, g_axial, d, *, block_n: int = BN_DEFAULT,
                        interpret: bool = False):
    """Eliminate the batched assembled diagonal.  d: [C, N] -> d_elim: [C, N].

    parent: int32[C] shared topology; g_axial: [C] (same dtype as d).
    N must be a multiple of block_n (wrappers pad).
    """
    C, N = d.shape
    assert N % block_n == 0, (N, block_n)
    grid = (N // block_n,)
    kernel = functools.partial(_hines_factor_kernel, n_comp=C)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((C,), lambda i: (0,)),                  # parent
            pl.BlockSpec((C,), lambda i: (0,)),                  # g_axial
            pl.BlockSpec((C, block_n), lambda i: (0, i)),        # d
        ],
        out_specs=pl.BlockSpec((C, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((C, N), d.dtype),
        interpret=interpret,
    )(parent, g_axial, d)


def hines_solve_factored_pallas(parent, g_axial, d_elim, b, *,
                                block_n: int = BN_DEFAULT,
                                interpret: bool = False):
    """Solve against a stored eliminated diagonal.  d_elim, b: [C, N] ->
    x: [C, N].  N must be a multiple of block_n (wrappers pad)."""
    C, N = d_elim.shape
    assert N % block_n == 0, (N, block_n)
    grid = (N // block_n,)
    kernel = functools.partial(_hines_solve_factored_kernel, n_comp=C)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((C,), lambda i: (0,)),                  # parent
            pl.BlockSpec((C,), lambda i: (0,)),                  # g_axial
            pl.BlockSpec((C, block_n), lambda i: (0, i)),        # d_elim
            pl.BlockSpec((C, block_n), lambda i: (0, i)),        # b
        ],
        out_specs=pl.BlockSpec((C, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((C, N), d_elim.dtype),
        interpret=interpret,
    )(parent, g_axial, d_elim, b)
