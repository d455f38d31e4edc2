"""Pallas TPU kernel: fused HH gating rates + ionic currents (the CVODE f).

This is the per-step mechanism hot-spot (NEURON spends the bulk of a step in
the channel state/current update — exp/div-heavy VPU work).  The kernel
fuses, for a tile of neurons x compartments:

    rates alpha/beta(V) -> (dm, dh, dn), ionic current i(V, m, h, n) and the
    conductance total g_tot (the Newton-diagonal term),

in a single VMEM pass over the state — one load of (v, m, h, n) and one store
of each output instead of ~10 separate HLO loops.

Layout: [BN, C] tiles — neurons on sublanes, compartments on lanes.
VMEM/block = 9 tiles * BN*C*4B; BN=256, C=64 -> ~2.4 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import mechanisms as mech

BN_DEFAULT = 256


def _hh_rhs_kernel(area_ref, v_ref, m_ref, h_ref, n_ref,
                   dm_ref, dh_ref, dn_ref, i_ref, g_ref):
    v = v_ref[...]
    m = m_ref[...]
    h = h_ref[...]
    n = n_ref[...]
    area = area_ref[...]                       # [1, C] broadcast over neurons
    dm, dh, dn = mech.gate_derivs(v, m, h, n)
    g_na, g_k, g_l = mech.channel_conductances(area, m, h, n)
    i_ion = g_na * (v - mech.ENA) + g_k * (v - mech.EK) + g_l * (v - mech.EL)
    dm_ref[...] = dm
    dh_ref[...] = dh
    dn_ref[...] = dn
    i_ref[...] = i_ion
    g_ref[...] = g_na + g_k + g_l


def hh_rhs_pallas(area, v, m, h, n, *, block_n: int = BN_DEFAULT,
                  interpret: bool = False):
    """area: [C]; v,m,h,n: [N, C] -> (dm, dh, dn, i_ion, g_tot) each [N, C]."""
    N, C = v.shape
    assert N % block_n == 0, (N, block_n)
    grid = (N // block_n,)
    dt = v.dtype
    out_shape = tuple(jax.ShapeDtypeStruct((N, C), dt) for _ in range(5))
    tile = pl.BlockSpec((block_n, C), lambda i: (i, 0))
    return pl.pallas_call(
        _hh_rhs_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, C), lambda i: (0, 0))] + [tile] * 4,
        out_specs=(tile,) * 5,
        out_shape=out_shape,
        interpret=interpret,
    )(area.reshape(1, C).astype(dt), v, m, h, n)
