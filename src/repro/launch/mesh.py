"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state.  Run under ``XLA_FLAGS=--xla_force_host_platform_device_count=512``
for the dry-run (dryrun.py sets this itself before importing jax).

  single-pod:  (16, 16)      axes (data, model)          = 256 chips (v5e pod)
  multi-pod:   (2, 16, 16)   axes (pod, data, model)     = 512 chips
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis explicitly ``Auto`` (sharding
    propagated by GSPMD, as the rounds and the dry-run expect)."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, found {len(devices)} — run "
            "with XLA_FLAGS=--xla_force_host_platform_device_count=512")
    return make_mesh(shape, axes, devices[:need])


def make_local_mesh(n_model: int = 1):
    """Small mesh over whatever devices exist (tests)."""
    n = len(jax.devices())
    n_model = min(n_model, n)
    return make_mesh((n // n_model, n_model), ("data", "model"),
               jax.devices()[: (n // n_model) * n_model])


def elastic_mesh(n_devices: int, model_parallel: int = 16):
    """Elasticity: mesh factory as a pure function of the device count.
    Resize = remesh + checkpoint restore with resharding (DESIGN.md §6)."""
    devices = jax.devices()[:n_devices]
    mp = math.gcd(model_parallel, n_devices)
    return make_mesh((n_devices // mp, mp), ("data", "model"), devices)
