"""JAX's persistent compilation cache, placed at a fixed path.

A round takes a minute or more to compile for a TPU, so entry points that
compile rounds (``chip_smoke.py``, ``benchmarks/run.py``,
``repro.launch.serve``) call ``use_compile_cache()`` once, before their
first compile.  Importing ``repro`` never does: library users keep
whatever cache their process configured.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def use_compile_cache() -> str:
    """Return the directory JAX caches compiled programs in.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
    sets nothing.  Otherwise the cache goes to ``<repo>/.jax_cache``: a
    fixed path, because the path is part of what makes an entry found
    again."""
    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
