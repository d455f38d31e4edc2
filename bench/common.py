"""Helpers shared by the two kinds of cell: the device check, compile and
memory readings, and hoisting a traced function's constants into
arguments so that one executable serves every seed."""
from __future__ import annotations

import contextlib
import sys
import time

import jax
from jax import tree_util as tu


def require_tpu(count: int = 1):
    """The devices to run on; refuses anything but ``count`` TPUs or more,
    and never falls back to the CPU."""
    devs = jax.devices()
    msg = None
    if devs[0].platform != "tpu":
        msg = (f"bench: JAX finds no TPU (platform {devs[0].platform!r}); "
               "the benchmark runs only on the chip")
    elif len(devs) < count:
        msg = f"bench: {count} TPUs wanted, {len(devs)} found"
    if msg:
        print(msg, file=sys.stderr, flush=True)
        raise SystemExit(2)
    return devs[:count]


class CompileClock:
    """Backend compiles and their seconds inside a block (tracing and
    lowering not included); a program loaded from the persistent cache
    counts as a cache hit, not as a compile."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def as_dict(self):
        return {"backend_compile_s": self.seconds,
                "backend_compiles": self.compiles,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


def peak_bytes(devices=None):
    """Peak bytes in use on the fullest of ``devices``, or None where the
    backend keeps no such count."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in (devices or jax.devices()[:1])]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def hoist(fn, *example_args, donate: bool = False, jitted=None):
    """``fn`` jitted with every array it closes over (the network, the
    drive) passed as an argument rather than baked into the program as a
    constant: the program, and so the compile-cache key, is then the same
    for every seed.  Returns ``call(*args)``, which runs the one compiled
    executable; ``call.lower_args`` are the arguments to lower it with.
    ``donate`` hands the arguments' buffers to the outputs (a carry that
    is replaced by each call).  ``jitted``: the ``call.jitted`` of another
    seed's hoisting of the same program, to run that executable on this
    seed's arrays."""
    closed = jax.make_jaxpr(fn, return_shape=True)(*example_args)
    cj, out_shape = closed
    out_tree = tu.tree_structure(out_shape)
    consts = [jax.numpy.asarray(c) for c in cj.consts]
    n_in = len(tu.tree_leaves(example_args))

    def flat_fn(consts, *leaves):
        return jax.core.eval_jaxpr(cj.jaxpr, consts, *leaves)

    jfn = jitted or jax.jit(flat_fn,
                            donate_argnums=tuple(range(1, 1 + n_in))
                            if donate else ())

    def call(*args):
        leaves = tu.tree_leaves(args)
        return tu.tree_unflatten(out_tree, jfn(consts, *leaves))

    call.jitted = jfn
    call.lower_args = (consts, *tu.tree_leaves(example_args))
    return call


@contextlib.contextmanager
def span(name: str, trace_on: bool):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    if trace_on:
        with jax.profiler.TraceAnnotation(name):
            yield
    else:
        yield


def now() -> float:
    return time.perf_counter()
