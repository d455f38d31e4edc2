"""Queue-implementation dispatch: the ``queue="dense"|"wheel"`` knob.

Every execution model (exec_bsp, exec_fap, exec_speculative, the SPMD
round) builds a ``QueueOps`` at trace time and goes through it for all
queue traffic, so the dense argsort queue and the bucketed event wheel
stay drop-in interchangeable and separately testable.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend import core as jex_core

from repro.core import events as ev
from repro.sched.wheel import WheelQueue, WheelSpec
from repro.sched import wheel as wh


class QueueOps(NamedTuple):
    name: str
    capacity: int
    make: Callable            # (n,) -> queue
    insert: Callable          # (eq, target[E], t[E], wa[E], wg[E], valid[E]) -> eq
    insert_grouped: Callable  # (eq, t[N,k], wa[N,k], wg[N,k], valid[N,k]) -> eq
    insert_batch: Callable    # insert for a small batch: flat in N (the
    #                           compact fan-out's per-spike edge batches)
    next_time: Callable       # (eq,) -> f64[N]
    deliver_until: Callable   # (eq, t_dl[N]) -> (eq, wa[N], wg[N], cnt[N])
    wrap: Callable            # (t, wa, wg, dropped) -> queue


def _dense_insert_grouped(eq, t_ev, w_ampa, w_gaba, valid):
    n, k = t_ev.shape
    tgt = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    return ev.insert(eq, tgt, t_ev.reshape(-1), w_ampa.reshape(-1),
                     w_gaba.reshape(-1), valid.reshape(-1))


def get_queue_ops(queue: str = "dense", *, ev_cap: int = 64,
                  wheel: WheelSpec = WheelSpec()) -> QueueOps:
    if queue == "dense":
        return QueueOps(
            name="dense", capacity=ev_cap,
            make=lambda n: ev.make_queue(n, ev_cap),
            insert=ev.insert,
            insert_grouped=_dense_insert_grouped,
            insert_batch=ev.insert_rows,
            next_time=ev.next_time,
            deliver_until=ev.deliver_until,
            wrap=ev.EventQueue,
        )
    if queue == "wheel":
        # the wheel's generic insert doubles as the batch insert: no slot
        # argsort anywhere, and on TPU the pairwise rank kernel is N-free.
        # The batch insert ranks in the dense [E] batch domain, so the
        # off-TPU scatter-min ranking allocates an O(E) key table for the
        # cap-bounded edge batches instead of the O(N*B) global table
        # (the full-E generic insert keeps the global domain: there
        # E ~ N*k and the remap's pairwise [E, E] compare would dominate).
        return QueueOps(
            name="wheel", capacity=wheel.capacity,
            make=lambda n: wh.make_wheel(n, wheel),
            insert=functools.partial(wh.insert, wheel),
            insert_grouped=functools.partial(wh.insert_grouped, wheel),
            insert_batch=functools.partial(wh.insert, wheel,
                                           rank_domain="batch"),
            next_time=wh.next_time,
            deliver_until=wh.deliver_until,
            wrap=WheelQueue,
        )
    raise ValueError(f"unknown queue implementation {queue!r}")


def gather_rows(eq, ids):
    """Per-neuron queue rows of a compacted id list (the active-set gather
    of the ``batch="compact"`` execution path).

    Both queue implementations share the [N, cap] flat slot layout
    (``WheelQueue`` docstring), so one gather serves either; ``ids`` must
    be pre-clipped to [0, N).  Returns (t, w_ampa, w_gaba) rows — the
    weights are read-only in the advance, only ``t`` is scattered back.
    """
    return eq.t[ids], eq.w_ampa[ids], eq.w_gaba[ids]


def scatter_rows(eq, ids, t_rows):
    """Write advanced delivery-time rows back; sentinel ids (>= N) drop.

    The vardt advance consumes events by overwriting their times with
    +inf and never touches the weight planes, so the scatter is a single
    [cap, Q] row write — valid for dense queue and wheel alike.  ``ids``
    must be unique in-range lanes (the compaction guarantees it): padding
    is remapped to distinct out-of-range ids so the write can claim
    ``unique_indices`` and skip XLA's duplicate-safe sequential scatter.
    """
    from repro.core import exec_common as xc
    return eq._replace(t=xc.scatter_at(eq.t, ids, t_rows))


def grouped_k(net):
    """Host-side check of ``make_network``'s static edge layout: edges
    grouped by postsynaptic neuron with uniform in-degree.  Returns the
    in-degree k when the layout holds, else None."""
    post = np.asarray(net.post)
    E, n = post.shape[0], int(net.n)
    if E % n == 0 and np.array_equal(
            post, np.repeat(np.arange(n, dtype=post.dtype), E // n)):
        return E // n
    return None


def edge_insert(qops: QueueOps, net) -> Callable:
    """Best insert path for a network's static edge list: when the grouped
    layout holds (``grouped_k``), fan-out events go through the grouped
    fast path (for the wheel: no scatter-min ranking, no sort of any
    kind); otherwise the generic insert."""
    k = grouped_k(net)
    if k is None:
        return qops.insert
    n = int(net.n)

    def ins(eq, target, t_ev, w_ampa, w_gaba, valid):
        return qops.insert_grouped(eq, t_ev.reshape(n, k),
                                   w_ampa.reshape(n, k),
                                   w_gaba.reshape(n, k),
                                   valid.reshape(n, k))

    return ins


def jaxpr_primitives(fn, *args, **kwargs) -> set:
    """All primitive names in fn's jaxpr, recursing into sub-jaxprs —
    used to certify the wheel insert path carries no ``sort``."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    prims: set = set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            prims.add(eqn.primitive.name)
            for v in eqn.params.values():
                for sub in _subjaxprs(v):
                    walk(sub)

    def _subjaxprs(v):
        if isinstance(v, jex_core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jex_core.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for x in v:
                yield from _subjaxprs(x)

    walk(closed.jaxpr)
    return prims
