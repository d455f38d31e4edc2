"""Shared pieces of the execution models: spike detection, synaptic fan-out,
batched state initialisation and device-side network arrays."""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import events as ev
from repro.core.cell import CellModel
from repro.core.network import Network

SPIKE_THR = -20.0     # mV upward crossing at the soma


class DeviceNet(NamedTuple):
    pre: jnp.ndarray
    post: jnp.ndarray
    delay: jnp.ndarray
    w_ampa: jnp.ndarray
    w_gaba: jnp.ndarray


def to_device(net: Network) -> DeviceNet:
    return DeviceNet(jnp.asarray(net.pre), jnp.asarray(net.post),
                     jnp.asarray(net.delay), jnp.asarray(net.w_ampa),
                     jnp.asarray(net.w_gaba))


def batch_init(model: CellModel, n: int, v0: float = -65.0):
    y = model.init_state(v0)
    return jnp.tile(y[None, :], (n, 1))


def detect_spikes(v_prev, v_new, t_prev, t_new):
    """Upward threshold crossing; spike time by linear interpolation.

    All inputs broadcastable over neurons. Returns (spiked bool[N], t_spike[N]).
    """
    crossed = jnp.logical_and(v_prev <= SPIKE_THR, v_new > SPIKE_THR)
    frac = (SPIKE_THR - v_prev) / jnp.where(v_new == v_prev, 1.0, v_new - v_prev)
    t_spike = t_prev + frac * (t_new - t_prev)
    return crossed, jnp.where(crossed, t_spike, 0.0)


def fanout_edges(dnet: DeviceNet, spiked, t_spike):
    """Edge-parallel synaptic fan-out of one spike per neuron.

    Returns candidate events (target, t_ev, w_ampa, w_gaba, valid), length E.
    """
    valid = spiked[dnet.pre]
    t_ev = t_spike[dnet.pre] + dnet.delay
    return dnet.post, t_ev, dnet.w_ampa, dnet.w_gaba, valid


fanout = fanout_edges     # historical name (shadowed by the knob in factories)


def horizon_times(dnet: DeviceNet, n: int, t_clock, t_end, *,
                  t_table=None, horizon_cap=None):
    """FAP dependency horizon: t_max[i] = min over in-edges (t[pre]+delay).

    This is the SPMD realisation of the paper's stepping-notification map
    (DESIGN.md §3): a scatter-min over the static edge list.
    Neurons without in-edges get t_end.

    The same helper serves the shard-local SPMD round (the notify -> horizon
    stage decomposition of ``distributed/fap_spmd``), which passes a
    ``dnet`` holding the shard's local edge slice with the *shard-relative*
    post index in ``dnet.post``:
      t_table: optional clock table indexed by ``dnet.pre`` when pre ids are
               global but ``t_clock`` is shard-local (the transport's notify
               output); defaults to ``t_clock`` itself,
      horizon_cap: optional per-round advancement bound (ms) folded in here
               so every execution model clamps identically.
    """
    tt = t_clock if t_table is None else t_table
    cand = tt[dnet.pre] + dnet.delay
    hor = jnp.full((n,), t_end, t_clock.dtype).at[dnet.post].min(cand)
    hor = jnp.minimum(hor, t_end)
    if horizon_cap is not None:
        hor = jnp.minimum(hor, t_clock + horizon_cap)
    return hor


def runnable_mask(t_clock, horizon, eps: float = 1e-12):
    """A neuron is runnable when strictly behind its dependency horizon."""
    return t_clock < horizon - eps


# ---------------------------------------------------------------------------
# active-set compaction: step only the runnable frontier
# ---------------------------------------------------------------------------
# The names of the scheduler round's stages, each a ``jax.named_scope`` on
# the work it does (``exec_fap._round``'s compact path, ``bdf._step_impl``).
# They reach the compiled module's ``op_name`` metadata, where the
# benchmark (``bench/stages.py``) reads device time per stage by
# these names: a rename in the program is a rename here.
ROUND_STAGES = (
    "fap.horizon",          # dependency horizon + runnable mask (+ k caps)
    "fap.compact",          # runnable mask -> [batch_cap] gather ids
    "fap.gather",           # lanes and queue rows into the batch
    "fap.advance",          # the vmapped variable-step BDF advance
    "fap.scatter",          # lanes and queue rows back, spike record
    "fap.fanout",           # spike fan-out + queue insert
    "fap.horizon_update",   # moved clocks pushed to their in-edge slots
    "bdf.setup",            # Newton setup (assemble + factor) gate
    "bdf.newton",           # modified Newton: rhs + solve iterations
)


class SchedStats(NamedTuple):
    """Per-run active-set telemetry accumulated over scheduler rounds.

    ``runnable`` sums the runnable-frontier size offered to the stepper
    each round; ``stepped`` the lanes that actually advanced; ``lanes``
    the lanes *dispatched* (vmap width: N on the dense path, ``batch_cap``
    per compact dispatch).  ``stepped / lanes`` is the realized batch
    occupancy and ``1 - stepped / lanes`` the wasted-lane fraction — the
    dense-masking overhead the compact path removes.

    ``horizon_live`` sums what the incremental horizon touches: the batch's
    lanes (their cap terms) and the real out-edge slots of moved lanes,
    which the round's push writes, of the ``batch_cap * (1 +
    max_out_degree)`` it could; over ``rounds`` times that width it is the
    stage's useful share (0 where the horizon is recomputed whole).
    ``fanout_rounds`` counts the compact FAP rounds whose spike fan-out +
    insert ran (those with a spike: the ``lax.cond`` guard); over
    ``rounds`` it is the insert's duty share.  It stays 0 on the dense FAP
    and BSP paths, which insert every round.  ``sched_metrics`` reports
    both per round.
    """
    runnable: jnp.ndarray      # i64[] sum over rounds of runnable lanes
    stepped: jnp.ndarray       # i64[] sum of lanes that actually advanced
    lanes: jnp.ndarray         # i64[] sum of lanes dispatched to the stepper
    rounds: jnp.ndarray        # i32[] scheduler rounds (compact: dispatches)
    horizon_live: jnp.ndarray  # i64[] live incremental horizon columns
    fanout_rounds: jnp.ndarray  # i32[] compact rounds that ran the insert

    @staticmethod
    def zeros() -> "SchedStats":
        z64, z32 = jnp.zeros((), jnp.int64), jnp.zeros((), jnp.int32)
        return SchedStats(z64, z64, z64, z32, z64, z32)


def sched_metrics(stats: SchedStats) -> dict:
    """Host-side summary of ``SchedStats`` (floats, safe for zero rounds)."""
    lanes = max(1, int(stats.lanes))
    rounds = max(1, int(stats.rounds))
    return {
        "rounds": int(stats.rounds),
        "runnable_per_round": float(stats.runnable) / rounds,
        "occupancy": float(stats.stepped) / lanes,
        "wasted_lane_frac": 1.0 - float(stats.stepped) / lanes,
        "horizon_live_per_round": float(stats.horizon_live) / rounds,
        "fanout_round_frac": float(stats.fanout_rounds) / rounds,
    }


def select_active(runnable, t_clock, cap: int, n_iters: int = 48):
    """Earliest-``cap`` restriction of the runnable frontier, sort-free.

    When more than ``cap`` neurons are runnable, keep those with the
    smallest clocks (``select_threshold`` bisection on counts — the same
    machinery as the explicit-scheduler ``k_select``); with ``cap`` or
    fewer runnable the mask is returned unchanged (the threshold lands on
    the maximum finite score).  The globally earliest runnable neuron is
    always kept, so the conservative-lookahead progress argument of
    ``exec_fap`` holds under any cap; clock ties beyond ``cap`` are
    resolved by the compaction's index order and simply roll to a later
    dispatch.
    """
    from repro.kernels.event_wheel import ops as ew_ops
    score = jnp.where(runnable, t_clock, jnp.inf)
    tau = ew_ops.select_threshold(score, cap, n_iters=n_iters)
    return jnp.logical_and(runnable, score <= tau)


def out_tables(net):
    """Host-side by-pre grouping of the static edge list: per neuron i the
    postsynaptic targets (sentinel N) and edge-list positions (sentinel E)
    of its out-edges, padded to the max out-degree.  Builders that need
    both tables (SPMD incremental horizon + compact fan-out) call it once —
    the O(E log E) grouping is the expensive part, not the two views."""
    pre = np.asarray(net.pre)
    post = np.asarray(net.post)
    n, E = int(net.n), int(pre.shape[0])
    deg = np.bincount(pre, minlength=n)
    mo = int(deg.max()) if E else 1
    order = np.argsort(pre, kind="stable")
    starts = np.zeros(n + 1, np.int64)
    starts[1:] = np.cumsum(deg)
    rank_in_pre = np.arange(E) - starts[pre[order]]
    post_t = np.full((n, mo), n, np.int32)
    post_t[pre[order], rank_in_pre] = post[order]
    edge_t = np.full((n, mo), E, np.int32)
    edge_t[pre[order], rank_in_pre] = order
    return post_t, edge_t


def in_edge_slots(edge_t, n: int, k: int) -> np.ndarray:
    """Host-side map of ``out_tables``' edge table onto the [K, N] by-post
    layout of a grouped net (``ops.by_post_layout``): edge ``e = j * K +
    k`` sits at flat slot ``k * N + j``; the padding E stays E (= K * N).
    The incremental horizon pushes a moved clock to these slots."""
    e = np.asarray(edge_t, np.int64)
    E = n * k
    return np.where(e < E, (e % k) * n + e // k, E).astype(np.int32)


def out_edge_table(net) -> np.ndarray:
    """Host-side static out-*edge* table: row i lists the edge-list
    positions of neuron i's out-edges, padded with the sentinel E.

    The compact fan-out path (``fanout="compact"``) gathers these rows for
    the <= spike_cap spiking lanes and inserts only that
    [spike_cap, max_out_degree] edge batch — O(spikes * k_out) per
    spiking round instead of the O(E) full fan-out.
    """
    return out_tables(net)[1]


def make_spike_insert(net, dnet: DeviceNet, qops, qinsert,
                      fanout: str = "dense", spike_cap: int = 256,
                      edge_table=None):
    """The fan-out + insert stage of every execution model, behind the
    ``fanout="dense"|"compact"`` knob.  Returns ``fn(eq, spiked[N],
    t_spike[N]) -> eq`` (at most one spike per neuron per call, the
    invariant all runners already hold).

    ``dense``   — the reference path: edge-parallel ``fanout`` over all E
                  edges + the net's best insert (``sched.edge_insert``).
    ``compact`` — activity-proportional delivery: when at most
                  ``spike_cap`` lanes spiked, compact the mask and gather
                  only those lanes' out-edges (``out_edge_table`` rows via
                  ``ops.compact_gather``), then insert the fixed
                  [spike_cap * k_out] batch through the queue's flat
                  batch insert.  More spikes than ``spike_cap`` fall back
                  to the dense branch under ``lax.cond`` — identical
                  event set either way (overflow *falls back*, never
                  drops).  Spike-free rounds insert nothing on either
                  branch, so callers may still guard with their own cond.
    """
    if fanout not in ("dense", "compact"):
        raise ValueError(f"unknown fanout mode {fanout!r}")

    def dense_ins(eq, spiked, t_sp):
        tgt, t_ev, wa, wg, valid = fanout_edges(dnet, spiked, t_sp)
        return qinsert(eq, tgt, t_ev, wa, wg, valid)

    if fanout == "dense":
        return dense_ins

    from repro.kernels.event_wheel import ops as ew_ops
    n, E = int(net.n), int(dnet.pre.shape[0])
    cap = min(int(spike_cap), n) if spike_cap > 0 else min(n, 256)
    # [N, MO], sentinel E (edge_table lets builders that also need the
    # out-post table share one out_tables() grouping pass)
    edge_tbl = jnp.asarray(out_edge_table(net) if edge_table is None
                           else edge_table)

    def compact_ins(eq, spiked, t_sp):
        ids, eids, _ = ew_ops.compact_gather(spiked, edge_tbl, cap, fill=E)
        idc = jnp.minimum(ids, n - 1)
        ok = jnp.logical_and((ids < n)[:, None], eids < E)  # [cap, MO]
        eidc = jnp.minimum(eids, E - 1)
        tgt = dnet.post[eidc]
        t_ev = t_sp[idc][:, None] + dnet.delay[eidc]
        return qops.insert_batch(eq, tgt.ravel(), t_ev.ravel(),
                                 dnet.w_ampa[eidc].ravel(),
                                 dnet.w_gaba[eidc].ravel(), ok.ravel())

    def ins(eq, spiked, t_sp):
        return jax.lax.cond(spiked.sum() <= cap, compact_ins, dense_ins,
                            eq, spiked, t_sp)

    return ins


def auto_batch_cap(stats: SchedStats, n: int, *, slack: float = 2.0,
                   floor: int = 32) -> int:
    """Pick a ``batch_cap`` from measured frontier occupancy
    (``RunResult.sched`` telemetry of a probe run): the mean per-round
    runnable frontier times ``slack`` headroom, rounded up to a power of
    two, clipped to [floor, n].  Zero-round telemetry returns ``floor``.
    """
    rounds = max(1, int(stats.rounds))
    mean_frontier = float(stats.runnable) / rounds
    want = max(float(floor), slack * mean_frontier)
    return min(n, 1 << max(0, int(np.ceil(np.log2(want)))))


def solver_stats(sts) -> dict:
    """Summed per-lane BDF counters for ``RunResult.solver`` (jit-safe:
    a dict of scalar arrays).  ``nsetups / nni`` is the Jacobian-reuse
    ratio of the freshness policy (1.0 on the legacy
    ``jac_policy="iteration"`` path, well under 0.5 under reuse)."""
    return {"nst": sts.nst.sum(), "nni": sts.nni.sum(),
            "nfe": sts.nfe.sum(), "nsetups": sts.nsetups.sum(),
            "netf": sts.netf.sum(), "nncf": sts.nncf.sum(),
            "nreset": sts.nreset.sum()}


def auto_spike_cap(rec, stats: SchedStats, n: int, *, slack: float = 4.0,
                   floor: int = 16) -> int:
    """Pick a ``spike_cap`` from measured spike-rate telemetry (the
    ``RunResult.rec`` / ``.sched`` of a probe run), mirroring
    ``auto_batch_cap``: mean spikes per scheduler round times ``slack``
    headroom, rounded up to a power of two, clipped to [floor, n].

    Spiking is burstier than the runnable frontier, hence the larger
    default slack — and undershooting is safe either way: a round with
    more than ``spike_cap`` spikes falls back to the dense fan-out branch
    (identical events, never a drop), it just stops being compact.
    """
    rounds = max(1, int(stats.rounds))
    mean_spikes = float(np.asarray(rec.count).sum()) / rounds
    want = max(float(floor), slack * mean_spikes)
    return min(n, 1 << max(0, int(np.ceil(np.log2(want)))))


def compact_frontier(runnable, t_clock, cap: int, n_iters: int = 48):
    """Select + compact the runnable frontier into a [cap] gather-id batch.

    Returns (ids i32[cap] — unique lane ids, sentinel N for empty slots;
    count i32 — selected lanes, may exceed cap when the frontier
    overflows: the overflow rolls to a later dispatch).  When the cap
    binds, the earliest-clock lanes are kept (``select_active``) and the
    globally earliest runnable lane is *force-included*: bisection-
    resolution clock ties can otherwise crowd the frontier head out of
    the index-ordered compaction, which would starve the one neuron the
    conservative-lookahead progress argument depends on.
    """
    from repro.kernels.event_wheel import ops as ew_ops
    n = t_clock.shape[0]
    sel = select_active(runnable, t_clock, cap, n_iters) if cap < n \
        else runnable
    ids, cnt = ew_ops.compact_ids(sel, cap)
    if cap < n:
        score = jnp.where(runnable, t_clock, jnp.inf)
        earliest = jnp.argmin(score).astype(ids.dtype)
        have = jnp.logical_or((ids == earliest).any(), ~runnable.any())
        last = jnp.maximum(jnp.minimum(cnt, cap) - 1, 0)
        ids = jnp.where(have, ids, ids.at[last].set(earliest))
    return ids, cnt


def gather_lanes(sts, ids_clipped):
    """Gather the per-neuron pytree rows of a compacted id list."""
    return jax.tree_util.tree_map(lambda x: x[ids_clipped], sts)


def unique_pad_ids(ids, n: int):
    """Remap sentinel padding (>= n) to distinct out-of-range ids so the
    scatters can claim ``unique_indices`` — without it XLA's duplicate-safe
    sequential scatter path dominates the compact round's cost."""
    pad = n + jnp.arange(ids.shape[0], dtype=ids.dtype)
    return jnp.where(ids < n, ids, pad)


def scatter_at(full, ids, vals):
    """``full.at[ids].set(vals)`` for a compacted id list: sentinel padding
    (>= N) is dropped and the write claims ``unique_indices`` via
    ``unique_pad_ids`` — the batch -> full-width store every compact path
    shares (single arrays here, pytrees via ``scatter_lanes``)."""
    ids_u = unique_pad_ids(ids, full.shape[0])
    return full.at[ids_u].set(vals, mode="drop", unique_indices=True)


def scatter_lanes(full, batch, ids):
    """Scatter advanced lanes back; sentinel ids (>= N) are dropped.

    ``ids`` must hold unique in-range entries (the compaction guarantees
    it) — the write is issued with ``unique_indices=True``.
    """
    n = jax.tree_util.tree_leaves(full)[0].shape[0]
    ids_u = unique_pad_ids(ids, n)
    return jax.tree_util.tree_map(
        lambda f, b: f.at[ids_u].set(b, mode="drop", unique_indices=True),
        full, batch)


def spike_rates(rec: ev.SpikeRecord, t_lo: float, t_hi: float):
    """Per-neuron firing rate (Hz) in a window; times in ms."""
    m = jnp.logical_and(rec.times >= t_lo, rec.times < t_hi)
    return m.sum(axis=1) / ((t_hi - t_lo) * 1e-3)


# ---------------------------------------------------------------------------
# preemption tolerance: round-boundary checkpoint/restore, fault injection
# and the detected-never-silent health watchdog shared by every vardt
# driver (single-host exec_fap/exec_bsp and the SPMD run_fap_spmd loop)
# ---------------------------------------------------------------------------
class SimCarry(NamedTuple):
    """The full round-boundary state of a vardt run — everything the next
    scheduler round reads, as ONE pytree so ``repro.checkpoint`` can save
    and restore it leaf-for-leaf.  Resume from a ``SimCarry`` snapshot is
    event-for-event identical to the uninterrupted run: same BDF history
    (``sts`` is the batched ``bdf.BDFState`` including the Jacobian-cache
    fields ``gamma_saved``/``nstlp``/``factors``), same pending events
    (``eq`` is the queue pytree — dense ``EventQueue``, ``WheelQueue`` or
    the SPMD round's raw ``(t, w_ampa, w_gaba)`` planes), same spike-record
    cursor (``rec``) and the same solver/sched/comm counters.

    ``hcarry`` holds the incremental-horizon carry where a runner
    maintains one (``exec_fap``: the in-edge clock table; the SPMD round:
    horizon, previous boundary clocks, moved ids) — the only layout- and
    mesh-shape-*dependent* leaves.  On elastic resume onto a different
    mesh shape ``restore_sim_checkpoint`` skips exactly these and the
    caller reseeds them from the restored clocks (a full recompute, which
    the incremental scheme equals bitwise because min is exact).
    """
    sts: object        # batched bdf.BDFState pytree [N, ...]
    eq: object         # queue pytree (EventQueue / WheelQueue / (t, wa, wg))
    rec: object        # ev.SpikeRecord (times + per-neuron cursor + overflow)
    hcarry: tuple      # incremental-horizon carry (possibly empty)
    counters: object   # dict of scalar telemetry (n_ev/n_rs/rounds/stats/...)


def empty_health(watchdog: bool = True) -> dict:
    """The ``RunResult.health`` record every checkpointed driver fills.

    Degradations follow the repo-wide detected-never-silent contract:
    every rollback, regression, violation and drop is counted here, and
    ``rollback_exhausted`` escalates to ``RunResult.failed``.
    """
    return {
        "watchdog": bool(watchdog),
        "checks": 0,                 # rounds the watchdog inspected
        "nonfinite_rounds": 0,       # rounds with a non-finite lane detected
        "clock_regressions": 0,      # lanes whose clock moved backwards
        "horizon_violations": 0,     # carried horizon past clock + cap
        "rollbacks": 0,              # quarantine-and-rollback events
        "rollback_exhausted": False,  # bounded retries spent -> failed
        "checkpoints_saved": 0,
        "resumed_from": None,        # round the run resumed at (or None)
        "elastic_reseeded": False,   # hcarry reseeded on mesh-shape change
        "dropped_events": 0,         # queue + parcel overflow (escalated)
        "straggler": None,           # StragglerMonitor.stats()
    }


def health_check(sts, t_prev, horizon=None, horizon_cap=None,
                 eps: float = 1e-9) -> dict:
    """Cheap per-round finiteness/invariant check (jit-safe scalars).

    * a lane is non-finite when any of its BDF history ``zn``, clock ``t``
      or step size ``h`` stopped being finite — the poisoned state that
      would otherwise propagate through parcels to the whole network,
    * clocks must be monotone: ``t`` never moves behind the previous
      round's clock (FAP lanes only ever advance),
    * a carried dependency horizon can never exceed its lane's clock by
      more than ``horizon_cap`` (the per-round advancement clamp) — drift
      here means the incremental maintenance went stale.
    """
    lane_bad = jnp.logical_or(
        ~jnp.isfinite(sts.zn).all(axis=tuple(range(1, sts.zn.ndim))),
        jnp.logical_or(~jnp.isfinite(sts.t), ~jnp.isfinite(sts.h)))
    out = {
        "nonfinite_lanes": lane_bad.sum(dtype=jnp.int32),
        "clock_regress": (sts.t < t_prev - eps).sum(dtype=jnp.int32),
    }
    if horizon is not None and horizon_cap is not None:
        out["horizon_violations"] = \
            (horizon > sts.t + horizon_cap + eps).sum(dtype=jnp.int32)
    return out


def health_check_tenants(sts, t_prev, eps: float = 1e-9) -> dict:
    """Per-tenant verdicts of the same invariants as ``health_check`` over
    a vmapped tenant axis (``repro.serve``): every ``sts`` leaf carries a
    leading [T] lane axis ([T, N, ...]), and each tenant's verdict reduces
    only over its OWN neurons — one poisoned tenant never taints a
    neighbour's verdict.  Returns arrays [T]:

      nonfinite_lanes  i32[T]  neurons whose zn/t/h went non-finite
      clock_regress    i32[T]  neurons whose clock moved backwards
      solver_failed    bool[T] BDF gave up (latched ``failed``) on a
                               *finite* state — deterministic, so the
                               service evicts rather than retries
    """
    zn_ok = jnp.isfinite(sts.zn).all(axis=tuple(range(2, sts.zn.ndim)))
    lane_bad = jnp.logical_or(
        ~zn_ok, jnp.logical_or(~jnp.isfinite(sts.t), ~jnp.isfinite(sts.h)))
    return {
        "nonfinite_lanes": lane_bad.sum(axis=1, dtype=jnp.int32),
        "clock_regress": (sts.t < t_prev - eps).sum(axis=1, dtype=jnp.int32),
        "solver_failed": sts.failed.any(axis=1),
    }


def poison_lane(carry: SimCarry, lane: int, value=jnp.nan) -> SimCarry:
    """Fault injection: overwrite one lane's BDF history with ``value``
    (non-finite by default) — the failure mode the watchdog must catch."""
    sts = carry.sts
    return carry._replace(sts=sts._replace(zn=sts.zn.at[lane].set(value)))


def save_sim_checkpoint(ckpt_dir: str, rnd: int, carry: SimCarry,
                        extras: dict = None, keep: int = 3) -> str:
    """Atomic round-boundary snapshot (``repro.checkpoint`` commit
    protocol) + pruning.  ``rnd`` is the scheduler-round counter."""
    from repro.checkpoint import checkpoint as ck
    path = ck.save_checkpoint(ckpt_dir, rnd, carry, extras=extras)
    ck.prune_checkpoints(ckpt_dir, keep=keep)
    return path


def restore_sim_checkpoint(ckpt_dir: str, rnd: int, like: SimCarry,
                           shardings=None):
    """Restore a ``SimCarry`` snapshot into the structure of ``like``.

    Returns (carry, extras, skipped) where ``skipped`` lists the tree
    paths whose *stored* leaf shape no longer matches ``like`` — those
    leaves keep ``like``'s value.  Shape drift is only legitimate for the
    mesh-shape-dependent ``hcarry`` leaves (elastic resume onto a
    different mesh); callers must reject any other skip and reseed the
    horizon carry from the restored clocks.  Every restored leaf is
    integrity-checked (nbytes + crc32) and the stored treedef must match
    ``like``'s exactly.  ``shardings``: optional matching pytree of
    shardings — restored leaves are ``device_put`` with theirs (the
    ``restore_checkpoint(shardings=)`` elastic path).
    """
    from repro.checkpoint import checkpoint as ck

    path = ck.step_path(ckpt_dir, rnd)
    manifest = ck.read_manifest(path)
    leaves, treedef = jax.tree_util.tree_flatten(like)
    if manifest["n_leaves"] != len(leaves):
        raise ValueError(f"checkpoint has {manifest['n_leaves']} leaves, "
                         f"expected {len(leaves)}")
    stored_td = manifest.get("treedef")
    if stored_td is not None and stored_td != str(treedef):
        raise ValueError(
            "checkpoint pytree structure does not match the restore target:"
            f"\n  stored:   {stored_td}\n  expected: {treedef}")
    paths = [jax.tree_util.keystr(kp) for kp, _ in
             jax.tree_util.tree_flatten_with_path(like)[0]]
    sh_leaves = treedef.flatten_up_to(shardings) if shardings is not None \
        else [None] * len(leaves)
    out, skipped = [], []
    for entry, leaf, pstr, sh in zip(manifest["entries"], leaves, paths,
                                     sh_leaves):
        arr = ck.load_leaf(path, entry)
        if tuple(arr.shape) != tuple(leaf.shape):
            skipped.append(pstr)
            out.append(leaf)
            continue
        a = arr.astype(leaf.dtype)
        out.append(jax.device_put(a, sh) if sh is not None
                   else jnp.asarray(a))
    return treedef.unflatten(out), manifest["extras"], skipped


def run_checkpointed(init_fn, step_fn, cond_fn, *, ckpt_dir=None,
                     checkpoint_every: int = 0, resume: bool = False,
                     keep: int = 3, fault=None, health_of=None,
                     max_rollbacks: int = 2, shardings=None, reseed=None,
                     fingerprint=None, extras_fn=None, log_fn=None,
                     straggler=None):
    """Host-stepped scheduler-round loop with round-boundary
    checkpoint/restore, fault injection and the health watchdog — the
    preemption-tolerance harness every vardt driver shares.

      init_fn() -> SimCarry                 fresh round-0 state
      step_fn(SimCarry) -> SimCarry         one jitted scheduler round
                                            (must bump counters["rounds"])
      cond_fn(SimCarry) -> bool             host-side continue predicate
      health_of(SimCarry, t_prev) -> dict   per-round watchdog scalars
                                            (``health_check``; None = off)
      fault: ``checkpoint.FaultPlan``       round-boundary injection
      reseed(SimCarry) -> SimCarry          re-derive skipped (elastic)
                                            ``hcarry`` leaves on restore
      fingerprint: JSON-able layout id      saved in the manifest extras;
                                            a restore whose stored value
                                            differs forces ``reseed`` even
                                            when leaf shapes coincide (a
                                            mesh-shape change can keep the
                                            hcarry widths while scrambling
                                            their shard-relative contents)

    Non-finite state detected by the watchdog quarantines the round:
    restore the last checkpoint (round 0 via ``init_fn`` when none exists
    yet) and retry, at most ``max_rollbacks`` times — then escalate
    ``rollback_exhausted`` (the caller folds it into ``RunResult.failed``).
    Detected, never silent: every event lands in the returned health dict.

    Returns (final SimCarry, health dict).
    """
    import time as _time

    from repro.checkpoint import checkpoint as ck
    from repro.checkpoint.fault_tolerance import (SimulatedFailure,
                                                  StragglerMonitor)

    if (resume or checkpoint_every) and not ckpt_dir:
        raise ValueError("checkpoint_every/resume need ckpt_dir=")
    log = log_fn or (lambda *_: None)
    # straggler: a caller-configured StragglerMonitor (window / regression
    # threshold knobs); default keeps the historical 32-round window
    monitor = straggler if straggler is not None else StragglerMonitor()
    health = empty_health(watchdog=health_of is not None)

    def _restore(rnd, like):
        carry, extras, skipped = restore_sim_checkpoint(
            ckpt_dir, rnd, like, shardings=shardings)
        stale = extras.get("fingerprint") != fingerprint \
            if fingerprint is not None else False
        if skipped or stale:
            bad = [p for p in skipped if "hcarry" not in p]
            if bad or reseed is None:
                raise ValueError(f"checkpoint leaf shapes changed outside "
                                 f"the horizon carry: {skipped}")
            carry = reseed(carry)
            health["elastic_reseeded"] = True
        return carry

    carry = init_fn()
    if resume:
        last = ck.latest_step(ckpt_dir)
        if last is not None:
            carry = _restore(last, carry)
            health["resumed_from"] = last
            log(f"[sim-ft] resumed from round {last}")
    rollbacks = 0
    poison_pending = fault is not None and fault.poison_at_round is not None
    fail_pending = fault is not None and fault.fail_at_round is not None
    while bool(cond_fn(carry)):
        rnd = int(carry.counters["rounds"])
        if fail_pending and rnd >= fault.fail_at_round:
            raise SimulatedFailure(rnd)
        if poison_pending and rnd >= fault.poison_at_round:
            poison_pending = False
            carry = poison_lane(carry, fault.poison_lane, fault.poison_value)
            log(f"[sim-ft] poisoned lane {fault.poison_lane} at round {rnd}")
        if fault is not None and fault.mutate is not None:
            carry = fault.mutate(rnd, carry)
        t_prev = carry.sts.t
        t0 = _time.time()
        new_carry = step_fn(carry)
        if health_of is not None:
            chk = {k: int(v) for k, v in health_of(new_carry, t_prev).items()}
            health["checks"] += 1
            health["clock_regressions"] += chk.get("clock_regress", 0)
            health["horizon_violations"] += chk.get("horizon_violations", 0)
            if chk.get("nonfinite_lanes", 0):
                health["nonfinite_rounds"] += 1
                rollbacks += 1
                if rollbacks > max_rollbacks:
                    health["rollback_exhausted"] = True
                    log(f"[sim-ft] non-finite state at round {rnd}: "
                        f"retries exhausted")
                    carry = new_carry
                    break
                health["rollbacks"] += 1
                last = ck.latest_step(ckpt_dir) if ckpt_dir else None
                log(f"[sim-ft] non-finite state at round {rnd}; rolling "
                    f"back to {'round ' + str(last) if last is not None else 'init'}")
                carry = init_fn() if last is None else _restore(last, carry)
                continue
        monitor.record(_time.time() - t0)
        carry = new_carry
        r2 = int(carry.counters["rounds"])
        if checkpoint_every and r2 % checkpoint_every == 0:
            ex = dict(extras_fn()) if extras_fn else {}
            if fingerprint is not None:
                ex["fingerprint"] = fingerprint
            save_sim_checkpoint(ckpt_dir, r2, carry, extras=ex or None,
                                keep=keep)
            health["checkpoints_saved"] += 1
    health["straggler"] = monitor.stats()
    return carry, health
