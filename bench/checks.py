"""The comparison that decides ``correct``, and the checks of one run's
guarantees.

``compare_trains`` holds the spike trains that the timed path produced
against the plain reference's (``bench/reference.py``), which simulates
every neuron against the synaptic events that the program's own spikes
send: the network is chaotic, so two correct runs drift apart, but each
neuron's spikes, given its inputs, are its equations'.  Spikes of a neuron
pair in order.  Every spike of the program before ``t_cut`` is compared:
with the reference's spike of the same rank when the reference has one
before ``t_cut + margin``, else it is unpaired.  A reference spike that the
program lacks is unpaired when it lies before ``t_cut - margin``; within
``margin`` of the cut the program may simply not have reached it.  The
margin is 1 ms or 2 % of ``t_cut``, whichever is more: a tonically firing
neuron's spike-time error grows with its number of periods, so the margin
grows with the window.

The numbers compared, each against its limit in ``LIMITS`` (set on the
chip from the program's readings and the control's, see PERF.md):

- ``gap_p50_ms``: the median over paired spikes of |t_program -
  t_reference|: the typical spike-time error, which the solver's
  tolerance sets;
- ``gap_p99_ms``: the 99th percentile of the same gaps over the neurons
  driven at or above their threshold current: a fault in a minority of
  neurons (their drive, their state, their spikes) that the median does
  not see.  A neuron driven below threshold spikes only when its synaptic
  input carries it across, and a voltage error at the solver's tolerance
  then moves that spike by up to tenths of a millisecond (CPU, N = 512:
  the first such spikes, from 7 ms on, take the 99th percentile of all
  gaps from 0.004 to 0.18 ms), so its spikes count in the other numbers
  and not in this one;
- ``unpaired_share``: spikes of either side without a partner, over all
  compared: a spike the program never recorded (and whose events it
  never sent, so the event count agrees) is a reference spike alone;
- ``exact_faults``: dropped events, solver failures, spike-record
  overflow, non-finite state, calls that moved no clock, and events
  delivered other than the spike train implies, all counted together:
  limit 0.

Printed beside them and not compared: ``syn_err_max``, over every neuron
and both synaptic channels, how far the program's conductance at the
neuron's clock lies from the decayed weights of the events that the spike
train sends it by then, over that sum plus the channel's mean weight.  An
event lost, late, doubled, sent to another neuron or with another weight
moves it by a whole weight.  The solver integrates the conductances, so a
sound run reads about a hundredth on the chip, and a looser tolerance
(the control) only 2.2x that: no limit between the two is safe (PERF.md).
"""
from __future__ import annotations

import numpy as np

MARGIN_MS = 1.0
MARGIN_REL = 0.02

# Set from the chip readings in PERF.md section 2: above the largest
# reading of sound runs over a dozen seeds, below the control's smallest.
# No sound run has left a spike unpaired, so that limit is exact.
LIMITS = {"gap_p50_ms": 0.006, "gap_p99_ms": 0.013, "unpaired_share": 0.0,
          "exact_faults": 0}


def margin_ms(t_cut: float) -> float:
    return max(MARGIN_MS, MARGIN_REL * t_cut)


def compare_trains(p_count, p_times, r_count, r_times, t_cut: float,
                   tail=None) -> dict:
    """The compared numbers of two spike records; ``tail`` (bool [N], all
    when None) marks the neurons whose gaps ``gap_p99_ms`` takes."""
    margin = margin_ms(t_cut)
    p_count = np.asarray(p_count)
    r_count = np.asarray(r_count)
    p_times = np.asarray(p_times, np.float64)
    r_times = np.asarray(r_times, np.float64)
    width = max(p_times.shape[1], r_times.shape[1])
    pt = _pad(p_times, p_count, width)
    rt = _pad(r_times, r_count, width)
    p_in = pt < t_cut                       # program spikes compared
    r_ok = rt < t_cut + margin              # reference spikes available
    pair = p_in & r_ok
    gaps = np.abs(pt[pair] - rt[pair])
    if tail is None:
        tail = np.ones(p_count.shape[0], bool)
    in_tail = pair & np.asarray(tail)[:, None]
    tail_gaps = np.abs(pt[in_tail] - rt[in_tail])
    p_lone = p_in & ~r_ok
    r_lone = (rt < t_cut - margin) & ~p_in
    unpaired = int(p_lone.sum() + r_lone.sum())
    n = int(gaps.size) + unpaired
    return {"paired": int(gaps.size), "unpaired": unpaired,
            "gap_p99_ms": float(np.percentile(tail_gaps, 99))
            if tail_gaps.size else 0.0,
            "gap_p99_all_ms": float(np.percentile(gaps, 99)) if gaps.size
            else 0.0,
            "paired_tail": int(tail_gaps.size),
            "gap_max_ms": float(gaps.max()) if gaps.size else 0.0,
            "gap_p50_ms": float(np.median(gaps)) if gaps.size else 0.0,
            "unpaired_share": unpaired / n if n else 0.0,
            "spikes_ref": int((rt < t_cut).sum())}


DRIVE_WIDTH = 32


def padded(count, times, width: int = DRIVE_WIDTH):
    """A spike record as [N, width] times with +inf beyond each count (a
    fixed width, so the reference compiles once for every seed)."""
    count = np.asarray(count)
    return _pad(np.asarray(times, np.float64), count,
                max(width, int(count.max(initial=0))))


def _pad(times, count, width):
    """Recorded times with +inf beyond each neuron's count, ``width`` wide."""
    out = np.full((times.shape[0], width), np.inf)
    k = min(times.shape[1], width)
    times = times[:, :k]
    slot = np.arange(k)[None, :]
    out[:, :k] = np.where(slot < count[:, None], times, np.inf)
    return out


# An event that arrives at its target's clock may or may not have been
# delivered yet (the neuron steps to an event's time and delivers it on its
# next iteration, if its step budget has one left), and the device's f64
# (f32 pairs on a TPU) may place that time a few ulps from the host's sum.
AT_CLOCK_MS = 1e-9


def implied_events(count, times, pre, post, delay, clock) -> tuple:
    """(events that the spike train sends and that arrive before their
    target's clock, those that arrive up to its clock): the program's
    delivered count must lie between the two."""
    count = np.asarray(count)
    times = np.asarray(times, np.float64)
    slot = np.arange(times.shape[1])[None, :]
    t = np.where(slot < count[:, None], times, np.inf)[pre]    # [E, S]
    lag = t + np.asarray(delay)[:, None] - np.asarray(clock)[post][:, None]
    return (int((lag < -AT_CLOCK_MS).sum()), int((lag <= AT_CLOCK_MS).sum()))


def events_off(n_events: int, implied: tuple) -> int:
    """How far the delivered count lies outside the implied range."""
    lo, hi = implied
    return max(0, lo - n_events) + max(0, n_events - hi)


def conductance_gap(count, times, net: dict, clock, g_ampa, g_gaba,
                    taus=(2.0, 8.0)) -> float:
    """The largest distance, over neurons and the two channels, of the
    program's conductance ``g_*`` at each neuron's ``clock`` from what the
    spike train implies: every event sent by then, its weight decayed
    exactly from its arrival (``taus``, ms).  An event that arrives at the
    clock (within ``AT_CLOCK_MS``) may or may not be in, so the implied
    value is a range.  Each neuron's distance is taken over the implied
    value plus the channel's mean weight: one event's worth where little
    has arrived, a share of what has where much has."""
    count = np.asarray(count)
    times = np.asarray(times, np.float64)
    clock = np.asarray(clock, np.float64)
    post = np.asarray(net["post"])
    slot = np.arange(times.shape[1])[None, :]
    t = np.where(slot < count[:, None], times, np.inf)[np.asarray(
        net["pre"])]                                            # [E, S]
    age = clock[post][:, None] - (t + np.asarray(net["delay"])[:, None])
    worst = 0.0
    for w, tau, g in ((net["w_ampa"], taus[0], g_ampa),
                      (net["w_gaba"], taus[1], g_gaba)):
        w = np.asarray(w, np.float64)
        if not (w > 0).any():
            continue
        part = w[:, None] * np.exp(-np.maximum(age, 0.0) / tau)
        lo = np.bincount(post, np.where(age > AT_CLOCK_MS, part, 0.0).sum(1),
                         clock.size)
        hi = np.bincount(post, np.where(age >= -AT_CLOCK_MS, part,
                                        0.0).sum(1), clock.size)
        g = np.asarray(g, np.float64)
        off = np.maximum(lo - g, 0.0) + np.maximum(g - hi, 0.0)
        worst = max(worst, float((off / (hi + w[w > 0].mean())).max()))
    return worst


def verdict(numbers: dict, limits: dict = None) -> tuple[bool, list]:
    """(correct, [[name, value, limit], ...]) for the compared numbers."""
    limits = limits or LIMITS
    rows = [[k, numbers[k], limits[k]] for k in limits]
    ok = all(v <= lim for _, v, lim in rows)
    return ok, rows
