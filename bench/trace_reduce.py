"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time,
device time per operation name, and the host's doing in each idle gap.

The trace is read with ``jax.profiler.ProfileData`` alone, so this runs on
the CPU too.  A device plane is one named ``/device:TPU:<i>``; its
operations are the events of its ``XLA Ops`` line, named by their HLO
text (``%fusion.12 = f32[...] fusion(...)``); a loop's event holds the
events of its body, so an operation's own time is its duration less its
children's.  Host planes are the
``/host:...`` ones; their events include the benchmark's own spans (names
starting ``bench.``), the span ``bench.traced`` marking the traced window.

``reduce(path)`` returns:

- ``window_s``: the length of ``bench.traced``;
- ``busy_s``: the union of the device's operation intervals inside it,
  averaged over the devices;
- ``op_s``: {HLO text: own device seconds inside the window}, averaged
  over devices, and ``op_n``: {HLO text: events on the first device};
- ``gaps``: [(label, seconds)] for each interval of the window in which no
  operation ran, labelled ``<bench span>/<innermost host event>`` by what
  the host was doing at the gap's middle.
"""
from __future__ import annotations

import glob
import gzip
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.traced"
BENCH_PREFIX = "bench."
# gaps shorter than this sit between the operations of one program and are
# summed under one label rather than attributed
SHORT_GAP_NS = 20_000
SHORT_LABEL = "between ops (<20us)"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str):
    """A trace directory, an ``.xplane.pb`` file, or one gzipped."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _events(line):
    return [(float(e.start_ns), float(e.start_ns) + float(e.duration_ns),
             e.name) for e in line.events]


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def split_planes(pd):
    devices, hosts = [], []
    for p in pd.planes:
        if p.name.startswith(DEVICE_PREFIX):
            devices.append(p)
        elif p.name.startswith("/host:"):
            hosts.append(p)
    return devices, hosts


def host_events(hosts):
    ev = []
    for p in hosts:
        for line in p.lines:
            ev.extend(e for e in _events(line) if e[1] > e[0])
    return ev


def reduce(path: str) -> dict:
    pd = load(path)
    devices, hosts = split_planes(pd)
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    hev = host_events(hosts)
    spans = [e for e in hev if e[2] == WINDOW_SPAN]
    if spans:
        lo, hi = min(s for s, _, _ in spans), max(e for _, e, _ in spans)
    else:
        all_ops = [e for p in devices for line in p.lines
                   if line.name == OPS_LINE for e in _events(line)]
        lo = min(s for s, _, _ in all_ops)
        hi = max(e for _, e, _ in all_ops)
    busy, op_s, op_n, idle = 0.0, {}, {}, []
    for i, p in enumerate(devices):
        ops = [e for line in p.lines if line.name == OPS_LINE
               for e in _events(line)]
        merged = _union(_clip([(s, e) for s, e, _ in ops], lo, hi))
        busy += sum(e - s for s, e in merged)
        for s, e, name, own in _own_times(ops):
            if e > lo and s < hi:
                op_s[name] = op_s.get(name, 0.0) + own * 1e-9
                if i == 0:
                    op_n[name] = op_n.get(name, 0) + 1
        prev = lo
        for s, e in merged:
            if s > prev:
                idle.append((prev, s))
            prev = e
        if hi > prev:
            idle.append((prev, hi))
    nd = len(devices)
    label = _Labeller(hev)
    gaps = [(label((s + e) / 2) if e - s >= SHORT_GAP_NS else SHORT_LABEL,
             (e - s) * 1e-9 / nd) for s, e in idle]
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy * 1e-9 / nd,
            "op_s": {k: v / nd for k, v in op_s.items()}, "op_n": op_n,
            "gaps": gaps, "devices": nd}


def _own_times(ops):
    """(start, end, name, own ns) of each event, own = its duration less
    that of the events nested in it."""
    out, stack = [], []
    for s, e, name in sorted(ops, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][3] -= e - s
        stack.append([s, e, name, e - s])
    out.extend(tuple(x) for x in reversed(stack))
    return out


def short_name(hlo: str) -> str:
    """``fusion.12 [fusion]`` for ``%fusion.12 = f32[8] fusion(...)``; a
    custom call also names its target."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    kind = re.search(r"[\}\)] ([a-z][\w\-]*)\(", hlo)
    target = re.search(r'custom_call_target="([^"]+)"', hlo)
    tag = target.group(1) if target else (kind.group(1) if kind else "")
    return f"{name} [{tag}]" if tag else name


class _Labeller:
    """``<bench span>/<innermost other host event>`` around a time."""

    def __init__(self, hev):
        import numpy as np
        self.names = [e[2] for e in hev]
        self.s = np.array([e[0] for e in hev], np.float64)
        self.e = np.array([e[1] for e in hev], np.float64)
        self.bench = np.array([n.startswith(BENCH_PREFIX)
                               and n != WINDOW_SPAN for n in self.names],
                              bool)
        self.other = np.array([not n.startswith(BENCH_PREFIX)
                               for n in self.names], bool)

    def _inner(self, mask):
        import numpy as np
        if not mask.any():
            return None
        idx = np.flatnonzero(mask)
        return self.names[idx[np.argmin((self.e - self.s)[idx])]]

    def __call__(self, t):
        around = (self.s <= t) & (self.e > t)
        b = self._inner(around & self.bench) or "none"
        o = self._inner(around & self.other) or "idle"
        return f"{b}/{o}"


def top(d: dict, k: int = 10):
    return [[n, v] for n, v in sorted(d.items(), key=lambda kv: -kv[1])[:k]]


def top_ops(reduced: dict, k: int = 10):
    """The ``k`` operations with most own device time, by short name."""
    by = {}
    for hlo, sec in reduced["op_s"].items():
        n = short_name(hlo)
        by[n] = by.get(n, 0.0) + sec
    return top(by, k)


def gap_table(gaps, k: int = 10):
    by = {}
    for label, sec in gaps:
        by[label] = by.get(label, 0.0) + sec
    return top(by, k)


def op_time(reduced: dict, patterns) -> tuple[float, int]:
    """Own device seconds, and events on the first device, of the
    operations whose HLO text contains every string of ``patterns``."""
    names = [n for n in reduced["op_s"] if all(p in n for p in patterns)]
    return (sum(reduced["op_s"][n] for n in names),
            sum(reduced["op_n"].get(n, 0) for n in names))
