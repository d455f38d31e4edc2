"""CPU rehearsal of the rest of the harness: the pinned wheel over a dozen
seeds, a cell or an entry added from new files alone, the comparison's
counts, the inputs' seeding, and the entry point's refusal to run without
a TPU."""
from __future__ import annotations

import hashlib
import io
import json
import os
import shutil
import subprocess
import sys
import types

import jax
import numpy as np
import pytest

from bench import checks, gen
from bench import run as R
from bench.lab import LabCell
from bench.tests.cells import BIG_SEED, ROOT, tiny


def test_pinned_wheel_no_drops_over_a_dozen_seeds():
    """The pinned wheel is at least what ``WheelSpec.auto`` asks for at the
    configuration's size on a dozen seeds, and a dozen seeds at a tiny
    size, all on one executable, drop no event."""
    from repro import sched
    from bench.lab import build_network, program_network
    spec = R.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    _, cfg, _ = R.find_cell(spec, "lab65k.mix")
    pinned = sched.WheelSpec(**cfg["runner"]["wheel"])
    for seed in range(1, 13):
        g = build_network(cfg, seed)
        assert np.bincount(g["pre"]).max() == cfg["max_out_degree"]
        auto = sched.WheelSpec.auto(program_network(g))
        assert auto.bucket_width == pinned.bucket_width
        assert auto.bucket_slots <= pinned.bucket_slots
    _, _, cfg, _ = tiny("lab65k.mix")
    traffic = {"drive": "regime", "regime": "burst"}   # every neuron spikes
    first = None
    for seed in range(1, 13):
        cell = LabCell(cfg, traffic, seed)
        cell.setup(like=first)
        first = first or cell
        for _ in range(6):
            cell.carry, p = cell.kcall(cell.carry)
        p = jax.device_get(p)
        assert int(p["dropped"]) == 0 and int(p["spikes"]) > 0, seed


def _digest(root):
    h = {}
    for dp, _, fs in os.walk(os.path.join(root, "bench")):
        for f in fs:
            if f.endswith((".py", ".json")) and "/." not in dp:
                p = os.path.join(dp, f)
                with open(p, "rb") as fh:
                    h[os.path.relpath(p, root)] = hashlib.sha1(
                        fh.read()).hexdigest()
    with open(os.path.join(root, "BENCHMARK.json"), "rb") as fh:
        h["BENCHMARK.json"] = hashlib.sha1(fh.read()).hexdigest()
    return h


def test_new_cell_from_new_files_alone(tmp_path):
    """A later PR adds a traffic mix and a per-layer metric as new files and
    new entries; no file already there changes."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".*", "__pycache__",
                                                  "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    before = _digest(str(tmp_path))
    (tmp_path / "bench" / "traffic" / "fast_only.json").write_text(
        json.dumps({"drive": "regime", "regime": "fast",
                    "why": "every neuron in the fast regime"}))
    (tmp_path / "bench" / "metrics" / "spikes_per_call.lab.py").write_text(
        "def value(ctx):\n"
        "    w = ctx['window']\n"
        "    return w['counts']['spikes'] / w['calls']\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "lab65k.fast", "config": "lab65k",
                              "traffic": "fast_only", "chips": 1,
                              "why": "fast regime"})
    for m in spec["end_to_end"]:
        if m["name"] == "bio_ms_per_s":
            m["workloads"].append("lab65k.fast")
    spec["per_layer"].append({"name": "spikes_per_call.lab", "unit": "1",
                              "better": "higher",
                              "source": "program_counter",
                              "layer": "scheduler", "moves": "bio_ms_per_s",
                              "workloads": ["lab65k.fast"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    after = _digest(str(tmp_path))
    changed = [k for k in before if before[k] != after.get(k)]
    assert changed == ["BENCHMARK.json"]
    spec2, w, cfg, traffic = tiny("lab65k.fast", root=str(tmp_path))
    assert traffic["regime"] == "fast"
    res = R.run_cell(spec2, "lab65k.fast", cfg, traffic, 9, 0.0, 0,
                     jax.devices(), out=io.StringIO(), root=str(tmp_path))
    assert res["correct"] is True, res["checks"]
    assert "bio_ms_per_s" in res["metrics"]
    ctx = {"window": {"counts": {"spikes": 10.0}, "calls": 5}}
    assert R.metric_value("spikes_per_call.lab", ctx,
                          str(tmp_path)) == 2.0


def test_new_entry_from_a_new_module_alone(monkeypatch):
    """A cell of a new kind is a new module ``bench/<entry>.py`` with a
    class ``Cell``: the harness finds it by the configuration's ``entry``
    and reads ``attempted`` and ``failed`` from it, with no edit of its
    own."""
    class Cell:
        def __init__(self, cfg, traffic, seed):
            self.seed = seed

        def setup(self):
            return {"n": 1}

        def window(self, seconds, trace_s=0.0, tracer=None):
            self.attempted, self.failed = 3, 1
            return {"bio_ms_per_s": 0.5}

        def check(self):
            return {name: 0 for name in checks.LIMITS}

    mod = types.ModuleType("bench.stub_entry")
    mod.Cell = Cell
    monkeypatch.setitem(sys.modules, "bench.stub_entry", mod)
    spec = R.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    w, cfg, traffic = R.find_cell(spec, "lab65k.mix")
    res = R.run_cell(spec, w["name"], dict(cfg, entry="stub_entry"), traffic,
                     BIG_SEED, 0.0, 0, jax.devices(), out=io.StringIO())
    assert (res["correct"], res["attempted"], res["failed"]) == (True, 3, 1)
    assert res["metrics"]["bio_ms_per_s"]["value"] == 0.5
    assert R.cell_class({"entry": "lab"}) is LabCell


def test_compare_trains_counts_gaps_and_unpaired():
    """Spikes pair by rank; a reference spike the program lacks is
    unpaired, and that alone makes the run not correct."""
    inf = np.inf
    p_count = np.array([2, 1, 0])
    p_times = np.array([[1.0, 2.0], [1.5, inf], [inf, inf]])
    r_count = np.array([2, 1, 1])
    r_times = np.array([[1.001, 2.002], [1.5, inf], [3.0, inf]])
    cmp = checks.compare_trains(p_count, p_times, r_count, r_times, 10.0)
    assert (cmp["paired"], cmp["unpaired"], cmp["spikes_ref"]) == (3, 1, 4)
    assert cmp["unpaired_share"] == 0.25
    assert cmp["gap_p50_ms"] == pytest.approx(0.001)
    assert cmp["gap_p99_ms"] == pytest.approx(0.00198)
    ok, rows = checks.verdict({**cmp, "exact_faults": 0})
    assert not ok
    assert [r[0] for r in rows if r[1] > r[2]] == ["unpaired_share"]


def test_conductance_gap_counts_events_by_their_arrival():
    """Two neurons, one excitatory edge each way: the conductance implied
    by the trains is each arrived event's weight decayed from its
    arrival; an event at the clock may be in or not."""
    net = {"pre": np.array([0, 1]), "post": np.array([1, 0]),
           "delay": np.array([1.0, 0.5]), "w_ampa": np.array([2e-4, 1e-4]),
           "w_gaba": np.zeros(2)}
    count = np.array([1, 1])
    times = np.array([[1.0], [3.0]])
    clock = np.array([3.5, 4.0])          # neuron 0's event arrives at 3.5
    ga = np.array([0.0, 2e-4 * np.exp(-2.0 / 2.0)])
    gap = checks.conductance_gap(count, times, net, clock, ga, np.zeros(2))
    assert gap == 0.0
    ga_in = ga + np.array([1e-4, 0.0])    # delivered at the clock: fine
    assert checks.conductance_gap(count, times, net, clock, ga_in,
                                  np.zeros(2)) == pytest.approx(0.0)
    lost = np.array([0.0, 0.0])           # neuron 1's event never came
    gap = checks.conductance_gap(count, times, net, clock, lost, np.zeros(2))
    want = 2e-4 * np.exp(-1.0)            # over want + the mean weight
    assert gap == pytest.approx(want / (want + 1.5e-4))


def test_inputs_follow_the_seed():
    for seed in (0, 7, 2 ** 40 + 3):
        a = gen.make_network(256, 16, seed, max_out_degree=40)
        b = gen.make_network(256, 16, seed, max_out_degree=40)
        assert all(np.array_equal(a[k], b[k]) for k in a if k != "n")
        assert np.array_equal(gen.mixture_currents(256, seed),
                              gen.mixture_currents(256, seed))
    c = gen.make_network(256, 16, 8, max_out_degree=40)
    assert not np.array_equal(a["pre"], c["pre"])
    shares = np.isclose(gen.mixture_currents(20000, 1),
                        0.8 * gen.SOMA_CALIBRATION["i_threshold"], rtol=0.05)
    assert 0.8 < shares.mean() < 0.95


def test_cli_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "lab65k.mix", "--seed", "1", "--seconds", "1"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "no TPU" in p.stderr
