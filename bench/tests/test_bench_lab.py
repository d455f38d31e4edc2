"""CPU rehearsal of the lab cells at a tiny size: the window loop and its
simulated-time accounting, one executable for every seed, the result
line's shape, and the comparison failing on a broken timed path and on
the control.  ``bench/run.py`` itself refuses to run without a TPU."""
from __future__ import annotations

import io
import json

import jax
import jax.numpy as jnp
import pytest

from bench import checks, gen
from bench import run as R
from bench.lab import LabCell, probe_of
from bench.tests.cells import BIG_SEED, ROOT, tiny


def test_lab_window_and_gvt_accounting(pallas_interpret):
    _, _, cfg, traffic = tiny("lab65k.mix")
    cell = LabCell(cfg, traffic, BIG_SEED)
    info = cell.setup()
    assert info["batch_cap"] == 64 and info["warmup_gvt_ms"] > 0
    win = cell.window(0.0)
    assert win["calls"] == 1 and win["failed_calls"] == 0
    assert win["restarts"] == 0
    assert win["bio_ms"] == pytest.approx(win["gvt_end_ms"]
                                          - win["gvt_start_ms"])
    assert win["bio_ms_per_s"] == pytest.approx(win["bio_ms"]
                                                / win["wall_s"])
    assert win["counts"]["rounds"] == 160
    assert not win["window_compile"]["backend_compiles"]
    cmp = cell.check()
    ok, rows = checks.verdict(cmp)
    assert ok, rows
    assert cmp["paired"] > 0 and cmp["exact_faults"] == 0


def test_lab_window_restarts_at_t_end():
    """A run whose GVT reaches t_end starts anew and keeps counting."""
    _, _, cfg, traffic = tiny("lab65k.mix")
    cfg.update(t_end_ms=0.6, rounds_per_call=8)
    cell = LabCell(cfg, traffic, 3)
    cell.setup()
    win = cell.window(2.0)
    assert win["restarts"] >= 1
    # the first run from the warm-up's GVT to t_end, each later one from
    # 0; the last up to where the window closed
    want = (0.6 - win["gvt_start_ms"] + 0.6 * (win["restarts"] - 1)
            + win["gvt_end_ms"])
    assert win["bio_ms"] == pytest.approx(want)
    assert win["counts"] == {}


def test_one_executable_for_every_seed():
    from bench.lab import build_network
    _, _, cfg, traffic = tiny("lab65k.mix")
    from bench.common import hoist
    from bench.lab import k_round_fn, make_runner
    texts = []
    for seed in (1, BIG_SEED):
        net = build_network(cfg, seed)
        run = make_runner(cfg, net, gen.drive(traffic, 512, seed),
                          cfg["t_end_ms"])
        carry = jax.eval_shape(run.init_carry)
        h = hoist(k_round_fn(run, 4), carry)
        texts.append(h.jitted.lower(*h.lower_args).as_text())
    assert texts[0] == texts[1]


def test_result_line_shape(pallas_interpret):
    spec, w, cfg, traffic = tiny("lab65k.mix")
    out = io.StringIO()
    res = R.run_cell(spec, w["name"], cfg, traffic, BIG_SEED, 0.0, 0,
                     jax.devices(), out=out)
    line = json.loads(json.dumps(res))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"bio_ms_per_s", "setup_s"}
    assert line["metrics"]["bio_ms_per_s"]["unit"] == "ms/s"
    assert line["metrics"]["bio_ms_per_s"]["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for name, c in line["checks"].items():
        assert set(c) == {"value", "limit"} and name in checks.LIMITS
    assert "setup " in out.getvalue() and "check " in out.getvalue()


# -- a broken timed path makes ``correct`` false ---------------------------
def _unchanged(run, fn):
    """A round that returns its state unchanged."""
    return lambda c: (c, probe_of(run, c))


def _half_batch(run, fn):
    """Half of the neurons left out of every call: their state is put
    back as it was."""
    def broken(c):
        new, _ = fn(c)
        keep = jnp.arange(new[0].t.shape[0]) % 2 == 0
        sts = jax.tree_util.tree_map(
            lambda a, b: jnp.where(
                keep.reshape((-1,) + (1,) * (a.ndim - 1)), a, b)
            if a.ndim and a.shape[0] == keep.shape[0] else a,
            new[0], c[0])
        new = (sts,) + tuple(new[1:])
        return new, probe_of(run, new)
    return broken


def _spike_altered(run, fn):
    """Every recorded spike moved 0.3 ms later where it is produced."""
    def broken(c):
        new, _ = fn(c)
        rec = new[2]
        moved = rec.times + jnp.where(jnp.isfinite(rec.times), 0.3, 0.0)
        new = new[:2] + (rec._replace(times=moved),) + new[3:]
        return new, probe_of(run, new)
    return broken


def _every_tenth(n):
    return jnp.arange(n) % 10 == 0


def _minority_moved(run, fn):
    """The spikes of every tenth neuron moved 0.3 ms later where they are
    produced: the median gap does not see it, the tail does."""
    def broken(c):
        new, _ = fn(c)
        rec = new[2]
        few = _every_tenth(rec.count.shape[0])[:, None]
        moved = rec.times + jnp.where(few & jnp.isfinite(rec.times), 0.3,
                                      0.0)
        new = new[:2] + (rec._replace(times=moved),) + new[3:]
        return new, probe_of(run, new)
    return broken


def _minority_unrecorded(run, fn):
    """The spikes of every tenth neuron never recorded: the reference's
    spikes of those neurons are left unpaired."""
    def broken(c):
        new, _ = fn(c)
        rec = new[2]
        few = _every_tenth(rec.count.shape[0])
        new = new[:2] + (rec._replace(count=jnp.where(few, 0, rec.count)),) \
            + new[3:]
        return new, probe_of(run, new)
    return broken


def _misdelivered(run, fn):
    """Every tenth neuron's AMPA conductance raised by one mean weight at
    the end of every call: an event delivered where none was sent.  The
    event count does not change; over this window's 15 ms the extra
    charge moves those neurons' spikes."""
    def broken(c):
        new, _ = fn(c)
        sts = new[0]
        few = _every_tenth(sts.t.shape[0])
        zn = sts.zn.at[:, 0, 4].add(jnp.where(few, 1e-4, 0.0))
        new = (sts._replace(zn=zn),) + tuple(new[1:])
        return new, probe_of(run, new)
    return broken


FAULTS = {"state_unchanged": (_unchanged, None),
          "half_batch": (_half_batch, None),
          "spike_altered": (_spike_altered, None),
          "minority_moved": (_minority_moved, "gap_p99_ms"),
          "minority_unrecorded": (_minority_unrecorded, "unpaired_share"),
          "misdelivered": (_misdelivered, None)}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_lab_fault_is_not_correct(fault):
    """Each fault the timed path can have makes ``correct`` false; where a
    number is named, that number fails while the median gap does not."""
    plant, catches = FAULTS[fault]
    spec, w, cfg, traffic = tiny("lab65k.mix")
    res = R.run_cell(spec, w["name"], cfg, traffic, 21, 0.0, 0,
                     jax.devices(), out=io.StringIO(),
                     cell_kw={"round_fn": plant})
    assert res["correct"] is False, res["checks"]
    if catches:
        rows = res["checks"]
        assert rows[catches]["value"] > rows[catches]["limit"], rows
        assert rows["gap_p50_ms"]["value"] <= rows["gap_p50_ms"]["limit"]


# -- the control ------------------------------------------------------------
def test_control_is_not_correct():
    """The program with its BDF tolerance one decade looser, the step that
    would tempt a later change, fails the comparison."""
    spec, w, cfg, traffic = tiny("lab65k.mix")
    res = R.run_cell(spec, w["name"], cfg, traffic, BIG_SEED, 0.0, 0,
                     jax.devices(), out=io.StringIO(),
                     cell_kw={"bdf_over": {"atol": 10 * cfg["bdf"]["atol"]}})
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["exact_faults"]["value"] == 0


def test_float32_reference_reads_far_below_the_program():
    """The reference in float32 in the program's place: its rounding moves
    spikes by far less than the solver's tolerance does, so it is no
    control here (PERF.md section 2)."""
    from bench.control import f32_reading
    _, _, cfg, traffic = tiny("lab65k.mix")
    r = f32_reading(cfg, traffic, BIG_SEED, 15.0)
    assert r["paired"] > 50 and r["exact_faults"] == 0
    assert r["gap_p50_ms"] < 0.1 * checks.LIMITS["gap_p50_ms"]


