"""The stage metrics: the program's scopes joined to a traced run's
operations by instruction name (``bench/stages.py``), on synthetic module
texts, on a trace recorded on a TPU v5e with the stage scopes
(``bench/tests/data/lab_scoped.xplane.pb.gz``: two one-round calls of the
lab round at N = 2,048, as ``lab_small`` was recorded) beside the compiled
module's text of its call (``lab_scoped.hlo.txt.gz``), and on a tiny lab
cell's own program.  Needs no chip."""
from __future__ import annotations

import gzip
import os

import pytest

from bench import run as R
from bench import stages, trace_reduce
from bench.tests.cells import BIG_SEED, ROOT, tiny

SMALL_TRACE = os.path.join(ROOT, "bench", "tests", "data",
                           "lab_small.xplane.pb.gz")
SCOPED_TRACE = os.path.join(ROOT, "bench", "tests", "data",
                            "lab_scoped.xplane.pb.gz")
SCOPED_HLO = os.path.join(ROOT, "bench", "tests", "data",
                          "lab_scoped.hlo.txt.gz")
# the five round stages whose shares, with the unscoped rest, make busy
STAGES = {"horizon_share.lab": ("fap.horizon", "fap.horizon_update"),
          "compact_share.lab": ("fap.compact",),
          "lane_move_share.lab": ("fap.gather", "fap.scatter"),
          "advance_share.lab": ("fap.advance",),
          "fanout_share.lab": ("fap.fanout",)}
SHARES = tuple(STAGES) + ("newton_setup_share.lab", "newton_share.lab")

HLO_SNIPPET = """\
HloModule jit_flat_fn, entry_computation_layout={()->f32[]}

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %neg.1 = f32[8]{0} negate(%param_0.1), metadata={op_name="jit(f)/while/body/fap.horizon_update/neg"}
}

ENTRY %main.9 (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  %fusion.7 = f32[8]{0} fusion(%p.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/while/body/fap.horizon_update/neg" source_file="x.py" source_line=3}
  %fusion.8 = f32[8]{0} fusion(%fusion.7), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/while/body/fap.horizon/lt"}
  ROOT %copy.3 = f32[8]{0} copy(%fusion.8)
}
"""


def test_op_scopes_and_whole_component_match():
    sc = stages.op_scopes(HLO_SNIPPET)
    assert sc["fusion.7"] == "jit(f)/while/body/fap.horizon_update/neg"
    assert sc["neg.1"].endswith("fap.horizon_update/neg")
    assert sc["copy.3"] == "" and sc["p.1"] == ""
    red = {"op_s": {"%fusion.7 = f32[8]{0} fusion(%p.1)": 2.0,
                    "%fusion.8 = f32[8]{0} fusion(%fusion.7)": 3.0,
                    "%copy.3 = f32[8]{0} copy(%fusion.8)": 5.0},
           "op_n": {"%fusion.7 = f32[8]{0} fusion(%p.1)": 4}, "busy_s": 10.0}
    assert stages.scope_time(red, sc, ("fap.horizon",)) == (3.0, 0)
    assert stages.scope_time(red, sc, ("fap.horizon_update",)) == (2.0, 4)
    assert stages.scope_time(red, sc, ("fap",)) == (0, 0)
    assert stages.scope_share(red, sc, ("fap.horizon",
                                        "fap.horizon_update")) == 50.0
    assert stages.scope_share(red, sc, ("bdf.setup",)) is None


HLO_LOOP = """\
HloModule jit_flat_fn

%fused_computation.2 (param_0.2: f32[8]) -> f32[8] {
  %param_0.2 = f32[8]{0} parameter(0)
  %neg.2 = f32[8]{0} negate(%param_0.2), metadata={op_name="jit(f)/while/body/fap.advance/neg"}
  ROOT %scatter.2 = f32[8]{0} scatter(%neg.2, %neg.2)
}

%body.3 (p.3: f32[8]) -> f32[8] {
  %p.3 = f32[8]{0} parameter(0)
  %copy.4 = f32[8]{0} copy(%p.3)
  ROOT %fusion.5 = f32[8]{0} fusion(%copy.4), kind=kLoop, calls=%fused_computation.2
}

%cond.6 (p.6: f32[8]) -> pred[] {
  %p.6 = f32[8]{0} parameter(0)
  ROOT %c.6 = pred[] constant(false)
}

ENTRY %main.7 (p.7: f32[8]) -> f32[8] {
  %p.7 = f32[8]{0} parameter(0)
  ROOT %while.8 = f32[8]{0} while(%p.7), condition=%cond.6, body=%body.3, metadata={op_name="jit(f)/while/body/fap.advance/while"}
}
"""

# fusions without metadata whose fused instructions come from two stages
HLO_MIXED = """\
HloModule jit_flat_fn

%fused_computation.10 (param_0.10: f32[8]) -> f32[8] {
  %param_0.10 = f32[8]{0} parameter(0)
  %neg.10 = f32[8]{0} negate(%param_0.10), metadata={op_name="jit(f)/while/body/fap.gather/neg"}
  ROOT %abs.10 = f32[8]{0} abs(%neg.10), metadata={op_name="jit(f)/while/body/fap.scatter/abs"}
}

%fused_computation.11 (param_0.11: f32[8]) -> f32[8] {
  %param_0.11 = f32[8]{0} parameter(0)
  %neg.11 = f32[8]{0} negate(%param_0.11), metadata={op_name="jit(f)/while/body/fap.compact/neg"}
  %abs.11 = f32[8]{0} abs(%neg.11), metadata={op_name="jit(f)/while/body/fap.advance/abs"}
  ROOT %copy.11 = f32[8]{0} copy(%abs.11)
}

ENTRY %main.12 (p.12: f32[8]) -> f32[8] {
  %p.12 = f32[8]{0} parameter(0)
  %fusion.13 = f32[8]{0} fusion(%p.12), kind=kLoop, calls=%fused_computation.10
  ROOT %fusion.14 = f32[8]{0} fusion(%fusion.13), kind=kLoop, calls=%fused_computation.11
}
"""


def test_op_scopes_of_instructions_without_metadata():
    """A fusion without metadata takes its fused instructions' scope; any
    other instruction without it, the scope of the loop it runs in.  A
    fusion whose instructions span two stages goes whole to one: its root's,
    or where the root has none, that of its first instruction in the text
    that has one."""
    sc = stages.op_scopes(HLO_LOOP)
    assert sc["fusion.5"] == "jit(f)/while/body/fap.advance/neg"
    assert sc["copy.4"] == sc["while.8"] == \
        "jit(f)/while/body/fap.advance/while"
    assert sc["p.7"] == ""
    sc = stages.op_scopes(HLO_MIXED)
    assert sc["fusion.13"] == "jit(f)/while/body/fap.scatter/abs"
    assert sc["fusion.14"] == "jit(f)/while/body/fap.compact/neg"
    red = {"op_s": {"%fusion.13 = f32[8]{0} fusion(%p.12)": 1.0,
                    "%fusion.14 = f32[8]{0} fusion(%fusion.13)": 2.0},
           "op_n": {}, "busy_s": 3.0}
    assert stages.scope_time(red, sc, ("fap.gather",))[0] == 0
    assert stages.scope_time(red, sc, ("fap.scatter",))[0] == 1.0
    assert stages.scope_time(red, sc, ("fap.compact",))[0] == 2.0
    assert stages.scope_time(red, sc, ("fap.advance",))[0] == 0


def _ctx(trace=None, scopes=None):
    return {"trace": trace, "op_scopes": scopes, "config": {"entry": "lab"},
            "window": {"counts": {}, "bio_ms": 1.0, "n": 2048},
            "setup": {"n": 2048, "batch_cap": 4}}


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(SMALL_TRACE)


def test_new_metrics_none_without_what_they_read(reduced, monkeypatch):
    """None without a trace, without the module's text, with no busy time,
    and on a trace of a program without the scopes; an untraced run never
    looks for the cell."""
    sc = stages.op_scopes(HLO_SNIPPET)
    unscoped = {stages.instr_name(n):
                "jit(flat_fn)/while/body/lt" for n in reduced["op_s"]}
    for name in SHARES:
        for ctx in (_ctx(None, sc), _ctx(reduced, None), _ctx(reduced, {}),
                    _ctx({**reduced, "busy_s": 0.0}, sc),
                    _ctx(reduced, unscoped)):
            assert R.metric_value(name, ctx, ROOT) is None, name
    monkeypatch.setattr(stages, "live_cell", lambda ctx: 1 / 0)
    ctx = _ctx(None)
    del ctx["op_scopes"]
    assert R.metric_value("advance_share.lab", ctx, ROOT) is None
    assert "op_scopes" not in ctx
    # a traced run whose cell cannot be read leaves the metrics out
    ctx = _ctx(reduced)
    del ctx["op_scopes"]
    assert R.metric_value("advance_share.lab", ctx, ROOT) is None
    assert ctx["op_scopes"] is None
    monkeypatch.setattr(stages, "live_cell", lambda ctx: None)
    ctx = _ctx(reduced)
    del ctx["op_scopes"]
    assert R.metric_value("horizon_share.lab", ctx, ROOT) is None


@pytest.fixture(scope="module")
def scoped():
    with gzip.open(SCOPED_HLO, "rt") as f:
        scopes = stages.op_scopes(f.read())
    return trace_reduce.reduce(SCOPED_TRACE), scopes


def _stage_of(op_name):
    comps = op_name.split("/")
    return [m for m, tags in STAGES.items() if set(tags) & set(comps)]


def test_every_traced_op_in_the_module_text(scoped):
    red, scopes = scoped
    assert all(stages.instr_name(n) in scopes for n in red["op_s"])


def test_stage_times_and_the_rest_add_up(scoped):
    """Each traced operation falls in one stage at most; the five stages'
    times and the unscoped rest sum to the operations' total."""
    red, scopes = scoped
    total = sum(red["op_s"].values())
    rest = 0.0
    for n, sec in red["op_s"].items():
        found = _stage_of(scopes[stages.instr_name(n)])
        assert len(found) <= 1, (n, found)
        rest += sec if not found else 0.0
    staged = sum(stages.scope_time(red, scopes, tags)[0]
                 for tags in STAGES.values())
    assert staged + rest == pytest.approx(total, rel=1e-9)
    assert staged >= 0.9 * red["busy_s"]


def test_horizon_scope_is_not_horizon_update(scoped):
    red, scopes = scoped
    full, n_full = stages.scope_time(red, scopes, ("fap.horizon",))
    upd, n_upd = stages.scope_time(red, scopes, ("fap.horizon_update",))
    both, _ = stages.scope_time(red, scopes, STAGES["horizon_share.lab"])
    assert 0 < full < upd and n_full and n_upd
    assert both == pytest.approx(full + upd)


def test_new_metrics_read_the_scoped_trace(scoped):
    """Every share is read; the five round stages and the unscoped rest
    make up busy, and the Newton setup and loop are parts of the advance."""
    red, scopes = scoped
    ctx = _ctx(red, scopes)
    got = {m: R.metric_value(m, ctx, ROOT) for m in SHARES}
    assert all(v is not None and 0 < v < 100 for v in got.values()), got
    assert sum(got[m] for m in STAGES) <= 100.0 + 1e-9
    assert got["newton_setup_share.lab"] < got["advance_share.lab"]
    assert got["newton_share.lab"] < got["advance_share.lab"]


def test_program_text_names_every_stage():
    """After the check, as in a traced run, the stage metrics find the run's
    cell, and its device call's compiled text maps the instructions to the
    program's scopes, every stage of the round among them."""
    from bench.lab import LabCell
    from repro.core.exec_common import ROUND_STAGES
    _, _, cfg, traffic = tiny("lab65k.mix")
    cfg.update(rounds_per_call=4)
    cell = LabCell(cfg, traffic, BIG_SEED)
    cell.setup()
    cell.window(0.0)
    cell.check()                      # frees the program's state
    assert stages.live_cell({"config": cfg}) is cell
    scopes = stages.op_scopes(stages.program_text(cell))
    have = {c for op in scopes.values() for c in op.split("/")}
    assert set(ROUND_STAGES) <= have
    # one traced operation of the advance, as half the busy time
    adv = next(n for n, op in scopes.items()
               if "fap.advance" in op.split("/"))
    hlo = f"%{adv} = f64[8]{{0}} fusion()"
    ctx = {"config": cfg, "trace": {"op_s": {hlo: 1.0}, "op_n": {hlo: 1},
                                    "busy_s": 2.0}}
    assert R.metric_value("advance_share.lab", ctx, ROOT) == 50.0
    assert R.metric_value("compact_share.lab", ctx, ROOT) is None
    assert ctx["op_scopes"] and set(ctx["op_scopes"]) == set(scopes)
