"""Dry-run machinery at test scale: the same build/lower/compile path as the
production 512-chip run, on a small forced-host-device mesh in a subprocess
(jax device count locks at first init, so this must not share the test
process)."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
from repro.configs import ARCHS, get_config
from repro.configs.base import ShapeConfig
from repro.launch import dryrun
from repro.launch.hlo_analysis import collective_bytes
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 2), ("data", "model"))
out = {}
for arch in ["smollm-135m", "qwen3-moe-30b-a3b", "rwkv6-7b", "zamba2-7b",
             "whisper-base", "internvl2-1b"]:
    cfg = get_config(arch).reduced()
    for shape in [ShapeConfig("t", 64, 8, "train"),
                  ShapeConfig("d", 64, 8, "decode")]:
        fn, args, sh = dryrun.build(cfg, shape, mesh)
        lowered = jax.jit(fn, in_shardings=sh).lower(*args)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        coll, _ = collective_bytes(compiled.as_text())
        out[f"{arch}/{shape.kind}"] = {
            "temp": mem.temp_size_in_bytes,
            "coll": int(coll),
            "flops": dryrun.cost_dict(compiled).get("flops", 0.0),
        }
print(json.dumps(out))
"""


@pytest.mark.slow
def test_dryrun_small_mesh_compiles():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=560,
                         cwd=ROOT)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert len(out) == 12
    for cell, rec in out.items():
        assert rec["flops"] > 0, cell
        # training cells must communicate (grad all-reduce at minimum)
        if cell.endswith("/train"):
            assert rec["coll"] > 0, cell


def test_production_mesh_shapes():
    """make_production_mesh geometry (without building it here)."""
    import math
    assert math.prod((16, 16)) == 256
    assert math.prod((2, 16, 16)) == 512


def test_dryrun_artifacts_complete():
    """The committed dry-run artifacts cover every (arch x shape x mesh)
    cell: ok for applicable cells, explicit skip records for long_500k on
    full-attention archs, plus the paper-neuro cells."""
    d = os.path.join(ROOT, "experiments", "dryrun")
    if not os.path.isdir(d):
        pytest.skip("dry-run artifacts not generated yet")
    from repro.configs import ARCHS, SHAPES, shape_applicable
    missing, bad = [], []
    for mesh in ("single", "multi"):
        for arch, cfg in ARCHS.items():
            for shape in SHAPES.values():
                p = os.path.join(d, f"{arch}__{shape.name}__{mesh}.json")
                if not os.path.exists(p):
                    missing.append(p)
                    continue
                rec = json.load(open(p))
                applicable, _ = shape_applicable(cfg, shape)
                want = "ok" if applicable else "skipped"
                if rec.get("status") != want:
                    bad.append((p, rec.get("status"), rec.get("error", "")[:80]))
        p = os.path.join(d, f"paper-neuro__sim_round__{mesh}.json")
        if not os.path.exists(p) or json.load(open(p)).get("status") != "ok":
            bad.append((p, "missing/err", ""))
    assert not missing, missing[:5]
    assert not bad, bad[:5]
