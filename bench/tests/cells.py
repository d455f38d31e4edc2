"""Tiny versions of the benchmark's cells for the CPU tests."""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run as R  # noqa: E402

BIG_SEED = 2 ** 31 + 12345


def tiny(workload, root=ROOT):
    """A cell's entry, config and traffic, read from its files and cut to
    a size the CPU runs in seconds."""
    spec = R.load_json(os.path.join(root, "BENCHMARK.json"))
    w, cfg, traffic = R.find_cell(spec, workload, root)
    # one call of 160 rounds reaches about 15 ms; the cap rolls work over
    # 8 rounds as 1,024 of 65,536 does over 64
    cfg.update(n_neurons=512, rounds_per_call=160)
    cfg["runner"] = dict(cfg["runner"], batch_cap=64)
    return spec, w, cfg, traffic
