"""Readings behind the limits in ``bench/checks.py``: the program's
comparison numbers over many seeds, and the controls', at a cell's own
size, all seeds in one process (one set-up).  The benchmark's runs never
call this.

    python3 bench/control.py --workload lab65k.mix --kind program \\
        --seeds 11,12,13 --seconds 10

Kinds:

- ``program``: the cell as it runs, one short window per seed;
- ``atol10``: the program with its BDF tolerance one decade looser;
- ``f32``: the plain reference computed in float32, put in the program's
  place: its trains, up to ``--t-ms``, are checked as the program's are.

Each seed prints one line ``reading {json}`` with the numbers compared.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def f32_reading(cfg, traffic, seed, t_ms):
    """The float32 reference in the program's place (lab cells)."""
    import jax.numpy as jnp

    from bench import checks, gen, reference
    from bench.lab import build_network
    g = build_network(cfg, seed)
    iinj = gen.drive(traffic, cfg["n_neurons"], seed)
    kw = dict(diam_um=cfg["soma_diam_um"])
    c32, t32, _ = reference.simulate(g, iinj, t_ms, dtype=jnp.float32,
                                        **kw)
    rc, rt, over = reference.simulate(
        g, iinj, t_ms + checks.margin_ms(t_ms),
        drive=checks.padded(c32, t32), **kw)
    cmp = checks.compare_trains(c32, t32, rc, rt, t_ms)
    cmp["exact_faults"] = over
    return cmp


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--kind", choices=("program", "atol10", "f32"),
                    required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--t-ms", type=float, default=6.0)
    args = ap.parse_args(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    # the TPU runtime's logs would go to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    import jax
    jax.config.update("jax_enable_x64", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import checks
    from bench import run as R
    from bench.common import require_tpu
    require_tpu(1)
    spec = R.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    _, cfg, traffic = R.find_cell(spec, args.workload)
    first = None
    for seed in [int(s) for s in args.seeds.split(",")]:
        if args.kind == "f32":
            rec = f32_reading(cfg, traffic, seed, args.t_ms)
        else:
            kw = {"bdf_over": {"atol": 10 * cfg["bdf"]["atol"]}} \
                if args.kind == "atol10" else {}
            cell = R.cell_class(cfg)(cfg, traffic, seed, **kw)
            cell.setup(like=first)
            first = first or cell
            win = cell.window(args.seconds)
            rec = cell.check()
            rec["bio_ms"] = win["bio_ms"]
            rec["call_s"] = win["call_s"]["each"]
            del cell
        ok, rows = checks.verdict(rec)
        print("reading " + json.dumps({"seed": seed, "kind": args.kind,
                                       "correct": ok, **rec},
                                      default=float), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
