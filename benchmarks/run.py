"""Benchmark orchestrator — one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV.  REPRO_BENCH_QUICK=1 trims sizes."""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    print("name,us_per_call,derived")
    from benchmarks import (accuracy_fig5, active_set, delays_fig3,
                            discontinuities_fig7, event_wheel, exchange,
                            lab_experiment_fig8, placement, regimes_fig9,
                            robustness, roofline, serve, solver,
                            speedup_fig10, stiffness_fig6)
    modules = [
        ("fig3", delays_fig3.run),
        ("fig5", accuracy_fig5.run),
        ("fig6", stiffness_fig6.run),
        ("fig7", discontinuities_fig7.run),
        ("fig8", lab_experiment_fig8.run),
        ("fig9", regimes_fig9.run),
        ("fig10", speedup_fig10.run),
        ("event_wheel", event_wheel.run),
        ("exchange", exchange.run),
        ("placement", placement.run),
        ("active_set", active_set.run),
        ("solver", solver.run),
        ("robustness", robustness.run),
        ("serve", serve.run),
        ("roofline", lambda: roofline.run(mesh="all")),
    ]
    from benchmarks.common import dump_json

    failures = 0
    for name, fn in modules:
        try:
            fn()
        except Exception:                                   # noqa: BLE001
            failures += 1
            print(f"{name}/ERROR,0,{traceback.format_exc(limit=1).strip()!r}",
                  file=sys.stderr)
        # flush this suite's records to BENCH_<name>.json (no-op for suites
        # that already dumped internally, or without REPRO_BENCH_JSON)
        dump_json(name)
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
