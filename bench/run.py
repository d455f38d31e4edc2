"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload lab65k.mix --seed 7 --seconds 30 --trace 0

The cell is found by name in ``BENCHMARK.json``; its configuration
(``bench/configs/<config>.json``) names the entry, the module
``bench/<entry>.py`` whose class ``Cell`` drives the program (``setup``,
``window``, ``check``, then ``attempted`` and ``failed``), and the
program's keyword arguments; its traffic file
(``bench/traffic/<traffic>.json``) names the generator of its inputs.
Each per-layer metric is ``bench/metrics/<metric>.py``, with a function
``value(ctx)`` that returns the number or None.

The run refuses to start without the TPUs the cell asks for.  It keeps
JAX's compilation cache in ``.jax_cache`` at the root of the checkout.
Set-up (``setup_s``) lasts from the start of this process to the start
of the window.  With ``--trace 0`` the result holds the cell's end-to-end
metrics; with ``--trace 1`` the first seconds of the window are traced and
the result holds its per-layer metrics, the device's busy and window
seconds, and a breakdown.  Either way the window's output is then compared
with the plain reference, and the numbers compared are printed beside
their limits, last on standard error and last in the result line.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_ROOT = os.path.join(BENCH, ".traces")


def load_json(path):
    with open(path) as f:
        return json.load(f)


def find_cell(spec: dict, workload: str, root: str = ROOT):
    """(workload entry, config file contents, traffic file contents), the
    files found by name under ``root``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    c = {c["name"]: c for c in spec["configs"]}[w["config"]]
    cfg = load_json(os.path.join(root, c["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     w["traffic"] + ".json"))
    return w, cfg, traffic


def metric_value(name: str, ctx: dict, root: str = ROOT):
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.value(ctx)


def metrics_for(spec: dict, workload: str, kind: str):
    """The cell's metrics of ``kind`` ("end_to_end" or "per_layer")."""
    return [m for m in spec[kind]
            if "workloads" not in m or workload in m["workloads"]]


class Tracer:
    """Starts and stops the profiler around the traced part of a window;
    the span ``bench.traced`` marks that part in the trace."""

    def __init__(self, path):
        self.path = path
        self.ann = None

    def start(self):
        import jax
        shutil.rmtree(self.path, ignore_errors=True)
        jax.profiler.start_trace(self.path)
        self.ann = jax.profiler.TraceAnnotation("bench.traced")
        self.ann.__enter__()

    def stop(self):
        import jax
        self.ann.__exit__(None, None, None)
        jax.profiler.stop_trace()


def cell_class(cfg):
    """The class ``Cell`` of the configuration's entry, ``bench/<entry>.py``."""
    return importlib.import_module("bench." + cfg["entry"]).Cell


def run_cell(spec, workload, cfg, traffic, seed, seconds, trace, devices,
             out=sys.stdout, cell_kw=None, root=ROOT):
    """Set up, measure, check; returns the result dict."""
    from bench import checks, trace_reduce
    from bench.common import now, peak_bytes
    cell = cell_class(cfg)(cfg, traffic, seed, **(cell_kw or {}))
    info = cell.setup()
    setup_s = now() - T_PROCESS
    say(out, "setup", {**info, "setup_s": setup_s, "seed": seed,
                       "workload": workload})
    trace_dir = os.path.join(TRACE_ROOT, f"{workload}-{seed}")
    tracer = Tracer(trace_dir) if trace else None
    win = cell.window(seconds, float(cfg.get("trace_seconds", 3.0)), tracer)
    mem = peak_bytes(devices)
    say(out, "window", win)
    cmp = cell.check()
    ok, rows = checks.verdict(cmp)
    say(out, "check", cmp)
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": bool(ok), "attempted": cell.attempted,
              "failed": cell.failed}
    ctx = {"workload": workload, "config": cfg, "setup": info, "window": win,
           "trace": None, "peaks": None, "setup_s": setup_s}
    breakdown = None
    if trace:
        red = trace_reduce.reduce(trace_dir)
        ctx["trace"] = red
        ctx["peaks"] = peaks_for(d0.device_kind, root)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": trace_reduce.top_ops(red),
                     "idle_gaps": trace_reduce.gap_table(red["gaps"])}
        shutil.rmtree(trace_dir, ignore_errors=True)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metrics_for(spec, workload, kind):
        if kind == "per_layer":
            v = metric_value(m["name"], ctx, root)
        else:       # taken by the benchmark itself on the host's clock
            v = setup_s if m["name"] == "setup_s" else win.get(m["name"])
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result.update({"metrics": metrics, "device": device})
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in rows}
    return result


def peaks_for(kind: str, root: str = ROOT) -> dict:
    table = load_json(os.path.join(root, "bench", "peaks.json"))
    if kind not in table["devices"]:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         "bench/peaks.json")
    return table["devices"][kind]


def say(out, tag, rec):
    print(f"{tag} " + json.dumps(rec, default=float), file=out, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    w, cfg, traffic = find_cell(spec, args.workload)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    # a fixed directory inside the checkout, so that every run of a cell
    # after the first finds its programs there
    # the TPU runtime's logs would go to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    jax.config.update("jax_enable_x64", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from bench.common import require_tpu
    devices = require_tpu(int(w["chips"]))
    from repro.launch.compile_cache import use_compile_cache
    from repro.kernels.event_wheel import ops as ew_ops
    cache = use_compile_cache()
    d0 = devices[0]
    say(sys.stdout, "device", {"platform": d0.platform,
                               "kind": d0.device_kind,
                               "count": len(devices), "compile_cache": cache,
                               "kernels": ew_ops.auto_impls()})
    result = run_cell(spec, args.workload, cfg, traffic, args.seed,
                      args.seconds, args.trace, devices)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result, default=float), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
