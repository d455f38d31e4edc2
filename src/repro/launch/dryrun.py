import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, and record the artifacts §Roofline reads.

Per cell:
  * full-depth compile  -> memory_analysis (fits-per-device proof),
                           trip-count-aware collective bytes (hlo_analysis),
                           raw cost_analysis (body-once, recorded as such)
  * depth La / Lb compiles -> exact per-layer FLOPs/bytes deltas, scaled to
    the full depth: total = c_a + (L - La)/(Lb - La) * (c_b - c_a)
    (XLA's HloCostAnalysis counts while bodies once — verified empirically;
    the delta method recovers the true totals; sub-layer *time* scans in the
    SSM families contribute <3% of layer FLOPs and are noted in DESIGN.md)

Usage:
  python -m repro.launch.dryrun --arch all --shape all --mesh both
  python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k --mesh single
"""
import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import ARCHS, SHAPES, get_config, shape_applicable  # noqa: E402
from repro.configs.base import ArchConfig, ShapeConfig                 # noqa: E402
from repro.distributed import sharding as shd                          # noqa: E402
from repro.distributed.ctx import sharding_ctx                         # noqa: E402
from repro.launch.hlo_analysis import collective_bytes, collective_breakdown  # noqa: E402
from repro.launch.mesh import make_production_mesh                     # noqa: E402
from repro.models import lm                                            # noqa: E402
from repro.optim import adamw_init                                     # noqa: E402

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins, no allocation)
# ---------------------------------------------------------------------------
def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def cost_dict(compiled) -> dict:
    """``compiled.cost_analysis()``, or {} where the backend reports none."""
    return compiled.cost_analysis() or {}


def input_specs(cfg: ArchConfig, shape: ShapeConfig):
    """Stand-ins for every model input of this (arch, shape) cell."""
    B, S = shape.global_batch, shape.seq_len
    if shape.kind in ("train", "prefill"):
        batch = {"tokens": sds((B, S), jnp.int32)}
        if shape.kind == "train":
            batch["labels"] = sds((B, S), jnp.int32)
        if cfg.family == "audio":
            batch["frames"] = sds((B, cfg.enc_frames, cfg.d_model), jnp.bfloat16)
        if cfg.family == "vlm":
            batch["patches"] = sds((B, cfg.n_patches, cfg.d_model), jnp.bfloat16)
        return batch
    # decode: one new token against a seq_len cache
    token = sds((B, 1), jnp.int32)
    state = jax.eval_shape(lambda: lm.make_decode_state(cfg, B, S))
    return {"token": token, "state": state}


def params_specs_sds(cfg: ArchConfig):
    return jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))


def model_flops(cfg: ArchConfig, shape: ShapeConfig) -> float:
    """MODEL_FLOPS = 6*N*D (train) / 2*N*D (inference), N = active params."""
    p = jax.tree_util.tree_leaves(params_specs_sds(cfg))
    n_total = sum(x.size for x in p)
    if cfg.n_experts:
        # active = total - (inactive experts' share)
        moe_per_layer = 3 * cfg.d_model * cfg.d_ff * cfg.n_experts
        active_moe = 3 * cfg.d_model * cfg.d_ff * cfg.top_k
        n_active = n_total - cfg.n_layers * (moe_per_layer - active_moe)
    else:
        n_active = n_total
    if shape.kind == "train":
        toks = shape.global_batch * shape.seq_len
        return 6.0 * n_active * toks
    if shape.kind == "prefill":
        toks = shape.global_batch * shape.seq_len
        return 2.0 * n_active * toks
    return 2.0 * n_active * shape.global_batch          # decode: 1 token/seq


# ---------------------------------------------------------------------------
# builders: jitted fns + shardings per cell kind
# ---------------------------------------------------------------------------
def build(cfg: ArchConfig, shape: ShapeConfig, mesh):
    params = params_specs_sds(cfg)
    p_specs = shd.to_named(
        shd.fit_specs(shd.param_specs(cfg, params, mesh), params, mesh), mesh)

    if shape.kind == "train":
        opt = jax.eval_shape(adamw_init, params)
        o_specs = shd.to_named(
            shd.fit_specs(shd.opt_specs(cfg, opt, mesh), opt, mesh), mesh)
        batch = input_specs(cfg, shape)
        b_specs_raw = shd.batch_specs(cfg, shape, mesh)
        b_specs_raw = {k: b_specs_raw[k] for k in batch}
        b_specs = shd.to_named(shd.fit_specs(b_specs_raw, batch, mesh), mesh)

        def fn(p, o, b):
            return lm.train_step(cfg, p, o, b, 1e-4, remat=True)

        return fn, (params, opt, batch), (p_specs, o_specs, b_specs)

    if shape.kind == "prefill":
        batch = input_specs(cfg, shape)
        b_specs_raw = shd.batch_specs(cfg, shape, mesh)
        b_specs_raw = {k: b_specs_raw[k] for k in batch}
        b_specs = shd.to_named(shd.fit_specs(b_specs_raw, batch, mesh), mesh)

        def fn(p, b):
            return lm.prefill(cfg, p, b)

        return fn, (params, batch), (p_specs, b_specs)

    # decode
    spec = input_specs(cfg, shape)
    s_specs = shd.to_named(
        shd.fit_specs(shd.decode_state_specs(cfg, shape, mesh),
                      spec["state"], mesh), mesh)
    t_specs = shd.to_named(
        shd.fit_specs(shd.token_spec(cfg, shape, mesh), spec["token"], mesh),
        mesh)

    def fn(p, token, state):
        return lm.decode_step(cfg, p, token, state, jnp.int32(shape.seq_len - 1))

    return fn, (params, spec["token"], spec["state"]), (p_specs, t_specs, s_specs)


def lower_and_compile(cfg, shape, mesh):
    fn, args, in_shardings = build(cfg, shape, mesh)
    with sharding_ctx(mesh):
        lowered = jax.jit(fn, in_shardings=in_shardings).lower(*args)
        t0 = time.time()
        compiled = lowered.compile()
        dt = time.time() - t0
    return lowered, compiled, dt


def _mem_dict(m):
    return {
        "argument_bytes": m.argument_size_in_bytes,
        "output_bytes": m.output_size_in_bytes,
        "temp_bytes": m.temp_size_in_bytes,
        "alias_bytes": m.alias_size_in_bytes,
        "code_bytes": m.generated_code_size_in_bytes,
    }


def _depth_pair(cfg: ArchConfig):
    period = max(cfg.shared_attn_every, 1) if cfg.family == "hybrid" else 1
    return period, 2 * period


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             skip_delta: bool = False) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "kind": shape.kind, "time": time.strftime("%Y-%m-%d %H:%M:%S")}
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.devices.size
    try:
        lowered, compiled, compile_s = lower_and_compile(cfg, shape, mesh)
        mem = compiled.memory_analysis()
        cost = cost_dict(compiled)
        print(f"  {arch}/{shape_name}/{mesh_kind} memory_analysis:", mem, flush=True)
        print(f"  {arch}/{shape_name}/{mesh_kind} cost_analysis: "
              f"flops={cost.get('flops')} bytes={cost.get('bytes accessed')}",
              flush=True)
        txt = compiled.as_text()
        coll_total, _ = collective_bytes(txt)
        coll_flat = collective_breakdown(txt)
        rec.update(
            status="ok", n_chips=int(n_chips), compile_seconds=compile_s,
            memory=_mem_dict(mem),
            cost_raw={"flops": cost.get("flops", 0.0),
                      "bytes_accessed": cost.get("bytes accessed", 0.0)},
            collective_bytes_per_device=coll_total,
            collective_breakdown_flat=coll_flat,
            model_flops=model_flops(cfg, shape),
        )
        del lowered, compiled, txt

        if not skip_delta:
            rec.update(delta_pass(cfg, shape, mesh))
    except Exception as e:                                   # noqa: BLE001
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    return rec


def delta_pass(cfg: ArchConfig, shape: ShapeConfig, mesh) -> dict:
    """Depth-delta FLOPs/bytes with fully-unrolled structural scans (so the
    compiled HLO contains every layer/chunk body; cost_analysis is then
    exact for the shallow models, and the L-scaling is linear algebra)."""
    from repro.models import common as cm

    La, Lb = _depth_pair(cfg)
    costs = {}
    with cm.unroll_scans():
        for Lx in (La, Lb):
            cfg_x = dataclasses.replace(cfg, n_layers=Lx)
            _, comp_x, _ = lower_and_compile(cfg_x, shape, mesh)
            cx = cost_dict(comp_x)
            costs[Lx] = (cx.get("flops", 0.0), cx.get("bytes accessed", 0.0))
            del comp_x
    scale = (cfg.n_layers - La) / (Lb - La)
    flops = costs[La][0] + scale * (costs[Lb][0] - costs[La][0])
    bytes_ = costs[La][1] + scale * (costs[Lb][1] - costs[La][1])
    return {"hlo_flops_per_device": flops, "hlo_bytes_per_device": bytes_,
            "delta_depths": [La, Lb],
            "delta_raw": {str(k): v for k, v in costs.items()}}


def run_delta_only(arch: str, shape_name: str, mesh_kind: str, out_dir: str):
    """Merge a (re)computed delta pass into an existing artifact."""
    path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_kind}.json")
    if not os.path.exists(path):
        return
    rec = json.load(open(path))
    if rec.get("status") != "ok":
        return
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    try:
        rec.update(delta_pass(cfg, shape, mesh))
        rec["delta_method"] = "unrolled"
    except Exception as e:                                   # noqa: BLE001
        rec["delta_error"] = f"{type(e).__name__}: {e}"
    with open(path, "w") as f:
        json.dump(rec, f, indent=2)
    print(f"[delta] {arch} {shape_name} {mesh_kind} "
          f"flops={rec.get('hlo_flops_per_device'):.3e}", flush=True)


def run_paper_cell(mesh_kind: str, optimized: bool = False) -> dict:
    """The paper's own workload: one FAP scheduler round, 2^20 neurons,
    sharded over every mesh axis (DESIGN.md §3).  optimized=True uses the
    shard-local event insert + explicit notification all-gathers (§Perf)."""
    from repro.core.cell import CellModel
    from repro.core.morphology import branched_tree
    from repro.distributed.fap_spmd import PaperNeuroSpec, build_fap_round

    name = "paper-neuro-opt" if optimized else "paper-neuro"
    rec = {"arch": name, "shape": "sim_round", "mesh": mesh_kind,
           "kind": "simulation", "time": time.strftime("%Y-%m-%d %H:%M:%S")}
    try:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
        model = CellModel(branched_tree(depth=3, seg_per_branch=2))
        spec = PaperNeuroSpec()
        fn, args, in_sh = build_fap_round(model, spec, mesh,
                                          optimized=optimized)
        with sharding_ctx(mesh):
            lowered = jax.jit(fn, in_shardings=in_sh).lower(*args)
            t0 = time.time()
            compiled = lowered.compile()
            compile_s = time.time() - t0
        mem = compiled.memory_analysis()
        cost = cost_dict(compiled)
        print(f"  {name}/{mesh_kind} memory_analysis:", mem, flush=True)
        txt = compiled.as_text()
        coll_total, _ = collective_bytes(txt)
        rec.update(
            status="ok", n_chips=int(mesh.devices.size),
            compile_seconds=compile_s, memory=_mem_dict(mem),
            cost_raw={"flops": cost.get("flops", 0.0),
                      "bytes_accessed": cost.get("bytes accessed", 0.0)},
            collective_bytes_per_device=coll_total,
            collective_breakdown_flat=collective_breakdown(txt),
            n_neurons=spec.n_neurons, k_in=spec.k_in, n_comp=spec.n_comp,
        )
    except Exception as e:                                   # noqa: BLE001
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    return rec


def save(rec: dict, out_dir: str):
    os.makedirs(out_dir, exist_ok=True)
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(rec, f, indent=2)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default=OUT_DIR)
    ap.add_argument("--skip-delta", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--delta-only", action="store_true",
                    help="recompute only the unrolled depth-delta FLOPs/bytes "
                         "and merge into existing artifacts")
    args = ap.parse_args()

    archs = sorted(ARCHS) if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    for mesh_kind in meshes:
        for arch in archs:
            if arch in ("paper-neuro", "paper-neuro-opt"):
                path = os.path.join(args.out,
                                    f"{arch}__sim_round__{mesh_kind}.json")
                if args.skip_existing and os.path.exists(path):
                    continue
                rec = run_paper_cell(mesh_kind, optimized=arch.endswith("-opt"))
                save(rec, args.out)
                print(f"[{rec['status']}] {arch} sim_round {mesh_kind} "
                      f"{rec.get('error', '')[:80]}", flush=True)
                continue
            for shape_name in shapes:
                if args.delta_only:
                    run_delta_only(arch, shape_name, mesh_kind, args.out)
                    continue
                path = os.path.join(
                    args.out, f"{arch}__{shape_name}__{mesh_kind}.json")
                if args.skip_existing and os.path.exists(path):
                    with open(path) as f:
                        if json.load(f).get("status") in ("ok", "skipped"):
                            print(f"[skip existing] {arch} {shape_name} {mesh_kind}")
                            continue
                t0 = time.time()
                rec = run_cell(arch, shape_name, mesh_kind, args.out,
                               skip_delta=args.skip_delta)
                save(rec, args.out)
                status = rec["status"]
                extra = rec.get("reason", rec.get("error", ""))[:80]
                print(f"[{status}] {arch} {shape_name} {mesh_kind} "
                      f"({time.time()-t0:.0f}s) {extra}", flush=True)


if __name__ == "__main__":
    main()
