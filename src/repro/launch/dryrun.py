import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# --- the two lines above MUST precede every other import (jax locks the ---
# --- device count on first init; the dry-run needs 512 placeholders).  ---

import argparse
import json
import time
import traceback

import jax

from repro.configs.base import INPUT_SHAPES
from repro.configs.registry import assigned_pairs, get_config, get_shape
from repro.core.hlo_analysis import analyze_hlo
from repro.core.roofline import build_report
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import ShardingRules
from repro.launch.specs import PARAM_DTYPE, lowering_args
from repro.models.model import Model
from repro.train.loop import TrainConfig

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results", "dryrun")

# Inference weights: shard over "model" only (container semantics) unless
# the per-chip shard would overflow HBM — then ZeRO-style ("data" too).
FSDP_INFERENCE_THRESHOLD = 12e9  # bytes per chip


def result_path(arch: str, shape: str, mesh_name: str) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    return os.path.join(RESULTS_DIR, f"{arch}__{shape}__{mesh_name}.json")


def _shardings_for(rules: ShardingRules, shape_kind: str, args):
    if shape_kind == "train":
        params, opt_state, batch = args
        return (rules.params(params), rules.opt_state(opt_state),
                rules.batch(batch))
    if shape_kind == "prefill":
        params, batch = args
        return (rules.params(params), rules.batch(batch))
    params, cache, batch = args
    return (rules.params(params), rules.cache(cache, batch["tokens"].shape[0]),
            rules.batch(batch))


def run_one(arch: str, shape_name: str, mesh_name: str,
            microbatches: int = 1, remat: bool = True,
            save: bool = True) -> dict:
    """Lower + compile one (arch, shape, mesh) and extract the roofline."""
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    multi_pod = mesh_name == "multipod"
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    model = Model(cfg)

    tcfg = TrainConfig(remat=remat, microbatches=microbatches)
    step, args = lowering_args(model, shape, tcfg)

    weight_bytes = cfg.param_count() * PARAM_DTYPE.dtype.itemsize
    model_axis = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    fsdp_inference = weight_bytes / model_axis > FSDP_INFERENCE_THRESHOLD
    rules = ShardingRules(mesh, train=(shape.kind == "train"),
                          fsdp=(True if shape.kind == "train"
                                else fsdp_inference),
                          decode=(shape.kind == "decode"))
    in_shardings = _shardings_for(rules, shape.kind, args)

    # decode: pin the output cache to the input cache layout — otherwise
    # XLA may pick a different output sharding and re-layout the whole
    # cache (a 34 MB collective-permute per layer per token, measured on
    # the multipod mesh)
    out_shardings = None
    if shape.kind == "decode":
        out_shardings = (None, in_shardings[1])

    t0 = time.time()
    with jax.set_mesh(mesh):
        jitted = (jax.jit(step, in_shardings=in_shardings,
                          out_shardings=out_shardings)
                  if out_shardings is not None
                  else jax.jit(step, in_shardings=in_shardings))
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = {}
        try:
            ma = compiled.memory_analysis()
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes"):
                v = getattr(ma, k, None)
                if v is not None:
                    mem[k] = int(v)
        except Exception as e:  # backend without memory analysis
            mem["error"] = str(e)

        xla_cost = {}
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, list):
                ca = ca[0]
            for k in ("flops", "bytes accessed"):
                if k in ca:
                    xla_cost[k] = float(ca[k])
        except Exception as e:
            xla_cost["error"] = str(e)

        hlo_text = compiled.as_text()

    cost = analyze_hlo(hlo_text)
    report = build_report(arch, shape, cfg, mesh_name, chips, cost)

    out = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "chips": chips,
        "kind": shape.kind,
        "fsdp": rules.fsdp,
        "microbatches": microbatches,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory_analysis": mem,
        "xla_cost_analysis": xla_cost,
        "parser": {
            "flops_per_chip": cost.flops_per_chip,
            "bytes_per_chip": cost.bytes_per_chip,
            "coll_wire_bytes_per_chip": cost.coll_wire_bytes_per_chip,
            "collectives_by_kind": cost.collectives,
        },
        "roofline": {
            "t_compute_s": report.t_compute,
            "t_memory_s": report.t_memory,
            "t_collective_s": report.t_collective,
            "dominant": report.dominant,
            "step_time_s": report.step_time,
            "model_flops": report.model_flops,
            "hlo_flops_total": report.hlo_flops_total,
            "useful_ratio": report.useful_ratio,
            "utilization": report.utilization,
            "power_w_per_chip": report.power_w_per_chip,
            "energy_j": report.energy_j,
        },
    }
    if save:
        with open(result_path(arch, shape_name, mesh_name), "w") as f:
            json.dump(out, f, indent=2)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description="Multi-pod dry-run")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=[None, *INPUT_SHAPES])
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    pairs = assigned_pairs()
    if args.arch:
        pairs = [(a, s) for a, s in pairs if a == args.arch]
    if args.shape:
        pairs = [(a, s) for a, s in pairs if s == args.shape]
    if not pairs and args.arch and args.shape:
        # explicit pair outside the assigned pool (extra architectures)
        pairs = [(args.arch, args.shape)]
    if not pairs:
        print("nothing to run")
        return 1

    failures = 0
    for arch, shape in pairs:
        path = result_path(arch, shape, args.mesh)
        if os.path.exists(path) and not args.force:
            print(f"[skip] {arch} × {shape} × {args.mesh} (cached)")
            continue
        try:
            out = run_one(arch, shape, args.mesh,
                          microbatches=args.microbatches)
            r = out["roofline"]
            print(f"[ok]   {arch} × {shape} × {args.mesh}: "
                  f"compile {out['compile_s']}s, dominant={r['dominant']}, "
                  f"step={r['step_time_s']*1e3:.2f}ms, "
                  f"useful={r['useful_ratio']:.2f}")
        except Exception:
            failures += 1
            print(f"[FAIL] {arch} × {shape} × {args.mesh}")
            traceback.print_exc()
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
