import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# --- device-count override must precede jax import (run this module as a
# --- subprocess: `python -m benchmarks.tpu_split`; benchmarks.run does).

"""TPU "divide and save" — the paper's method on the production pod.

The pod's 256 chips are factorised as (data=n, model=256/n): n independent
model replicas ("containers"), each over 256/n chips, the request batch
split n ways (core/splitter.py semantics). For every factorisation we lower
the serve step, derive the 3-term roofline, the step time and the
activity-model energy — the TPU analogue of Fig. 3 — then fit the paper's
convex model forms and let the DivideAndSave scheduler pick n*.
"""

import argparse
import json
import sys

import numpy as np

import jax

from benchmarks.common import save, table
from repro.configs.registry import get_config, get_shape
from repro.core import containers
from repro.core.energy_model import fit_best
from repro.core.hlo_analysis import analyze_hlo
from repro.core.roofline import build_report
from repro.core.scheduler import DivideAndSaveScheduler
from repro.launch.mesh import make_container_mesh
from repro.launch.sharding import ShardingRules
from repro.launch.specs import lowering_args
from repro.models.model import Model

TOTAL_CHIPS = 256
HBM_BYTES = 16e9


def measure(arch: str, shape_name: str, n: int) -> dict:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    spec = containers.ContainerSpec(n, TOTAL_CHIPS // n, TOTAL_CHIPS)
    feasible = containers.feasible(cfg, spec, hbm_bytes=HBM_BYTES)
    mesh = make_container_mesh(TOTAL_CHIPS, n)
    model = Model(cfg)
    step, args = lowering_args(model, shape)
    rules = ShardingRules(mesh, train=False, fsdp=False)
    if shape.kind == "train":
        in_sh = (rules.params(args[0]), rules.opt_state(args[1]),
                 rules.batch(args[2]))
    elif shape.kind == "prefill":
        in_sh = (rules.params(args[0]), rules.batch(args[1]))
    else:
        in_sh = (rules.params(args[0]),
                 rules.cache(args[1], args[2]["tokens"].shape[0]),
                 rules.batch(args[2]))
    with jax.set_mesh(mesh):
        compiled = jax.jit(step, in_shardings=in_sh).lower(*args).compile()
        txt = compiled.as_text()
    cost = analyze_hlo(txt)
    rep = build_report(arch, shape, cfg, f"({n},{TOTAL_CHIPS//n})",
                       TOTAL_CHIPS, cost)
    return {"n": n, "chips_per_container": TOTAL_CHIPS // n,
            "feasible": feasible,
            "weight_gb_per_chip":
                containers.weight_bytes_per_chip(cfg, spec) / 1e9,
            "t_compute": rep.t_compute, "t_memory": rep.t_memory,
            "t_collective": rep.t_collective, "step_time": rep.step_time,
            "dominant": rep.dominant, "energy_j": rep.energy_j}


def run(arch: str = "qwen3-8b", shape: str = "decode_32k",
        quick: bool = False) -> str:
    B = get_shape(shape).global_batch
    ns = [n for n in (1, 2, 4, 8, 16, 32, 64, 128, 256)
          if TOTAL_CHIPS % n == 0 and (B % n == 0 or B >= n)]
    if quick:
        ns = [1, 4, 16, 64]
    points = []
    for n in ns:
        try:
            points.append(measure(arch, shape, n))
            p = points[-1]
            print(f"[n={n:3d}] step {p['step_time']*1e3:8.2f} ms  "
                  f"E {p['energy_j']:9.1f} J  dom {p['dominant']}"
                  f"{'' if p['feasible'] else '  (infeasible: HBM)'}",
                  flush=True)
        except Exception as e:  # noqa: BLE001 — record and continue
            print(f"[n={n}] FAILED: {e}", flush=True)

    feas = [p for p in points if p["feasible"]]
    base = points[0]
    rows = [[p["n"], p["chips_per_container"],
             "Y" if p["feasible"] else "n",
             p["step_time"] / base["step_time"],
             p["energy_j"] / base["energy_j"], p["dominant"],
             p["weight_gb_per_chip"]] for p in points]
    lines = [f"# Divide-and-save on the pod — {arch} × {shape}",
             "", "Normalised to the n=1 (whole-pod single container) "
             "benchmark. Roofline estimates from a lowering on 512 "
             "fake CPU devices: CPU-derived, not chip measurements.", ""]
    lines += table(["n", "chips/ctr", "feasible", "step (norm)",
                    "energy (norm)", "dominant", "weights GB/chip"], rows)

    # convex fits + online scheduler choice over feasible factorisations
    if len(feas) >= 3:
        xs = np.array([p["n"] for p in feas], float)
        tfit = fit_best(xs, [p["step_time"] / base["step_time"]
                             for p in feas])
        efit = fit_best(xs, [p["energy_j"] / base["energy_j"]
                             for p in feas])
        sched = DivideAndSaveScheduler([p["n"] for p in feas],
                                       objective="energy", epsilon=0.0)
        for p in feas:
            sched.observe(p["n"], p["step_time"], p["energy_j"])
        best = sched.pick()
        lines += ["", f"time fit: {tfit.kind} {tuple(round(c, 4) for c in tfit.coef)}",
                  f"energy fit: {efit.kind} {tuple(round(c, 4) for c in efit.coef)}",
                  f"scheduler (energy objective) picks n* = {best}"]
    payload = {"arch": arch, "shape": shape, "points": points}
    return save(f"tpu_split_{arch}_{shape}", payload, lines)


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--shape", default="decode_32k")
    ap.add_argument("--quick", action="store_true")
    a = ap.parse_args()
    print(run(a.arch, a.shape, quick=a.quick))
    sys.exit(0)
