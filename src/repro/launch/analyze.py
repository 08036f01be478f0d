import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# (device-count override before jax import — same as dryrun.py)

"""Perf-iteration profiler: lower one (arch, shape, mesh), print the
roofline terms and the TOP collectives / byte-heavy computations — the
"profile" used by the §Perf hypothesis→change→measure loop.

    PYTHONPATH=src python -m repro.launch.analyze --arch X --shape Y
"""

import argparse

import jax

from repro.configs.registry import get_config, get_shape
from repro.core.hlo_analysis import (_parse_computation, _split_computations,
                                     analyze_hlo)
from repro.core.roofline import build_report
from repro.launch.dryrun import FSDP_INFERENCE_THRESHOLD, _shardings_for
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import ShardingRules
from repro.launch.specs import PARAM_DTYPE, lowering_args
from repro.models.model import Model
from repro.train.loop import TrainConfig


def lower_text(arch, shape_name, multi_pod=False, microbatches=1,
               remat=True, overrides=None, remat_policy="none"):
    cfg = get_config(arch)
    if overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **overrides)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = Model(cfg)
    step, args = lowering_args(model, shape,
                               TrainConfig(remat=remat,
                                           remat_policy=remat_policy,
                                           microbatches=microbatches))
    weight_bytes = cfg.param_count() * PARAM_DTYPE.dtype.itemsize
    model_axis = dict(mesh.shape)["model"]
    fsdp = (shape.kind == "train"
            or weight_bytes / model_axis > FSDP_INFERENCE_THRESHOLD)
    rules = ShardingRules(mesh, train=(shape.kind == "train"), fsdp=fsdp,
                          decode=(shape.kind == "decode"))
    in_sh = _shardings_for(rules, shape.kind, args)
    with jax.set_mesh(mesh):
        compiled = jax.jit(step, in_shardings=in_sh).lower(*args).compile()
        mem = compiled.memory_analysis()
        txt = compiled.as_text()
    return cfg, shape, mesh, txt, mem


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--top", type=int, default=12)
    ap.add_argument("--remat-policy", default="none")
    ap.add_argument("--dump", default=None, help="write HLO text here")
    ap.add_argument("--set", action="append", default=[],
                    help="config override, e.g. --set moe_dispatch_groups=16")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        overrides[k] = type(getattr(get_config(args.arch), k))(
            float(v) if "." in v else int(v)) \
            if not isinstance(getattr(get_config(args.arch), k), str) else v

    cfg, shape, mesh, txt, mem = lower_text(
        args.arch, args.shape, args.multipod, args.microbatches,
        overrides=overrides, remat_policy=args.remat_policy)
    if args.dump:
        with open(args.dump, "w") as f:
            f.write(txt)
    cost = analyze_hlo(txt)
    rep = build_report(args.arch, shape, cfg, "pod", mesh.devices.size, cost)
    print(f"== {args.arch} × {args.shape}  (microbatches="
          f"{args.microbatches})")
    print(f"t_compute {rep.t_compute*1e3:10.2f} ms")
    print(f"t_memory  {rep.t_memory*1e3:10.2f} ms")
    print(f"t_coll    {rep.t_collective*1e3:10.2f} ms   <- dominant: "
          f"{rep.dominant}")
    print(f"useful_ratio {rep.useful_ratio:.3f}   "
          f"HBM temp {getattr(mem, 'temp_size_in_bytes', 0)/1e9:.1f} GB")
    print(f"collectives by kind: "
          f"{ {k: f'{v:.2e}' for k, v in cost.collectives.items()} }")

    comps = {c.name: c for (n, e, ls) in _split_computations(txt)
             for c in [_parse_computation(n, e, ls)]}
    rows = []
    for name, c in comps.items():
        m = cost.trip_counts.get(name, 0)
        for col in c.collectives:
            rows.append((col.wire_bytes_per_chip * m, col.kind,
                         col.result_bytes, col.participants, m, name[:48]))
    rows.sort(reverse=True)
    print(f"\ntop {args.top} collectives (wire bytes/chip × trips):")
    for r in rows[:args.top]:
        print(f"  {r[0]:.3e}  {r[1]:<18s} res={r[2]:.2e} p={r[3]:4d} "
              f"mult={r[4]:6.0f}  {r[5]}")

    brows = []
    wb = {b for c in comps.values() for (_, b) in c.whiles}
    for name, c in comps.items():
        m = cost.trip_counts.get(name, 0)
        if m <= 0:
            continue
        fused = name in wb and not c.whiles
        b = (c.bytes_slices if fused else c.bytes_accessed) * m
        brows.append((b, m, fused, name[:48]))
    brows.sort(reverse=True)
    print(f"\ntop byte-heavy computations:")
    for b, m, f, n in brows[:args.top]:
        print(f"  {b:.3e}  mult={m:6.0f} fused={f}  {n}")


if __name__ == "__main__":
    main()
