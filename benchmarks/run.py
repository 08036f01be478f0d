"""Benchmark harness entry point: one benchmark per paper table/figure +
the TPU adaptation sweep.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--skip-tpu]
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--skip-tpu", action="store_true",
                    help="skip the (slower) pod-factorisation sweep")
    args = ap.parse_args()

    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    from benchmarks import (decode_throughput, fig1_cores, fig3_split,
                            pool_scaling, table2_fit)

    t0 = time.time()
    print("=" * 72)
    print("fig1_cores — single container, varying CPU allocation")
    print("=" * 72)
    print(fig1_cores.run(quick=args.quick))

    print("=" * 72)
    print("fig3_split — n containers: time / energy / power")
    print("=" * 72)
    print(fig3_split.run(quick=args.quick))

    print("=" * 72)
    print("table2_fit — convex model fits")
    print("=" * 72)
    print(table2_fit.run(quick=args.quick))

    print("=" * 72)
    print("pool_scaling — concurrent container pool + adaptive scheduler")
    print("=" * 72)
    print(pool_scaling.run(quick=args.quick))

    print("=" * 72)
    print("pool_scaling (process) — thread vs pinned-process containers")
    print("=" * 72)
    print(pool_scaling.run_process(quick=args.quick))

    print("=" * 72)
    print("pool_scaling (streaming) — Router time-to-first-chunk")
    print("=" * 72)
    print(pool_scaling.run_streaming(quick=args.quick))

    print("=" * 72)
    print("decode_throughput — fused chunked decode vs per-token")
    print("=" * 72)
    print(decode_throughput.run(quick=args.quick))

    if not args.skip_tpu:
        sweeps = [("qwen3-8b", "decode_32k")]
        if not args.quick:
            sweeps.append(("qwen3-8b", "prefill_32k"))
        for arch, shape in sweeps:
            print("=" * 72)
            print(f"tpu_split — divide-and-save on the 256-chip pod: "
                  f"{arch} × {shape} (CPU subprocess: lowered over 512 "
                  "fake CPU devices; roofline estimates, not chip "
                  "measurements)")
            print("=" * 72)
            cmd = [sys.executable, "-m", "benchmarks.tpu_split",
                   "--arch", arch, "--shape", shape]
            if args.quick:
                cmd.append("--quick")
            # a CPU-only child by design: it must never contend for a
            # chip this parent may hold
            r = subprocess.run(cmd, env={**os.environ,
                                         "JAX_PLATFORMS": "cpu"})
            if r.returncode != 0:
                print("tpu_split FAILED")
                return 1

    print(f"\nall benchmarks done in {time.time()-t0:.0f}s "
          f"(results in benchmarks/results/)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
