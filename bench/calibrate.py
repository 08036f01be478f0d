"""Readings that a cell's correctness limit is set from, on the chip.

    python3 -m bench.calibrate --workload <cell> --seeds 11,12,13 --seconds 20

For each seed, in one process: one run of the cell (``bench.run.run_cell``,
the timed path and its own check), then on that run's sample of finished
requests the widest logit gap of the tokens the fp8 control puts first.
It prints the program's reading (the run's ``logit_gap``) beside the
control's. The limit lies between the largest program reading and the
smallest control reading (``bench/correct.py``).
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

from bench import run as bench_run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    from bench import correct, spec
    cell = spec.load_cell(args.workload)
    bench_run._compile_cache()
    cfg, mod = cell.config, cell.model
    for seed in (int(s) for s in args.seeds.split(",")):
        res, log, sample = bench_run.run_cell(cell, seed, args.seconds,
                                              False)
        w = mod.reference_weights(cfg, seed)
        ref = mod.Reference(cfg, w)
        ctl = mod.Reference(cfg, w, fp8=True)
        ctrl = correct.logit_gaps(log, sample, ref,
                                  int(cfg["serving"]["max_len"]), control=ctl)
        del ref, ctl, w
        gc.collect()
        print(json.dumps({
            "seed": seed, "correct": res["correct"],
            "requests": len(sample),
            "tokens": sum(len(r.tokens) for r in sample),
            "program_gap": res["checks"]["logit_gap"]["value"],
            "control_gap": max(ctrl) if ctrl else None,
            "limit": res["checks"]["logit_gap"]["limit"],
            "control_gaps": ctrl}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
