"""Find a cell's knee once, on the chip: offer its traffic at a list of
rates in one process and print what each rate got.

    python3 -m bench.sweep --workload <cell> --seed <n> --seconds <s> \\
        --rates 1.0,1.5,2.0

One set-up, then per rate the mix's ramp and a window of ``--seconds``;
requests still in flight at the window's close are cancelled before the
next rate. For each rate it prints the requests due and served in the
window, tokens/s, the tails and the requests still queued at the close:
the knee is the highest rate whose queue does not grow. Not part of a
benchmark run; the chosen rate goes into the cell's traffic file.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys

from bench import run as bench_run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    from bench import drive, spec, stats, traffic
    cell = spec.load_cell(args.workload)
    bench_run._compile_cache()
    bench_run.check_devices(cell.chips)
    containers = cell.mix.containers or cell.chips
    model, params, backend = bench_run.set_up(cell, args.seed, containers)
    vocab = int(cell.config["vocab_size"])
    from repro.serving import Router
    router = Router(backend)
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dataclasses.replace(cell.mix, rate_rps=rate, loop="open")
        reqs = traffic.schedule(mix, args.seed + k, args.seconds, vocab)
        for r in reqs:
            r.rid += 1_000_000 * (k + 1)
        log = drive.run(router, reqs, loop="open", window_s=args.seconds,
                        ramp_s=mix.ramp_s, late_limit_s=5.0)
        win = stats.window_requests(log)
        e2e = stats.end_to_end(log)
        queued = sum(1 for r in log.records.values()
                     if r.submit_s is not None and r.submit_s < args.seconds
                     and (r.first_s is None or r.first_s > args.seconds))
        ttft = sorted(stats.ttft_s(r) for r in win if r.first_s is not None)
        out = {"rate_rps": rate, "due": len(win),
               "first_token_in_window": sum(
                   1 for r in win if r.first_s is not None
                   and r.first_s < args.seconds),
               "done_in_window": sum(1 for r in win if r.done_s is not None
                                     and r.done_s < args.seconds),
               "queued_at_close": queued,
               "ttft_p50_s": stats.percentile(ttft, 50) if ttft else None,
               **e2e}
        print(json.dumps(out), flush=True)
        for rec in log.records.values():
            if rec.done_s is None:
                router.cancel(rec.rid)
        router.poll()
    router.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
