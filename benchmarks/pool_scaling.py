"""Pool scaling — the "save" half of divide-and-save, measured.

Three pieces of evidence:
  (a) REAL wall times: a fixed request batch served by the container pool
      at n ∈ {1, 2, 4}, sequential vs concurrent engines. Concurrency is
      thread-per-container on the shared device (jax releases the GIL
      during XLA execution), so the speedup is genuine overlap, not
      simulation.
  (b) the online scheduler loop on a synthetic convex time/energy profile
      (§VI-style simulation): the adaptive pool must find the known
      argmin within a handful of waves.
  (c) ``--isolation process``: the same wave served thread-per-container
      vs **process-per-container with pinned disjoint cpusets**
      (serving/process_pool.py — the paper's actual ``--cpus`` mechanism)
      at n ∈ {1, 2, 4}, emitting ``BENCH_process_pool.json``. Counts past
      the host's core budget fall back to explicit round-robin shared
      cores (flagged per row) rather than silently overlapping.
  (d) ``--streaming``: the same wave admitted request-by-request through
      the ``Router`` (serving/router.py) and consumed as chunk events,
      recording **time-to-first-chunk p50/p95** and streamed tokens/s per
      count, emitting ``BENCH_streaming.json`` — the latency axis the
      wave API could not observe at all.

The measured model is a mid-size reduction — large enough that XLA compute
dominates Python dispatch, which is what lets threads overlap on CPU.
"""
from __future__ import annotations

import time

from benchmarks.common import make_requests, save, save_bench, table
from repro.configs.base import reduce_config
from repro.configs.registry import get_config
from repro.models.model import Model
from repro.serving.adaptive import AdaptiveServingPool, synthetic_pool_factory
from repro.serving.pool import ContainerServingPool


def bench_config():
    """Mid-size serving config: big enough per-step compute to overlap."""
    return reduce_config(get_config("qwen3-0.6b"), n_layers=4, d_model=512,
                         n_heads=8, n_kv_heads=4, d_ff=2048,
                         vocab_size=8192)


def measure_pool(model, params, requests, ns=(1, 2, 4), n_slots=2,
                 max_len=128, reps: int = 3) -> list[dict]:
    """Sequential vs concurrent wall/energy per container count.

    Modes are interleaved and the best of ``reps`` kept — min is the
    standard noise filter for wall timings on a shared, small host."""
    rows = []
    for n in ns:
        pool = ContainerServingPool(model, params, n,
                                    n_slots_per_container=n_slots,
                                    max_len=max_len)
        pool.serve_timed(list(requests), concurrent=False)  # compile warmup
        seq, con = [], []
        for _ in range(reps):
            _, _, w, e = pool.serve_timed(list(requests), concurrent=False)
            seq.append((w, e))
            _, _, w, e = pool.serve_timed(list(requests), concurrent=True)
            con.append((w, e))
        (w_seq, e_seq), (w_con, e_con) = min(seq), min(con)
        rows.append({"n": n, "wall_seq_s": w_seq, "wall_conc_s": w_con,
                     "speedup": w_seq / w_con,
                     "energy_seq_j": e_seq, "energy_conc_j": e_con})
    return rows


def adaptive_convergence(feasible=(1, 2, 4, 8), waves: int = 8):
    """Drive the adaptive pool against a convex synthetic profile; returns
    (per-wave picks, per-wave exploitation choices, known argmin)."""
    def t(n):
        return 1.0 / n + 0.02 * n * n      # convex, argmin at n=4

    def e(n):
        return t(n) * (40.0 + 7.0 * n)

    apool = AdaptiveServingPool(None, None, list(feasible),
                                objective="time",
                                pool_factory=synthetic_pool_factory(t, e))
    choices = []
    for _ in range(waves):
        apool.serve_wave([])
        choices.append(apool.choice)
    picks = [w.n_containers for w in apool.history]
    known = min(feasible, key=t)
    return picks, choices, known


def measure_process_pool(cfg, requests, ns=(1, 2, 4), n_slots=2,
                         max_len=128, reps: int = 2,
                         params_seed: int = 0) -> list[dict]:
    """Thread-per-container (shared runtime) vs process-per-container
    (pinned disjoint cpusets) wall/energy per count. Each lane is warmed
    (compile / spawn+compile) before timing, so rows compare steady-state
    serving, not startup."""
    import jax

    from repro.core.testbed import available_cores
    from repro.models.model import Model
    from repro.serving.process_pool import ProcessContainerPool

    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(params_seed))
    avail = len(available_cores())
    rows = []
    for n in ns:
        tpool = ContainerServingPool(model, params, n,
                                     n_slots_per_container=n_slots,
                                     max_len=max_len)
        tpool.serve_timed(list(requests))              # compile warmup
        thread = min((tpool.serve_timed(list(requests))[2:]
                      for _ in range(reps)))
        shared = n > avail
        with ProcessContainerPool(cfg, n, n_slots_per_container=n_slots,
                                  max_len=max_len, params_seed=params_seed,
                                  allow_shared_cores=shared) as ppool:
            t0 = time.perf_counter()
            ppool.serve_timed(list(requests))          # spawn + compile
            spawn_s = time.perf_counter() - t0
            proc = min((ppool.serve_timed(list(requests))[2:]
                        for _ in range(reps)))
        rows.append({"n": n, "wall_thread_s": thread[0],
                     "wall_process_s": proc[0],
                     "energy_thread_j": thread[1],
                     "energy_process_j": proc[1],
                     "process_spawn_s": spawn_s,
                     "shared_cores": shared})
    return rows


def run_process(quick: bool = False) -> str:
    """The thread-vs-process lane: emits ``BENCH_process_pool.json``."""
    from repro.core.testbed import available_cores

    ns = (1, 2) if quick else (1, 2, 4)
    n_requests, max_new, reps = (6, 4, 1) if quick else (16, 8, 3)
    if quick:
        from repro.configs.registry import get_config as _get
        cfg = _get("qwen3-0.6b-reduced")
    else:
        cfg = bench_config()
    requests = make_requests(cfg, n_requests, max_new, plen_range=(20, 60))
    rows = measure_process_pool(cfg, requests, ns=ns, reps=reps)
    avail = len(available_cores())
    lines = ["# Pool scaling — thread vs process (pinned cpuset) containers",
             "", f"{n_requests} requests × {max_new} new tokens, arch "
             f"{cfg.name}, {avail} host cores; wall excludes spawn+compile "
             "(warm pools)", ""]
    lines += table(
        ["n", "thread wall (s)", "process wall (s)", "thread E (J)",
         "process E (J)", "spawn+compile (s)", "shared cores"],
        [[r["n"], r["wall_thread_s"], r["wall_process_s"],
          r["energy_thread_j"], r["energy_process_j"],
          r["process_spawn_s"], str(r["shared_cores"])] for r in rows])
    save_bench("process_pool", {
        "config": cfg.name, "host_cores": avail,
        "per_n": {str(r["n"]): {k: v for k, v in r.items() if k != "n"}
                  for r in rows}})
    return save("pool_scaling_process", {"measured": rows}, lines)


def measure_streaming(model, params, requests, ns=(1, 2, 4), n_slots=2,
                      max_len=128, reps: int = 3) -> list[dict]:
    """Request-level streaming through the Router: per count, the wave is
    admitted one request at a time (continuous admission, least-loaded +
    bucket-aware dispatch) and consumed as chunk events. Records wall,
    tokens/s and time-to-first-chunk p50/p95 — the latency axis the wave
    API could not even observe."""
    import numpy as np

    from repro.serving import Request, Router
    from repro.serving.backend import ThreadBackend

    def clone(reqs):
        return [Request(r.rid, r.prompt.copy(), r.max_new_tokens)
                for r in reqs]

    rows = []
    for n in ns:
        router = Router(ThreadBackend(model, params, n,
                                      n_slots_per_container=n_slots,
                                      max_len=max_len))
        # compile warmup (prefill buckets + chunk lengths)
        for h in [router.submit(r) for r in clone(requests)]:
            h.result()
        best = None
        for _ in range(reps):
            t0 = time.perf_counter()
            handles = [router.submit(r) for r in clone(requests)]
            router.drain()
            wall = time.perf_counter() - t0
            ttfc = [h.ttfc_s for h in handles if h.ttfc_s is not None]
            toks = sum(len(h.completion.tokens) for h in handles)
            row = {"n": n, "wall_s": wall,
                   "tokens_per_s": toks / wall if wall > 0 else 0.0,
                   "ttfc_p50_s": float(np.percentile(ttfc, 50)),
                   "ttfc_p95_s": float(np.percentile(ttfc, 95))}
            if best is None or row["wall_s"] < best["wall_s"]:
                best = row
        router.close()
        rows.append(best)
    return rows


def run_streaming(quick: bool = False) -> str:
    """The streaming lane: emits ``BENCH_streaming.json`` (time-to-first-
    chunk percentiles + streamed throughput per container count)."""
    import jax

    ns = (1, 2) if quick else (1, 2, 4)
    n_requests, max_new, reps = (6, 4, 1) if quick else (16, 8, 3)
    if quick:
        from repro.configs.registry import get_config as _get
        cfg = _get("qwen3-0.6b-reduced")
    else:
        cfg = bench_config()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    requests = make_requests(cfg, n_requests, max_new, plen_range=(20, 60))
    rows = measure_streaming(model, params, requests, ns=ns, reps=reps,
                             max_len=128)
    lines = ["# Pool scaling — request-level streaming (Router)",
             "", f"{n_requests} requests × {max_new} new tokens, arch "
             f"{cfg.name}; continuous admission, chunk-event consumption; "
             "warm engines (compile excluded)", ""]
    lines += table(
        ["n", "wall (s)", "tok/s", "ttfc p50 (s)", "ttfc p95 (s)"],
        [[r["n"], r["wall_s"], r["tokens_per_s"], r["ttfc_p50_s"],
          r["ttfc_p95_s"]] for r in rows])
    save_bench("streaming", {
        "config": cfg.name,
        "per_n": {str(r["n"]): {k: v for k, v in r.items() if k != "n"}
                  for r in rows}})
    return save("pool_scaling_streaming", {"measured": rows}, lines)


def measure_paged(model, params, requests, ns=(1, 2), n_slots=2,
                  max_len=128, block_size=16, reps: int = 3) -> list[dict]:
    """Dense vs paged KV cache at EQUAL HBM budget (the paged pool
    defaults to the dense footprint: ``n_slots × max_len / block_size``
    blocks). Same streamed wave through the Router both ways; per row:
    tokens/s, time-to-first-chunk p50/p95, and the max sustained
    in-flight per container (``engine.peak_active``) — the paged engine
    must exceed ``n_slots``, the dense engine cannot."""
    import numpy as np

    from repro.serving import EngineConfig, Request, Router
    from repro.serving.backend import ThreadBackend

    def clone(reqs):
        return [Request(r.rid, r.prompt.copy(), r.max_new_tokens)
                for r in reqs]

    rows = []
    for n in ns:
        for cache in ("dense", "paged"):
            ecfg = EngineConfig(n_slots=n_slots, max_len=max_len,
                                cache=cache, block_size=block_size)
            backend = ThreadBackend(model, params, n, config=ecfg)
            router = Router(backend)
            # compile warmup (prefill buckets + chunk lengths)
            for h in [router.submit(r) for r in clone(requests)]:
                h.result()
            best = None
            for _ in range(reps):
                t0 = time.perf_counter()
                handles = [router.submit(r) for r in clone(requests)]
                router.drain()
                wall = time.perf_counter() - t0
                ttfc = [h.ttfc_s for h in handles if h.ttfc_s is not None]
                toks = sum(len(h.completion.tokens) for h in handles)
                row = {"n": n, "cache": cache, "wall_s": wall,
                       "tokens_per_s": toks / wall if wall > 0 else 0.0,
                       "ttfc_p50_s": float(np.percentile(ttfc, 50)),
                       "ttfc_p95_s": float(np.percentile(ttfc, 95))}
                if best is None or row["wall_s"] < best["wall_s"]:
                    best = row
            best["n_slots"] = n_slots
            best["kv_blocks"] = ecfg.resolved_max_blocks
            best["max_in_flight"] = max(e.peak_active
                                        for e in backend.engines)
            router.close()
            rows.append(best)
    return rows


def run_paged(quick: bool = False) -> str:
    """The paged-cache lane: emits ``BENCH_paged.json``. The headline
    number is ``max_in_flight``: at the same HBM budget the paged engine
    packs strictly more concurrent short requests per container than the
    dense engine has slots."""
    import jax

    ns = (1,) if quick else (1, 2)
    n_requests, max_new, reps = (8, 4, 1) if quick else (24, 6, 3)
    if quick:
        from repro.configs.registry import get_config as _get
        cfg = _get("qwen3-0.6b-reduced")
    else:
        cfg = bench_config()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # short prompts + small budgets: the workload the dense layout wastes
    # a full max_len row on, and the paged layout packs by the block
    requests = make_requests(cfg, n_requests, max_new, plen_range=(8, 24))
    rows = measure_paged(model, params, requests, ns=ns, reps=reps)
    n_slots = rows[0]["n_slots"]
    paged_rows = [r for r in rows if r["cache"] == "paged"]
    exceeds = all(r["max_in_flight"] > n_slots for r in paged_rows)
    lines = ["# Pool scaling — dense vs paged KV cache (equal HBM budget)",
             "", f"{n_requests} requests × {max_new} new tokens, arch "
             f"{cfg.name}; n_slots={n_slots}, paged pool = dense footprint "
             f"({paged_rows[0]['kv_blocks']} blocks); streamed via the "
             "Router, warm engines", ""]
    lines += table(
        ["n", "cache", "wall (s)", "tok/s", "ttfc p50 (s)", "ttfc p95 (s)",
         "max in-flight"],
        [[r["n"], r["cache"], r["wall_s"], r["tokens_per_s"],
          r["ttfc_p50_s"], r["ttfc_p95_s"], r["max_in_flight"]]
         for r in rows])
    lines += ["", f"paged max in-flight > n_slots={n_slots} on every "
              f"count: {exceeds}"]
    save_bench("paged", {
        "config": cfg.name, "n_slots": n_slots,
        "kv_blocks": paged_rows[0]["kv_blocks"],
        "paged_exceeds_slots": exceeds,
        "per_n": {f"{r['n']}_{r['cache']}":
                  {k: v for k, v in r.items() if k not in ("n", "cache")}
                  for r in rows}})
    return save("pool_scaling_paged", {"measured": rows}, lines)


def run(quick: bool = False) -> str:
    import jax

    n_requests, max_new, reps = (8, 4, 2) if quick else (16, 8, 3)
    cfg = bench_config()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    requests = make_requests(cfg, n_requests, max_new, plen_range=(20, 60))

    rows = measure_pool(model, params, requests, reps=reps)
    payload: dict = {"measured": rows}
    base = rows[0]["wall_seq_s"]
    md_rows = [[r["n"], r["wall_seq_s"], r["wall_conc_s"], r["speedup"],
                r["wall_conc_s"] / base, r["energy_seq_j"],
                r["energy_conc_j"]] for r in rows]
    lines = ["# Pool scaling — concurrent vs sequential container pool",
             "", f"{n_requests} requests × {max_new} new tokens, "
             f"arch {cfg.name} (bench reduction)", ""]
    lines += table(["n", "seq wall (s)", "conc wall (s)", "speedup",
                    "conc vs n=1 seq", "E seq (J)", "E conc (J)"], md_rows)

    picks, choices, known = adaptive_convergence()
    converged_at = next((i for i in range(len(choices))
                         if all(c == known for c in choices[i:])), None)
    payload["adaptive"] = {"picks": picks, "choices": choices,
                           "known_optimum": known,
                           "converged_at_wave": converged_at}
    lines += ["", "## Adaptive pool on synthetic convex profile "
              f"(known optimum n={known})", "",
              f"per-wave picks:   {picks}",
              f"per-wave choices: {choices}",
              f"converged at wave: {converged_at}"]
    best = max(rows, key=lambda r: r["speedup"])
    save_bench("pool_scaling", {
        "config": cfg.name,
        "best_speedup": best["speedup"], "best_speedup_n": best["n"],
        "adaptive_converged_at_wave": converged_at,
        "per_n": {str(r["n"]): {"wall_seq_s": r["wall_seq_s"],
                                "wall_conc_s": r["wall_conc_s"],
                                "energy_seq_j": r["energy_seq_j"],
                                "energy_conc_j": r["energy_conc_j"]}
                  for r in rows}})
    return save("pool_scaling", payload, lines)


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", "--smoke", action="store_true", dest="quick",
                    help="tiny config / fewer counts (CI smoke)")
    ap.add_argument("--isolation", default="thread",
                    choices=("thread", "process"),
                    help="thread: sequential-vs-concurrent lane (default); "
                         "process: thread-vs-pinned-process lane emitting "
                         "BENCH_process_pool.json")
    ap.add_argument("--streaming", action="store_true",
                    help="request-level streaming lane (Router): "
                         "time-to-first-chunk p50/p95 + streamed tok/s, "
                         "emitting BENCH_streaming.json")
    ap.add_argument("--paged", action="store_true",
                    help="dense vs paged KV cache at equal HBM budget: "
                         "tok/s, ttfc p50/p95, max sustained in-flight, "
                         "emitting BENCH_paged.json")
    args = ap.parse_args()
    if args.paged:
        print(run_paged(quick=args.quick))
    elif args.streaming:
        print(run_streaming(quick=args.quick))
    elif args.isolation == "process":
        print(run_process(quick=args.quick))
    else:
        print(run(quick=args.quick))
