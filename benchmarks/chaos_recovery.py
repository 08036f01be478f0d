"""Chaos recovery — the fault-tolerance claims, measured.

Two headline numbers for the ``BENCH_chaos.json`` perf trajectory:

  (a) **recovery latency**: a scripted ``FaultPlan`` kills one of two
      containers mid-stream; the Router re-dispatches the lost requests
      to the survivor (and the supervisor respawns the casualty). The
      metric is the wall time from the ``ContainerFailure`` record to the
      last lost request's completion — how long a container crash is
      visible in request latency.
  (b) **shed rate under overload**: a burst far beyond ``max_queue`` hits
      a single container; admission control must shed the excess as fast
      typed rejections while every admitted request still completes. The
      metric is the shed fraction plus the rejection turnaround (shed
      requests must fail in microseconds, not queue).

Both run the in-process ``ThreadBackend`` (deterministic, no spawn cost)
with ``chunk_tokens=1`` so step-indexed faults land mid-stream by
construction. ``--smoke`` shrinks the workload for the CI chaos lane.
"""
from __future__ import annotations

import argparse
import time

from benchmarks.common import make_requests, save, save_bench, table
from repro.configs.base import reduce_config
from repro.configs.registry import get_config
from repro.models.model import Model
from repro.serving import Fault, FaultPlan, RejectedEvent, Router
from repro.serving.backend import ThreadBackend
from repro.serving.engine import EngineConfig


def bench_config(smoke: bool):
    if smoke:
        return get_config("qwen3-0.6b-reduced")
    return reduce_config(get_config("qwen3-0.6b"), n_layers=4, d_model=512,
                         n_heads=8, n_kv_heads=4, d_ff=2048,
                         vocab_size=8192)


def bench_recovery(model, params, n_requests: int, max_new: int) -> dict:
    """Kill container 0 after 3 macro-steps; how long until its lost
    requests are done on the survivor/respawn?"""
    cfg = model.cfg
    plan = FaultPlan((Fault("kill", container_id=0, after_steps=3),))
    config = EngineConfig(n_slots=2, max_len=128, chunk_tokens=1)
    backend = ThreadBackend(model, params, 2, config=config,
                            fault_plan=plan, max_respawns=2)
    reqs = make_requests(cfg, n_requests, max_new)
    with Router(backend, max_retries=2) as router:
        t0 = time.perf_counter()
        handles = {r.rid: router.submit(r) for r in reqs}
        router.drain()
        wall = time.perf_counter() - t0
        assert router.container_failures, "the injected kill never fired"
        fail = router.container_failures[0]
        lost = set(fail.lost_rids)
        completed = {rid: h for rid, h in handles.items()
                     if h.completion is not None}
        assert set(completed) == set(handles), "requests lost to the kill"
        recovery_s = (max(completed[rid].done_at for rid in lost)
                      - fail.time_s) if lost else 0.0
    return {"wall_s": wall, "n_requests": n_requests,
            "n_lost": len(lost), "n_retried": router.retry_total,
            "recovery_latency_s": recovery_s}


def bench_overload(model, params, n_requests: int, max_queue: int,
                   max_new: int) -> dict:
    """One container, a burst of ``n_requests`` against ``max_queue``
    admission: shed fraction + rejection turnaround, and every admitted
    request must still complete."""
    cfg = model.cfg
    config = EngineConfig(n_slots=2, max_len=128)
    backend = ThreadBackend(model, params, 1, config=config)
    reqs = make_requests(cfg, n_requests, max_new, seed=1)
    with Router(backend, max_queue=max_queue) as router:
        t0 = time.perf_counter()
        admitted, shed_turnaround = [], []
        for r in reqs:
            ts = time.perf_counter()
            h = router.submit(r)
            if isinstance(h.failure, RejectedEvent):
                shed_turnaround.append(time.perf_counter() - ts)
            else:
                admitted.append(h)
        router.drain()
        wall = time.perf_counter() - t0
        assert all(h.completion is not None for h in admitted)
        n_shed = router.shed_total
    return {"overload_wall_s": wall, "n_burst": n_requests,
            "max_queue": max_queue, "n_admitted": len(admitted),
            "n_shed": n_shed, "shed_rate": n_shed / n_requests,
            "shed_turnaround_s": (max(shed_turnaround)
                                  if shed_turnaround else 0.0)}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config / small workload (CI chaos lane)")
    args = ap.parse_args()
    cfg = bench_config(args.smoke)
    model = Model(cfg)
    import jax
    params = model.init(jax.random.PRNGKey(0))
    n_req, max_new = (6, 8) if args.smoke else (16, 24)
    rec = bench_recovery(model, params, n_req, max_new)
    over = bench_overload(model, params, n_requests=4 * n_req,
                          max_queue=max(2, n_req // 2), max_new=max_new)
    payload = {"smoke": args.smoke, "recovery": rec, "overload": over}
    lines = ["# Chaos recovery", "",
             "## Recovery after an injected container kill", ""]
    lines += table(list(rec), [list(rec.values())])
    lines += ["", "## Load-shedding under a burst", ""]
    lines += table(list(over), [list(over.values())])
    print(save("chaos_recovery", payload, lines))
    save_bench("chaos", {**rec, **over})


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    main()
