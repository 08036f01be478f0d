"""Serving launcher: request-level streaming Router over containers,
with the online divide-and-save scheduler.

The serving surface is the ``Router`` (serving/router.py): requests are
admitted one at a time (least-loaded + bucket-aware dispatch across the
containers), completions stream back as typed per-chunk events, and —
when the container count is left to the scheduler — the
``DivideAndSaveScheduler`` observes sliding windows of (time, energy,
tokens/s, time-to-first-chunk) stats and resizes the container count
between windows. ``--no-stream`` serves the same traffic through the
legacy wave shim (``serve_wave`` / the pool facades) instead.

Container isolation is picked exactly as before: the default is a
``ThreadBackend`` (engines overlap in this process); ``--submesh``
places each container on a disjoint slice of the host's jax devices
(fake a pod on CPU with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``);
``--isolation process`` runs one OS process per container pinned to a
disjoint core set before jax initialises (the paper's
``docker run --cpus=C/n``). Process isolation is CPU-only: a TPU
belongs to one process, so on a TPU host it is refused and
``--submesh`` is the container.

``--arch`` names the config exactly as the registry does: ``qwen3-0.6b``
is the published widths, ``qwen3-0.6b-reduced`` the CPU-sized toy the
examples below use. A container failure (an engine step that raised)
ends the run with a non-zero exit code, even when its requests were
retried to completion.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b-reduced \
        --containers 4 --requests 16 --stream
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b-reduced \
        --waves 8 --objective time
    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
        python -m repro.launch.serve --arch qwen3-0.6b-reduced \
        --containers 2 --submesh
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b-reduced \
        --containers 2 --isolation process --total-cores 2 --stream
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.configs.registry import ARCH_NAMES, get_config
from repro.core.containers import feasible_counts
from repro.core.testbed import available_cores
from repro.launch.mesh import make_container_meshes
from repro.models.model import Model
from repro.serving import ChunkEvent, EngineConfig, Request, Router
from repro.serving.adaptive import AdaptiveServingPool
from repro.serving.backend import (ProcessBackend, SubmeshBackend,
                                   ThreadBackend, process_isolation_refusal)
from repro.serving.pool import ContainerServingPool
from repro.serving.process_pool import ProcessContainerPool
from repro.workload.replay import replay
from repro.workload.slo import SLOClass, SLOSpec
from repro.workload.traces import PRESETS, load_or_synthesize


def _engine_config(args) -> EngineConfig:
    """The per-container engine configuration the flags describe — one
    frozen EngineConfig threaded through every backend flavour."""
    return EngineConfig(n_slots=args.slots, cache=args.cache,
                        block_size=args.block_size,
                        max_blocks=args.max_blocks,
                        prefix_cache=args.prefix_cache)


def _make_backend(args, cfg, model, params, n, units):
    """One container backend per isolation flavour — the Router is
    agnostic, so all the flag handling collapses here."""
    engine_cfg = _engine_config(args)
    if args.isolation == "process":
        return ProcessBackend(cfg, n, total_cores=units, params_seed=0,
                              config=engine_cfg,
                              max_respawns=args.max_respawns)
    if args.submesh:
        return SubmeshBackend(model, params, n,
                              meshes=make_container_meshes(units, n),
                              concurrent=not args.sequential,
                              config=engine_cfg,
                              max_respawns=args.max_respawns)
    return ThreadBackend(model, params, n,
                         concurrent=not args.sequential,
                         config=engine_cfg,
                         max_respawns=args.max_respawns)


def _router_fault_kw(args) -> dict:
    """The Router's fault-tolerance knobs from the serving flags."""
    return dict(max_retries=args.max_retries,
                request_deadline_s=args.deadline_s,
                max_queue=args.max_queue,
                shed_p95_s=args.shed_p95_s)


def _stream_requests(router: Router, requests, verbose_chunks: bool):
    """Continuous admission: submit everything, then consume the streams,
    printing chunk arrivals as they land."""
    handles = [router.submit(r) for r in requests]
    for h in handles:
        parts = []
        for ev in h.stream():
            if isinstance(ev, ChunkEvent):
                parts.append(list(ev.tokens))
        if verbose_chunks:
            chunks = " | ".join(" ".join(map(str, p)) for p in parts)
            ttfc = (f"{h.ttfc_s * 1e3:6.1f}ms" if h.ttfc_s is not None
                    else "   n/a")        # zero-budget: DoneEvent only
            print(f"  rid {h.rid} [container {h.container_id}] "
                  f"ttfc {ttfc}  chunks: {chunks}")
    return handles


def _exit_on_container_failures(router: Router) -> None:
    """A container whose engine step raised fails the run, even when the
    Router retried its requests to completion elsewhere."""
    fails = router.container_failures
    if fails:
        raise SystemExit(f"{len(fails)} container failure(s); the first:\n"
                         f"{fails[0].message}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b",
                    choices=ARCH_NAMES + tuple(f"{a}-reduced"
                                               for a in ARCH_NAMES),
                    help="registry name; append -reduced for the "
                         "CPU-sized variant")
    ap.add_argument("--containers", type=int, default=0,
                    help="0 = let the scheduler choose online")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--cache", default="dense", choices=("dense", "paged"),
                    help="KV cache layout: dense n_slots rows (baseline) "
                         "or the paged block cache (in-flight bounded by "
                         "the block budget, not --slots)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV block (paged cache)")
    ap.add_argument("--max-blocks", type=int, default=None,
                    help="physical KV blocks per container (paged; "
                         "default: the dense footprint "
                         "slots*max_len/block_size)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="copy-on-write prefix sharing in the paged "
                         "cache: requests whose leading prompt blocks "
                         "hash-match cached blocks skip that much "
                         "prefill (requires --cache paged; no-op for "
                         "architectures the sharing gate excludes)")
    ap.add_argument("--prefix-cached-blocks", type=int, default=0,
                    help="resident prefix-cache working set budgeted "
                         "on top of the kv pool when sizing feasible "
                         "container counts (online mode)")
    ap.add_argument("--waves", type=int, default=6,
                    help="traffic waves (adaptive: scheduler windows)")
    ap.add_argument("--objective", default="energy",
                    choices=("energy", "time"))
    ap.add_argument("--stream", action="store_true", default=True,
                    help="request-level streaming via the Router "
                         "(default)")
    ap.add_argument("--no-stream", dest="stream", action="store_false",
                    help="serve through the legacy wave shim instead")
    ap.add_argument("--print-chunks", action="store_true",
                    help="print every request's chunk arrivals")
    ap.add_argument("--sequential", action="store_true",
                    help="disable container concurrency (baseline)")
    ap.add_argument("--units", type=int, default=8,
                    help="resource units to factorise (cores / chips)")
    ap.add_argument("--submesh", action="store_true",
                    help="place each container on a disjoint sub-mesh of "
                         "the host's jax devices (see XLA_FLAGS above)")
    ap.add_argument("--isolation", default="thread",
                    choices=("thread", "process"),
                    help="thread: engines overlap in this process "
                         "(baseline); process: one pinned OS process per "
                         "container — the paper's --cpus shares")
    ap.add_argument("--total-cores", type=int, default=None,
                    help="CPU cores to carve among process containers "
                         "(default: all cores this process may use)")
    ap.add_argument("--max-retries", type=int, default=1,
                    help="re-dispatches per request after a container "
                         "failure before it fails typed")
    ap.add_argument("--max-respawns", type=int, default=2,
                    help="automatic container respawns before the "
                         "circuit breaker leaves it dead")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request deadline (seconds, end-to-end "
                         "across retries; default none)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="admission bound: shed new requests once this "
                         "many are in flight (default unbounded)")
    ap.add_argument("--shed-p95-s", type=float, default=None,
                    help="shed new requests while the recent "
                         "time-to-first-chunk p95 exceeds this "
                         "(seconds; default never)")
    ap.add_argument("--trace", default=None,
                    help="replay a workload trace open-loop instead of "
                         "synthetic waves: a preset name "
                         f"({', '.join(sorted(PRESETS))}) or a trace "
                         "JSONL path")
    ap.add_argument("--trace-seed", type=int, default=0,
                    help="synthesis seed for a preset --trace")
    ap.add_argument("--time-scale", type=float, default=1.0,
                    help="compress trace time (10 = a 600s trace "
                         "replays in 60s; arrival pattern preserved, "
                         "absolute rates scaled)")
    ap.add_argument("--slo-ttfc-p95", type=float, default=None,
                    help="single-class SLO: time-to-first-chunk p95 "
                         "target in seconds; switches the scheduler to "
                         "the energy_under_slo objective")
    ap.add_argument("--priority-classes", default=None,
                    help="multi-class SLO spec 'interactive:0.5,"
                         "batch:4.0[:queue_frac]' — rank follows the "
                         "listed order; implies energy_under_slo")
    ap.add_argument("--tenant-quota", type=int, default=None,
                    help="max in-flight requests per tenant (SLO mode)")
    args = ap.parse_args()
    if args.isolation == "process" and args.submesh:
        ap.error("--submesh needs one process owning all devices; pick "
                 "either --submesh or --isolation process")
    use_compile_cache()
    if args.isolation == "process":
        refusal = process_isolation_refusal()
        if refusal:
            ap.error(refusal)

    cfg = get_config(args.arch)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    units = args.units
    if args.isolation == "process":
        # factorise cores that actually exist: the process pool carves
        # REAL cpusets, so the unit budget is the core budget
        avail = len(available_cores())
        units = min(units, args.total_cores or avail, avail)
        print(f"process isolation over {units} cores")
    if args.submesh:
        # factorise devices that actually exist: largest power of two the
        # pod (or the CPU device-count override) provides, clamped by an
        # explicit --units so a smaller requested factorisation is honoured
        units = 1 << (min(args.units, jax.device_count()).bit_length() - 1)
        print(f"submesh placement over {units} of {jax.device_count()} "
              f"devices")

    # SLO vocabulary from the flags: a multi-class spec wins; a bare
    # p95 target becomes a single-class spec. Either switches the
    # scheduler objective to energy_under_slo (the Router derives the
    # binding constraint from the spec itself).
    slo = None
    if args.priority_classes:
        slo = SLOSpec.parse(args.priority_classes)
    elif args.slo_ttfc_p95 is not None:
        slo = SLOSpec((SLOClass(ttfc_p95_s=args.slo_ttfc_p95),))

    if args.trace is not None:
        _serve_trace(args, cfg, model, params, units, slo)
        return

    def batch_of_requests(base):
        return [Request(rid=base + i,
                        prompt=rng.integers(0, cfg.vocab_size, (8,),
                                            dtype=np.int32),
                        max_new_tokens=args.max_new)
                for i in range(args.requests)]

    if args.containers:
        n = args.containers
        meshes = None
        if args.stream:
            backend = _make_backend(args, cfg, model, params, n, units)
            meshes = getattr(backend, "meshes", None)
            with Router(backend, **_router_fault_kw(args)) as router:
                handles = _stream_requests(router, batch_of_requests(0),
                                           args.print_chunks)
                # a second pass through the wave shim for the aggregate
                # accounting line (warm engines — no recompiles)
                done, per, wall, energy = router.serve_wave(
                    batch_of_requests(len(handles)))
                ttfc = sorted(h.ttfc_s for h in handles
                              if h.ttfc_s is not None)
                if ttfc:
                    print(f"streamed {len(handles)} requests: ttfc p50 "
                          f"{ttfc[len(ttfc) // 2] * 1e3:.1f}ms  max "
                          f"{ttfc[-1] * 1e3:.1f}ms")
                _print_wave(args, n, done, per, wall, energy, meshes,
                            router.backend)
            _exit_on_container_failures(router)
            return
        backend = _make_backend(args, cfg, model, params, n, units)
        meshes = getattr(backend, "meshes", None)
        if args.isolation == "process":
            pool = ProcessContainerPool(cfg, n, backend=backend)
        else:
            pool = ContainerServingPool(model, params, n, backend=backend)
        done, per, wall, energy = pool.serve_timed(batch_of_requests(0))
        _print_wave(args, n, done, per, wall, energy, meshes,
                    getattr(pool, "backend", None))
        if args.isolation == "process":
            pool.close()
        return

    # online mode: the scheduler probes container counts, bounded by the
    # memory-feasible factorisations of the host; a paged engine budgets
    # its block pool too (the block-granular memory model), so the
    # scheduler searches the frontier the engine actually allocates
    engine_cfg = _engine_config(args)
    kv_kw = ({"kv_blocks": engine_cfg.resolved_max_blocks,
              "block_size": engine_cfg.block_size,
              "prefix_cached_blocks": args.prefix_cached_blocks}
             if args.cache == "paged" else {})
    feasible = feasible_counts(cfg, units, **kv_kw) or [1]
    if args.stream:
        # windowed adaptation: no explicit waves — requests stream in,
        # the scheduler observes each window and resizes between windows
        router = Router(
            backend_factory=lambda n: _make_backend(args, cfg, model,
                                                    params, n, units),
            feasible_counts=feasible, objective=args.objective,
            epsilon=0.2, window=args.requests, **_router_fault_kw(args))
        for wave in range(args.waves):
            _stream_requests(router, batch_of_requests(
                wave * args.requests), args.print_chunks)
        _print_windows(router.history)
        print(f"feasible counts: {feasible}")
        print(f"converged choice: n={router.choice}")
        print("scheduler summary:", router.scheduler.summary())
        router.close()
        _exit_on_container_failures(router)
        return
    apool = AdaptiveServingPool(model, params, feasible,
                                objective=args.objective, epsilon=0.2,
                                n_slots_per_container=args.slots,
                                concurrent=not args.sequential,
                                submesh_devices=units if args.submesh
                                else None,
                                isolation=args.isolation,
                                total_cores=units if args.isolation ==
                                "process" else None)
    for wave in range(args.waves):
        apool.serve_wave(batch_of_requests(wave * args.requests))
        w = apool.history[-1]
        print(f"wave {w.wave}: n={w.n_containers} wall {w.wall_s:.2f}s "
              f"{w.tokens_per_s:.1f} tok/s energy {w.energy_j:.1f}J "
              f"p50 {w.latency_p50_s:.3f}s p95 {w.latency_p95_s:.3f}s")
    print(f"feasible counts: {feasible}")
    print(f"converged choice: n={apool.choice}")
    print("scheduler summary:", apool.scheduler.summary())
    apool.close()


def _print_windows(history) -> None:
    for w in history:
        print(f"window {w.window}: n={w.n_containers} "
              f"wall {w.wall_s:.2f}s {w.tokens_per_s:.1f} tok/s "
              f"energy {w.energy_j:.1f}J "
              f"ttfc p50 {w.ttfc_p50_s:.3f}s p95 {w.ttfc_p95_s:.3f}s "
              f"lat p50 {w.latency_p50_s:.3f}s"
              + (f" retries {w.n_retries} failed {w.n_failed} "
                 f"shed {w.n_shed}"
                 if w.n_retries or w.n_failed or w.n_shed else ""))
        for name, cw in sorted(w.per_class.items()):
            tgt = (f" target {cw.target_ttfc_p95_s:.3f}s "
                   f"{'MET' if cw.attained else 'VIOLATED'}"
                   if cw.attained is not None else "")
            print(f"    [{name}] done {cw.n_done} shed {cw.n_shed} "
                  f"failed {cw.n_failed} "
                  f"ttfc p95 {cw.ttfc_p95_s:.3f}s{tgt}")


def _serve_trace(args, cfg, model, params, units, slo) -> None:
    """Open-loop trace replay through the live Router — the launcher
    face of ``workload.replay``. Online (scheduler-resized) when
    ``--containers 0``, fixed count otherwise."""
    trace = load_or_synthesize(args.trace, seed=args.trace_seed)
    objective = "energy_under_slo" if slo is not None else args.objective
    router_kw = dict(**_router_fault_kw(args), slo=slo,
                     tenant_quota=args.tenant_quota,
                     window=args.requests, window_s=5.0)
    if args.containers:
        backend = _make_backend(args, cfg, model, params,
                                args.containers, units)
        router = Router(backend, **router_kw)
    else:
        engine_cfg = _engine_config(args)
        kv_kw = ({"kv_blocks": engine_cfg.resolved_max_blocks,
                  "block_size": engine_cfg.block_size,
                  "prefix_cached_blocks": args.prefix_cached_blocks}
                 if args.cache == "paged" else {})
        feasible = feasible_counts(cfg, units, **kv_kw) or [1]
        router = Router(
            backend_factory=lambda n: _make_backend(args, cfg, model,
                                                    params, n, units),
            feasible_counts=feasible, objective=objective,
            epsilon=0.1, **router_kw)
    with router:
        report = replay(trace, router, time_scale=args.time_scale,
                        vocab_size=cfg.vocab_size)
        _print_windows(router.history)
    print(f"trace {report.trace} (seed {report.seed}, "
          f"time_scale {report.time_scale:g}): "
          f"{report.n_done}/{report.n_requests} done, "
          f"{report.n_shed} shed, {report.n_failed} failed in "
          f"{report.duration_s:.1f}s")
    print(f"goodput {report.goodput_rps:.2f} rps  "
          f"ttfc p95 {report.ttfc_p95_s:.3f}s  "
          f"energy/done {report.energy_per_done_j:.2f}J  "
          f"counts {list(report.counts_visited)} -> n={report.final_n}")
    for name, cw in sorted(report.per_class.items()):
        tgt = (f" target {cw.target_ttfc_p95_s:.3f}s "
               f"{'MET' if cw.attained else 'VIOLATED'}"
               if cw.attained is not None else "")
        print(f"  [{name}] done {cw.n_done} shed {cw.n_shed} "
              f"failed {cw.n_failed} ttfc p95 {cw.ttfc_p95_s:.3f}s{tgt}")
    _exit_on_container_failures(router)


def _print_wave(args, n, done, per, wall, energy, meshes, backend) -> None:
    toks = sum(len(c.tokens) for c in done)
    mode = (args.isolation if args.isolation == "process" else
            ("sequential" if args.sequential else "concurrent"))
    if args.stream:
        mode += "+stream"
    print(f"n={n} ({mode}): {len(done)} requests, "
          f"{toks} tokens in {wall:.2f}s ({toks/wall:.1f} tok/s, "
          f"~{energy:.1f}J)")
    for r in per:
        devs = ""
        if meshes is not None:
            ids = sorted(d.id for d in meshes[r.container_id].devices.flat)
            devs = f" devices {ids}"
        if args.isolation == "process" and backend is not None:
            cores = backend.reported_core_sets[r.container_id]
            devs = f" cores {sorted(cores)}"
        print(f"  container {r.container_id}: {r.n_requests} reqs "
              f"wall {r.wall_s:.2f}s busy {r.busy_s:.2f}s "
              f"{r.tokens_per_s:.1f} tok/s ~{r.energy_j:.1f}J "
              f"p50 {r.latency_p50_s:.3f}s p95 {r.latency_p95_s:.3f}s"
              f"{devs}")


if __name__ == "__main__":
    main()
