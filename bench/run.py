"""Run one cell of the benchmark once.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. Builds the cell's configuration with bf16
weights drawn on the device from ``--seed``, serves it through the
program's Router (``ThreadBackend`` on one chip, one ``SubmeshBackend``
replica per chip on four), warms up every shape the cell's traffic uses,
offers the cell's traffic for ``--seconds``, then checks the served
tokens against the plain reference. The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics
from a profiler trace of a few seconds in mid-window), ``device``,
``breakdown`` (``--trace 1``) and ``checks``, the numbers compared with
their limits, which are also the last lines of standard error.

Exits non-zero and prints no result without an accelerator or with
fewer chips than the cell needs. JAX's persistent compilation cache is
kept in ``.jax_cache/`` of the checkout, so that only a cell's first run
there compiles.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_S = 4.0          # seconds traced in mid-window with --trace 1
# requests of each size warmed: finishing together, they make the cache
# free their rows in one scrub of that many rows
WARM_GROUPS = 8


class Unfit(Exception):
    """The machine cannot run the cell: no result is printed."""


def _compile_cache() -> None:
    import jax
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def check_devices(chips: int) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    print(f"device: {d.platform} {d.device_kind!r} x{len(devs)}",
          file=sys.stderr)
    if d.platform == "cpu":
        raise Unfit("JAX found no accelerator")
    if len(devs) < chips:
        raise Unfit(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return info


# keys of a configuration's ``serving`` that are the harness's, not the
# engine's: the weights' and the cache's types, and the sizing notes
SERVING_OWN = ("weight_dtype", "kv_dtype", "pool_bytes", "why")
# engine fields the harness sets itself: the cache's type from
# ``kv_dtype``, and greedy decoding, which the logit-gap check needs
ENGINE_SET = ("dtype", "greedy")


def _engine_config(cfg: dict):
    """The engine's configuration from ``serving``: every key that names
    an ``EngineConfig`` field, the harness's own keys left out, any other
    key refused, so that a misspelt key fails at set-up and not as a
    silent default."""
    import dataclasses

    import jax.numpy as jnp

    from repro.serving import EngineConfig
    s = cfg["serving"]
    fields = {f.name for f in dataclasses.fields(EngineConfig)}
    unknown = sorted(k for k in s if k not in SERVING_OWN
                     and (k not in fields or k in ENGINE_SET))
    if unknown:
        raise ValueError(
            f"unknown serving keys {unknown}: each key is an EngineConfig "
            f"field the harness does not set itself "
            f"({sorted(fields - set(ENGINE_SET))}) or one of the harness's "
            f"own {list(SERVING_OWN)}")
    return EngineConfig(dtype=getattr(jnp, s["kv_dtype"]), greedy=True,
                        **{k: v for k, v in s.items() if k in fields})


def build(cell, seed: int, containers: int):
    """The model, its weights on the device and the serving backend."""
    import jax
    import jax.numpy as jnp

    from bench.models.common import check_program_tree, seed_key
    from repro.models.model import Model
    from repro.serving.backend import SubmeshBackend, ThreadBackend
    mod, cfg = cell.model, cell.config
    model = Model(mod.program_config(cfg, cell.config_name))
    wdtype = getattr(jnp, cfg["serving"]["weight_dtype"])
    make = jax.jit(lambda k: mod.to_program(
        mod.init_weights(cfg, k, wdtype), cfg))
    params = jax.block_until_ready(make(seed_key(seed)))
    check_program_tree(params, jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), wdtype)))
    config = _engine_config(cfg)
    if cell.chips == 1 and containers == 1:
        backend = ThreadBackend(model, params, 1, config=config)
    else:
        from repro.launch.mesh import make_container_meshes
        meshes = make_container_meshes(cell.chips, containers)
        backend = SubmeshBackend(model, params, containers, meshes=meshes,
                                 config=config)
    return model, params, backend


def warm_requests(cell, vocab: int) -> list:
    """Every shape the cell's traffic makes the engine compile: a prompt
    in each power-of-two length bucket the mix reaches, and groups of 1
    to ``WARM_GROUPS`` requests that finish on the same step."""
    import numpy as np

    from repro.serving import Request
    lo = min(c["prompt"].lo for c in cell.mix.classes)
    hi = max(c["prompt"].hi for c in cell.mix.classes)
    lens, b = [], 16
    while b < 2 * hi:
        if b >= lo:
            lens.append(max(lo, min(b, hi)))
        b *= 2
    rng = np.random.default_rng(0)
    out, rid = [], -1
    for k in range(1, WARM_GROUPS + 1):
        for _ in range(k):
            n = lens[(-rid - 1) % len(lens)]
            out.append(Request(rid=rid, prompt=rng.integers(
                0, vocab, (n,), dtype=np.int32), max_new_tokens=2 + 2 * k))
            rid -= 1
    return out


def warm_up(backend, reqs: list) -> None:
    """Serve the warm-up set on every container, straight through the
    backend, until each request is done."""
    import dataclasses

    from repro.serving.events import DoneEvent, FailedEvent
    want = set()
    for cid in range(backend.capacity):
        for r in reqs:
            rr = dataclasses.replace(r, rid=r.rid - 100_000 * cid)
            want.add(rr.rid)
            backend.submit(cid, rr)
    while want:
        for ev in backend.poll():
            if isinstance(ev, FailedEvent):
                raise RuntimeError(f"warm-up request failed: {ev.reason}")
            if isinstance(ev, DoneEvent):
                want.discard(ev.rid)
        if backend.failures:
            raise RuntimeError(backend.failures[0].message)


def set_up(cell, seed: int, containers: int):
    """``build``, then the warm-up on every container: what a run counts
    as set-up."""
    model, params, backend = build(cell, seed, containers)
    warm_up(backend, warm_requests(cell, int(cell.config["vocab_size"])))
    return model, params, backend


class _Tracer:
    """Starts and stops the profiler around the middle of the window."""

    def __init__(self, window_s: float, directory: str):
        span = min(TRACE_S, window_s / 2)
        self.lo = window_s / 2 - span / 2
        self.hi = window_s / 2 + span / 2
        self.dir = directory
        self.state = 0
        self.span = None
        self.annotation = None
        self.stalls: list[tuple[float, float]] = []  # profiler start/stop

    def __call__(self, t: float) -> None:
        import jax
        if self.state == 0 and t >= self.lo:
            t0 = time.perf_counter()
            jax.profiler.start_trace(self.dir)
            self.stalls.append((t, t + time.perf_counter() - t0))
            self.annotation = jax.profiler.TraceAnnotation("bench.traced")
            self.annotation.__enter__()
            self.span = [self.stalls[-1][1], None]
            self.state = 1
        elif self.state == 1 and t >= self.hi:
            self.stop(t)

    def stop(self, t: float) -> None:
        import jax
        if self.state != 1:
            return
        self.annotation.__exit__(None, None, None)
        self.span[1] = t
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        self.stalls.append((t, t + time.perf_counter() - t0))
        self.state = 2


class _CompileCounter:
    """Counts programs lowered while ``active``."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.active and event == self.EVENT:
            self.count += 1


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def run_cell(cell, seed: int, seconds: float, trace: bool,
             need_chip: bool = True) -> tuple[dict, object, list]:
    """One run of ``cell``: the result object, the run's request log and
    the sample of it that ``correct`` was decided on."""
    import jax
    import numpy as np

    from bench import correct, drive, readers, spec, stats, traffic
    from bench import trace as trace_lib
    from repro.serving import Router
    device = (check_devices(cell.chips) if need_chip else
              {"platform": jax.devices()[0].platform,
               "kind": jax.devices()[0].device_kind,
               "count": len(jax.devices())})
    peaks = spec.peaks(device["kind"]) if need_chip else None
    containers = cell.mix.containers or cell.chips
    counter = _CompileCounter()
    model, params, backend = set_up(cell, seed, containers)
    vocab = int(cell.config["vocab_size"])
    setup_s = time.perf_counter() - T_START
    print(f"setup: {setup_s:.3f}s", file=sys.stderr)

    rows = backend.config.n_rows * containers
    if cell.mix.loop == "open":
        reqs = traffic.schedule(cell.mix, seed, seconds, vocab)
        clients = 0
    else:
        clients = int(round(cell.mix.clients_per_row * rows))
        reqs = traffic.schedule(cell.mix, seed, seconds, vocab, n=4096)
    tracer = None
    tmp = None
    if trace:
        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        tracer = _Tracer(seconds, tmp)

    def on_tick(t: float) -> None:
        counter.active = 0.0 <= t < seconds
        if tracer is not None:
            tracer(t)

    router = Router(backend)
    try:
        log = drive.run(router, reqs, loop=cell.mix.loop, window_s=seconds,
                        ramp_s=cell.mix.ramp_s, clients=clients,
                        on_tick=on_tick)
    finally:
        counter.active = False
        if tracer is not None:
            tracer.stop(seconds)
    log.compiles_in_window = counter.count
    failures = list(backend.failures)
    device["memory_peak_bytes"] = memory_peak(jax.devices()[:cell.chips])

    window = stats.window_requests(log) if cell.mix.loop == "open" else [
        r for r in log.records.values() if r.submit_s is not None
        and 0.0 <= r.submit_s < seconds]
    attempted = len(window)
    failed = sum(1 for r in window if r.failed is not None)
    e2e = stats.end_to_end(log)
    e2e["setup_s"] = setup_s

    result: dict = {"attempted": attempted, "failed": failed}
    if trace and tracer.span is not None and tracer.span[1] is not None:
        log.trace_span = tuple(tracer.span)
        log.stalls = list(tracer.stalls)
        files = [os.path.join(dp, f) for dp, _, fs in os.walk(tmp)
                 for f in fs if f.endswith(".xplane.pb")]
        events = trace_lib.load(files[0])
        reduced = trace_lib.reduce(events)
        n_ops = sum(len(v) for v in reduced.ops.values())
        print(f"trace: {reduced.window_s:.3f}s traced, {n_ops} device ops "
              f"in it on {len(reduced.ops)} chip(s), busy "
              f"{reduced.busy_s:.3f}s", file=sys.stderr)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    else:
        reduced = None
    if tmp is not None:
        shutil.rmtree(tmp, ignore_errors=True)

    sample = correct.sample(log, seed)
    router.close()
    del router, backend, params, model
    gc.collect()

    cfg, mod = cell.config, cell.model
    ctx = readers.Context(
        log=log, trace=reduced, work=mod.work(cfg), peaks=peaks or {},
        chips=cell.chips,
        kv_bytes=np.dtype(cfg["serving"]["kv_dtype"]).itemsize,
        act_bytes=np.dtype(cfg["serving"]["weight_dtype"]).itemsize)
    metrics = {}
    if trace:
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}

    ref = mod.Reference(cfg, mod.reference_weights(cfg, seed))
    gaps = correct.logit_gaps(log, sample, ref,
                              int(cfg["serving"]["max_len"]))
    del ref
    limit = float(cfg["limits"]["logit_gap"])
    gap = max(gaps) if gaps else float("inf")
    print(f"sample: {len(sample)} requests, "
          f"{sum(len(r.tokens) for r in sample)} served tokens",
          file=sys.stderr)
    checks = {
        "logit_gap": {"value": gap, "limit": limit},
        "failed_requests": {"value": failed, "limit": 0},
        "container_failures": {"value": len(failures), "limit": 0},
    }
    ok = gap <= limit and failed == 0 and not failures and attempted > 0
    result.update({"correct": bool(ok), "metrics": metrics,
                   "device": device, "checks": checks})
    return result, log, sample


def emit(result: dict) -> None:
    """Print the compared numbers as the last lines of standard error and
    the result as the last line of standard output, ``checks`` last."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    order = ["correct", "attempted", "failed", "metrics", "device",
             "breakdown", "checks"]
    print(json.dumps({k: result[k] for k in order if k in result}))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seconds <= 0:
        ap.error("--seconds must be > 0")
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    from bench import spec
    try:
        import repro  # noqa: F401  (the system under test, in src/)
    except ImportError as e:
        print(f"the system under test is not in this checkout: {e}",
              file=sys.stderr)
        return 1
    try:
        cell = spec.load_cell(args.workload)
    except (KeyError, FileNotFoundError) as e:
        print(f"cannot resolve {args.workload!r}: {e}", file=sys.stderr)
        return 2
    _compile_cache()
    try:
        result, _, _ = run_cell(cell, args.seed, args.seconds,
                                bool(args.trace))
    except Unfit as e:
        print(f"cannot run {cell.name}: {e}", file=sys.stderr)
        return 1
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
