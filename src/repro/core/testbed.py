"""CPU container testbed — the paper's mechanism, literally.

``docker run --cpus=C/n`` is reproduced as an OS process pinned to a
disjoint set of C/n cores (``os.sched_setaffinity``, applied BEFORE jax
initialises its threadpool, so XLA's worker threads inherit the cpuset —
the in-process equivalent of the cgroup cpu limit). Two workloads run on
that harness:

  * the YOLOv4-tiny-shaped convolutional detector below, frame-by-frame
    over a synthetic video split into equal segments (§V steps 1-4), and
  * full ``ServingEngine`` containers (serving/process_pool.py), which
    reuse ``assign_core_sets`` + ``spawn_pinned`` from this module.

Core carve-up is centralised in ``assign_core_sets``: per-container core
sets are pairwise disjoint **by construction and by assertion** — asking
for more containers than cores raises instead of silently time-sharing
(the historic modulo wrap corrupted both the isolation claim and
``busy_core_seconds``). Pass ``allow_shared=True`` to opt into round-robin
shared cores explicitly: the analogue of fractional ``--cpus < 1`` shares,
where the kernel time-slices and the isolation claim is knowingly waived.

Energy on the host is modelled (no power sensor in this container):
``P(t) = P_IDLE + P_CORE · busy_cores(t)`` integrated over the run — the
same activity-based bookkeeping the paper measures with the Jetson INA
sensors. Constants below are host-class x86 figures; they cancel in the
normalised (vs 1-container benchmark) plots the paper reports.
"""
from __future__ import annotations

import dataclasses
import multiprocessing as mp
import os
import time
from typing import Callable, Sequence

import numpy as np

from repro.core import splitter

P_IDLE_W = 40.0    # host idle draw
P_CORE_W = 3.5     # per busy core

_FRAME_SHAPE = (128, 128, 3)
# YOLOv4-tiny-ish backbone: stride-2 conv stages + 1x1 head (CSP blocks
# collapsed — we need the compute/memory character, not mAP)
_CHANNELS = (16, 32, 64, 128, 256)


# ---------------------------------------------------------------------------
# reusable pinned-worker harness
# ---------------------------------------------------------------------------
def available_cores() -> list[int]:
    """Cores this process may use — ``sched_getaffinity`` where it exists
    (Linux: respects cgroup/container cpusets), ``cpu_count`` elsewhere so
    non-Linux dev hosts still get a sane carve-up (children then run
    unpinned, see ``_pinned_main``)."""
    try:
        return sorted(os.sched_getaffinity(0))
    except AttributeError:
        return list(range(os.cpu_count() or 1))


def assign_core_sets(n_containers: int, total_cores: int | None = None,
                     avail: Sequence[int] | None = None,
                     allow_shared: bool = False) -> list[frozenset[int]]:
    """Carve the host's cores into one set per container — the
    ``docker run --cpus`` allocation as explicit cpusets.

    With ``n_containers <= cores`` the sets are contiguous, equal-size
    (``cores // n``) and pairwise disjoint; disjointness is asserted, not
    assumed, because it IS the isolation claim every measurement rests on.
    With more containers than cores the request is contradictory unless
    ``allow_shared=True``, which degrades to round-robin single-core sets
    (kernel time-slicing — the fractional-share analogue) instead of the
    old silent modulo wrap.
    """
    if n_containers <= 0:
        raise ValueError("n_containers must be positive")
    if avail is None:
        avail = available_cores()
    else:
        avail = sorted(set(avail))
    if total_cores is not None:
        if total_cores <= 0:
            raise ValueError("total_cores must be positive")
        avail = avail[:total_cores]
    total = len(avail)
    if n_containers > total:
        if not allow_shared:
            raise ValueError(
                f"{n_containers} containers over {total} cores cannot have "
                "pairwise-disjoint core sets; reduce n_containers or pass "
                "allow_shared=True to accept round-robin time-shared cores "
                "(the --cpus < 1 fractional-share analogue)")
        return [frozenset({avail[i % total]}) for i in range(n_containers)]
    cpc = total // n_containers
    sets = [frozenset(avail[i * cpc:(i + 1) * cpc])
            for i in range(n_containers)]
    seen: set[int] = set()
    for s in sets:
        assert len(s) == cpc and not (seen & s), \
            "core assignment produced overlapping or ragged sets"
        seen |= s
    return sets


def _pinned_main(cores: Sequence[int], body: Callable, conn, args) -> None:
    """Child entry point: affinity FIRST, then the body (whose jax import
    sizes the XLA threadpool from the already-restricted cpuset)."""
    try:
        os.sched_setaffinity(0, set(cores))
    except (AttributeError, OSError):   # non-Linux dev hosts: run unpinned
        pass
    body(conn, *args)


def spawn_pinned(body: Callable, cores: Sequence[int], args: tuple = (),
                 ctx=None):
    """Spawn ``body(conn, *args)`` in a fresh process pinned to ``cores``
    before jax can initialise. Returns ``(process, parent_conn)``.

    ``body`` must be a module-level (picklable) function and must do its
    jax import inside itself — a spawn context guarantees the child starts
    without the parent's already-initialised jax runtime.
    """
    ctx = ctx or mp.get_context("spawn")
    parent, child = ctx.Pipe()
    proc = ctx.Process(target=_pinned_main,
                       args=(sorted(cores), body, child, args))
    proc.start()
    child.close()
    return proc, parent


# ---------------------------------------------------------------------------
# the paper's video-detection workload on that harness
# ---------------------------------------------------------------------------
def _detector_body(conn, go, frames, batch):
    """Container body. Affinity was set by the harness; jax import here
    (threadpool size follows the cpuset), then warmup, then the timed
    frame loop. The paper's experiment divides CPU cores, so the child
    asks for the CPU platform itself: on an accelerator host it must not
    contend for a device the parent may hold. The config update, not
    only the variable, because a spawned child re-imports the parent's
    main module, which may have imported jax already."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    jax.config.update("jax_platforms", "cpu")

    def init(key):
        params = []
        cin = _FRAME_SHAPE[-1]
        for cout in _CHANNELS:
            key, k1 = jax.random.split(key)
            params.append(jax.random.normal(k1, (3, 3, cin, cout),
                                            jnp.float32) * 0.1)
            cin = cout
        key, k1 = jax.random.split(key)
        head = jax.random.normal(k1, (1, 1, cin, 18), jnp.float32) * 0.1
        return params, head

    @jax.jit
    def infer(params_head, x):
        params, head = params_head
        for w in params:
            x = jax.lax.conv_general_dilated(
                x, w, window_strides=(2, 2), padding="SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            x = jnp.maximum(x, 0.1 * x)          # leaky relu
        x = jax.lax.conv_general_dilated(
            x, head, window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jnp.max(x, axis=(1, 2))           # per-frame detection proxy

    ph = init(jax.random.PRNGKey(0))
    warm = infer(ph, jnp.asarray(frames[:batch]))
    warm.block_until_ready()
    conn.send("ready")
    go.wait()

    t0 = time.perf_counter()
    outs = []
    for i in range(0, len(frames), batch):
        fb = frames[i:i + batch]
        if len(fb) < batch:                      # pad the tail batch
            fb = np.concatenate(
                [fb, np.zeros((batch - len(fb), *_FRAME_SHAPE),
                              np.float32)])
        outs.append(np.asarray(infer(ph, jnp.asarray(fb))))
    dt = time.perf_counter() - t0
    out = np.concatenate(outs)[:len(frames)]
    conn.send((dt, out))
    conn.close()


@dataclasses.dataclass
class SplitRunResult:
    n_containers: int
    cores_per_container: int
    wall_s: float                 # max over containers (parallel)
    per_container_s: list
    outputs: np.ndarray           # combined, original frame order
    busy_core_seconds: float
    disjoint: bool = True         # False only under allow_shared overflow

    @property
    def avg_power_w(self) -> float:
        return P_IDLE_W + P_CORE_W * self.busy_core_seconds / self.wall_s

    @property
    def energy_j(self) -> float:
        return self.avg_power_w * self.wall_s


def run_split(frames: np.ndarray, n_containers: int,
              total_cores: int | None = None,
              batch: int = 8, allow_shared: bool = False) -> SplitRunResult:
    """§V: split the video into n segments, spawn n pinned containers,
    run simultaneously, combine in order. Raises for ``n_containers``
    beyond the core budget unless ``allow_shared`` (see
    ``assign_core_sets``)."""
    core_sets = assign_core_sets(n_containers, total_cores=total_cores,
                                 allow_shared=allow_shared)
    cpc = len(core_sets[0])
    disjoint = sum(len(s) for s in core_sets) == len(set().union(*core_sets))
    segs = splitter.split_array(frames, n_containers)

    ctx = mp.get_context("spawn")
    go = ctx.Event()
    procs, conns = [], []
    for cores, seg in zip(core_sets, segs):
        pr, parent = spawn_pinned(_detector_body, cores,
                                  args=(go, seg, batch), ctx=ctx)
        procs.append(pr)
        conns.append(parent)
    for c in conns:                # all children compiled & ready
        assert c.recv() == "ready"
    t0 = time.perf_counter()
    go.set()
    times, outs = [], []
    for c in conns:
        dt, out = c.recv()
        times.append(dt)
        outs.append(out)
    wall = time.perf_counter() - t0
    for pr in procs:
        pr.join()
    combined = splitter.combine_arrays(outs)
    # per-container core-seconds; under allow_shared overflow the sets
    # time-slice, so cap at what the distinct cores could physically have
    # run — otherwise avg_power_w would report more busy cores than exist
    busy = sum(t * cpc for t in times)
    busy = min(busy, len(set().union(*core_sets)) * wall)
    return SplitRunResult(n_containers, cpc, wall, times, combined, busy,
                          disjoint)


def run_single_container(frames: np.ndarray, cores: int,
                         batch: int = 8) -> float:
    """Fig. 1 point: ONE container limited to ``cores`` cores."""
    return run_split(frames, 1, total_cores=cores, batch=batch).wall_s


def make_video(n_frames: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n_frames, *_FRAME_SHAPE)).astype(np.float32)
