"""``ContainerBackend`` — one protocol behind every container flavour.

PR 1–4 grew three parallel serving hierarchies: thread-per-container
engines (``pool.py``), pinned OS processes (``process_pool.py``) and
sub-mesh-committed engines (the mesh-aware engine paths). This module
refactors their execution machinery behind one request-level protocol so
the ``Router`` (serving/router.py) and the wave-shim pools are written
once, against:

    capacity                       # number of containers
    submit(cid, req)               # enqueue one request on a container
    poll() -> list[Event]          # advance + drain streamed events
    load(cid) -> int               # queued+active requests (dispatch)
    stats(cid) -> (busy_s, tokens) # cumulative counters (energy/windows)
    drain(concurrent) -> [...]     # wave shim: run all containers idle
    close()                        # release engines / children

``poll`` is pull-driven: callers that want progress call it, each call
advances every container that has work by at most one engine macro-step
and returns the events that materialised (see serving/events.py — one
``ChunkEvent`` per request per macro-step, a ``DoneEvent`` per
completion). ``drain`` is the wave fast-path: it runs every container to
idle (concurrently for real backends) and returns the per-container
``(completions, wall_s, busy_s, tokens)`` tuples that
``pool.assemble_wave`` has consumed since PR 4 — which is what keeps the
PR 1–4 parity suites green through the wave shim.

Three implementations:

* ``ThreadBackend`` — one ``ServingEngine`` per container in this
  process (jax releases the GIL during XLA dispatch, so engines overlap
  on the shared device); the PR 1 pool's machinery.
* ``SubmeshBackend`` — ``ThreadBackend`` whose engines are committed to
  pairwise-disjoint device sub-meshes (PR 3's physical placement; the
  disjointness validation lives here now).
* ``ProcessBackend`` — one OS process per container pinned to a disjoint
  core set before jax initialises (PR 4's ``docker run --cpus``
  mechanism). Children host a ``ServingEngine`` behind a streaming pipe
  protocol: ``("submit", [Request...])`` in, ``("events", [Event...],
  busy_s, tokens)`` out after every engine step — so chunk events cross
  the process boundary with the same shape as thread events, and the
  parent's ``stats`` are the child's own counters. Params reach children
  by seeded re-init, ``.npz`` handoff (``save_params``) or — new — a
  ``multiprocessing.shared_memory`` mapping (``share_params``) that
  skips the copy through the filesystem.
"""
from __future__ import annotations

import dataclasses
import multiprocessing as mp
import os
import threading
import time
import traceback
from collections import deque
from typing import Any, Callable, Protocol, Sequence, runtime_checkable

import numpy as np

from repro.core.testbed import assign_core_sets, spawn_pinned
# the child body and everything its spawn payload unpickles pre-affinity
# live in serving/child.py (import-light by contract — see its docstring)
from repro.serving.child import (_IDLE_POLL_S, SharedParams, _load_params,
                                 _load_params_shm, _serving_child)
from repro.serving.engine import (Completion, EngineConfig, Request,
                                  ServingEngine)
from repro.serving.events import (ContainerFailure, DoneEvent, Event,
                                  FailedEvent)
from repro.serving.faults import FaultInjector, FaultPlan, describe_exitcode

_READY_POLL_S = 0.25


@runtime_checkable
class ContainerBackend(Protocol):
    """The request-level serving protocol (see module docstring).

    Supervising backends additionally expose an *optional* fault-
    tolerance surface the Router discovers with ``getattr`` (so minimal
    structural backends — test substrates — keep satisfying the
    protocol): ``alive(cid) -> bool`` (dispatchable right now — dead and
    respawning containers are excluded), ``cancel(cid, rid)`` (remove a
    request wherever it is and free its cache reservation), and a
    ``failures`` list of every ``ContainerFailure`` surfaced so far.
    ``poll()`` may interleave ``ContainerFailure`` records with the
    request events — it must NOT raise for a container-scoped failure,
    only for backend-wide invariant violations."""

    capacity: int

    def submit(self, cid: int, req: Request) -> None: ...

    def poll(self) -> list[Event]: ...

    def load(self, cid: int) -> int: ...

    def stats(self, cid: int) -> tuple[float, int]: ...

    def drain(self, concurrent: bool = True
              ) -> list[tuple[list[Completion], float, float, int]]: ...

    def close(self) -> None: ...


def validate_disjoint_meshes(meshes: Sequence[Any],
                             n_containers: int) -> None:
    """Per-container sub-meshes must be pairwise disjoint device slices —
    that IS the isolation claim sub-mesh placement rests on."""
    if len(meshes) != n_containers:
        raise ValueError(f"{len(meshes)} meshes for "
                         f"{n_containers} containers")
    sets = [frozenset(m.devices.flat) for m in meshes]
    for i, a in enumerate(sets):
        for b in sets[i + 1:]:
            if a & b:
                raise ValueError(
                    "container sub-meshes overlap: "
                    f"{sorted(d.id for d in a & b)}")


# ---------------------------------------------------------------------------
# in-process backends (thread / submesh)
# ---------------------------------------------------------------------------
class ThreadBackend:
    """One ServingEngine per container in this process. ``poll`` advances
    active engines one macro-step each — in worker threads when more than
    one container has work, so streaming overlaps the same way waves do —
    and ``drain`` runs each engine's ``run()`` to idle (thread-per-
    container, the PR 1 wave machinery verbatim).

    Supervision: an engine whose ``step()`` raises is *failed*, not
    propagated — ``poll()`` appends a ``ContainerFailure`` (kind
    ``"error"``, with the in-flight rids) to the event stream and, while
    the respawn budget lasts, rebuilds the engine in place from the kept
    model/params (incarnation bumped, so a ``FaultPlan`` scoped to
    incarnation 0 does not re-fire). After ``max_respawns`` rebuilds the
    circuit breaker trips: the container stays dead, ``alive()`` is
    False, and submits to it raise. ``drain`` keeps the wave contract
    (raise on any failure) — waves have no per-request recovery path."""

    kind = "thread"

    def __init__(self, model, params, n_containers: int,
                 n_slots_per_container: int = 4, max_len: int = 512,
                 engine_factory: Callable[..., ServingEngine] | None = None,
                 meshes: Sequence[Any] | None = None,
                 concurrent: bool = True,
                 config: EngineConfig | None = None,
                 fault_plan: FaultPlan | None = None,
                 max_respawns: int = 2):
        if meshes is not None:
            validate_disjoint_meshes(meshes, n_containers)
        self.capacity = n_containers
        self.model = model
        self.params = params
        self.meshes = meshes
        self.concurrent = concurrent
        self.config = config or EngineConfig(
            n_slots=n_slots_per_container, max_len=max_len)
        self.fault_plan = fault_plan
        self.max_respawns = max_respawns
        self._engine_factory = engine_factory
        self._events: deque[Event] = deque()   # append is GIL-atomic
        self._executor = None                  # lazy; poll-step overlap
        self.failures: list[ContainerFailure] = []
        self._alive = [True] * n_containers
        self._respawns = [0] * n_containers
        self._incarnation = [0] * n_containers
        # dead engines leave cumulative busy/tokens behind; the rebuilt
        # engine restarts at zero, so stats() adds the pre-failure base
        # or window deltas would go negative across a respawn
        self._stats_base = [(0.0, 0)] * n_containers
        self.engines: list[ServingEngine] = [
            self._build_engine(cid, 0) for cid in range(n_containers)]

    def _build_engine(self, cid: int, incarnation: int) -> ServingEngine:
        mesh_kw = ({"mesh": self.meshes[cid]}
                   if self.meshes is not None else {})
        if self._engine_factory is None:
            eng = ServingEngine(self.model, self.params, self.config,
                                **mesh_kw)
        else:
            # custom factories (tests, instrumented engines) keep the
            # legacy call style; their forwarding path warns once
            eng = self._engine_factory(self.model, self.params,
                                       n_slots=self.config.n_slots,
                                       max_len=self.config.max_len,
                                       **mesh_kw)
        eng.container_id = cid
        eng.on_event = self._events.append
        if self.fault_plan is not None:
            inj = FaultInjector(self.fault_plan, cid, incarnation)
            eng.fault = inj if inj.armed else None
        return eng

    def _fail_container(self, cid: int, exc: BaseException) -> None:
        """Convert an engine-step exception into a ContainerFailure event
        and either rebuild the engine (bounded) or trip the breaker."""
        eng = self.engines[cid]
        lost = tuple(r.rid for r in eng.queue) + eng.admitting + tuple(
            s.rid for s in eng.slots if s.active and s.rid not in
            eng.admitting)
        tb = "".join(traceback.format_exception(
            type(exc), exc, exc.__traceback__))
        fail = ContainerFailure(
            container_id=cid, kind="error",
            message=f"engine step raised:\n{tb}",
            time_s=time.perf_counter(), lost_rids=lost)
        self.failures.append(fail)
        self._events.append(fail)
        base_b, base_t = self._stats_base[cid]
        self._stats_base[cid] = (base_b + eng.busy_s,
                                 base_t + eng.tokens_generated)
        if self._respawns[cid] < self.max_respawns:
            self._respawns[cid] += 1
            self._incarnation[cid] += 1
            # in-process "respawn": a fresh engine over the same (kept)
            # model/params — jit caches are shared process-wide, so this
            # is cheap and immediately serving
            self.engines[cid] = self._build_engine(
                cid, self._incarnation[cid])
        else:
            self._alive[cid] = False

    # -- supervision surface -------------------------------------------
    def alive(self, cid: int) -> bool:
        return self._alive[cid]

    def cancel(self, cid: int, rid: int) -> None:
        """Remove ``rid`` from container ``cid`` wherever it is (queued
        or mid-decode) and free its cache reservation. No event is
        emitted — the canceller owns the terminal event."""
        if self._alive[cid]:
            self.engines[cid].cancel(rid)

    # -- streaming ------------------------------------------------------
    def submit(self, cid: int, req: Request) -> None:
        if not self._alive[cid]:
            raise RuntimeError(f"container {cid} is circuit-broken "
                               f"(after {self._respawns[cid]} respawns)")
        self.engines[cid].submit(req)

    def submit_many(self, cid: int, reqs: Sequence[Request]) -> None:
        if not self._alive[cid]:
            raise RuntimeError(f"container {cid} is circuit-broken "
                               f"(after {self._respawns[cid]} respawns)")
        self.engines[cid].submit_many(reqs)

    def poll(self) -> list[Event]:
        active = [eng for cid, eng in enumerate(self.engines)
                  if self._alive[cid] and eng.has_work]
        failed: list[tuple[int, BaseException]] = []
        if self.concurrent and len(active) > 1:
            if self._executor is None:
                # persistent workers: a stream polls once per macro-step
                # for its whole life — per-poll thread spawns would churn
                from concurrent.futures import ThreadPoolExecutor
                self._executor = ThreadPoolExecutor(
                    max_workers=self.capacity,
                    thread_name_prefix="container-step")
            futures = [(eng, self._executor.submit(eng.step))
                       for eng in active]
            for eng, f in futures:      # join ALL steps before failing —
                try:                    # a swallowed error would hang the
                    f.result()          # stream waiting for a DoneEvent
                except BaseException as e:
                    failed.append((eng.container_id, e))
        else:
            for eng in active:
                try:
                    eng.step()
                except BaseException as e:
                    failed.append((eng.container_id, e))
        for cid, exc in failed:
            self._fail_container(cid, exc)
        for eng in self.engines:
            # poll-driven consumers take completions from DoneEvents;
            # nobody calls run() on a streamed engine, so drain its done
            # list (all engines — zero-budget submissions complete at
            # submit, without the engine ever becoming active) or a
            # long-lived stream accumulates one Completion per request
            # and a later wave drain() would return the stale backlog
            eng.done.clear()
        out: list[Event] = []
        while self._events:
            out.append(self._events.popleft())
        return out

    def load(self, cid: int) -> int:
        eng = self.engines[cid]
        return len(eng.queue) + sum(1 for s in eng.slots if s.active)

    def stats(self, cid: int) -> tuple[float, int]:
        eng = self.engines[cid]
        base_b, base_t = self._stats_base[cid]
        return base_b + eng.busy_s, base_t + eng.tokens_generated

    # -- wave shim ------------------------------------------------------
    def drain(self, concurrent: bool | None = None
              ) -> list[tuple[list[Completion], float, float, int]]:
        """Run every container to idle; per-container results for
        ``assemble_wave``. Wave consumers take completions, not events,
        so the event buffer is cleared afterwards (``engine.run`` emitted
        into it redundantly). Waves have no per-request recovery path, so
        a circuit-broken container fails the whole wave here."""
        dead = [cid for cid in range(self.capacity)
                if not self._alive[cid]]
        if dead:
            raise RuntimeError(
                f"cannot drain a wave: containers {dead} are "
                "circuit-broken (see backend.failures)")
        if concurrent is None:
            concurrent = self.concurrent
        out: list[Any] = [None] * self.capacity

        def run_one(cid: int) -> None:
            try:
                eng = self.engines[cid]
                t0 = time.perf_counter()
                busy0, toks0 = eng.busy_s, eng.tokens_generated
                comps = eng.run()
                out[cid] = (comps, time.perf_counter() - t0,
                            eng.busy_s - busy0,
                            eng.tokens_generated - toks0)
            except BaseException as e:  # propagate across the thread join
                out[cid] = e

        if concurrent and self.capacity > 1:
            workers = [threading.Thread(target=run_one, args=(cid,),
                                        daemon=True)
                       for cid in range(self.capacity)]
            for w in workers:
                w.start()
            for w in workers:
                w.join()
        else:
            for cid in range(self.capacity):
                run_one(cid)
        self._events.clear()
        for e in out:
            if isinstance(e, BaseException):
                raise e
        return out

    def close(self) -> None:
        if self._executor is not None:
            self._executor.shutdown(wait=False)
            self._executor = None
        self._events.clear()
        self.engines = []
        self.capacity = 0


class SubmeshBackend(ThreadBackend):
    """ThreadBackend whose engines are committed to disjoint device
    sub-meshes (``launch/mesh.make_container_meshes``) — the containers
    are physical on the device axis, so the threads overlap real parallel
    hardware instead of one shared device."""

    kind = "submesh"

    def __init__(self, model, params, n_containers: int,
                 n_slots_per_container: int = 4, max_len: int = 512,
                 engine_factory: Callable[..., ServingEngine] | None = None,
                 meshes: Sequence[Any] | None = None,
                 concurrent: bool = True,
                 config: EngineConfig | None = None,
                 fault_plan: FaultPlan | None = None,
                 max_respawns: int = 2):
        if meshes is None:
            raise ValueError("SubmeshBackend needs per-container meshes "
                             "(launch/mesh.make_container_meshes)")
        super().__init__(model, params, n_containers,
                         n_slots_per_container=n_slots_per_container,
                         max_len=max_len, engine_factory=engine_factory,
                         meshes=meshes, concurrent=concurrent,
                         config=config, fault_plan=fault_plan,
                         max_respawns=max_respawns)


# ---------------------------------------------------------------------------
# params handoff for process containers
# ---------------------------------------------------------------------------
def save_params(params: Any, path: str) -> str:
    """Write a params tree to ``path`` (.npz, leaves in tree order) for the
    cross-process handoff: children rebuild the tree structure from
    ``jax.eval_shape(model.init, ...)`` and unflatten these leaves — exact
    float bytes, so parity with the parent's params is preserved."""
    import jax
    leaves = jax.tree_util.tree_leaves(params)
    np.savez(path, **{f"leaf{i}": np.asarray(leaf)
                      for i, leaf in enumerate(leaves)})
    return path


class ParamsShare:
    """Parent-side owner of the shared block. Keep it alive while any
    child may attach; ``close()`` unlinks the segment. Pass ``.handle``
    (the picklable SharedParams) to pools/backends."""

    def __init__(self, shm, handle: SharedParams):
        self._shm = shm
        self.handle = handle

    def close(self) -> None:
        if self._shm is None:
            return
        shm, self._shm = self._shm, None
        shm.close()
        try:
            shm.unlink()
        except FileNotFoundError:
            pass

    def __enter__(self) -> "ParamsShare":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def share_params(params: Any) -> ParamsShare:
    """Lay the params tree's leaves out back-to-back in one shared-memory
    segment (leaves in tree order, byte-exact, so parity with the parent's
    params is preserved — same contract as ``save_params``)."""
    import jax
    from multiprocessing import shared_memory
    leaves = [np.asarray(leaf) for leaf in jax.tree_util.tree_leaves(params)]
    specs, offset = [], 0
    for leaf in leaves:
        # leaves are aligned to their itemsize so the child-side ndarray
        # views are valid for any dtype
        align = max(leaf.dtype.itemsize, 1)
        offset = (offset + align - 1) // align * align
        specs.append((leaf.shape, leaf.dtype.str, offset))
        offset += leaf.nbytes
    shm = shared_memory.SharedMemory(create=True, size=max(offset, 1))
    for leaf, (shape, dtype, off) in zip(leaves, specs):
        dst = np.ndarray(shape, dtype=dtype, buffer=shm.buf, offset=off)
        dst[...] = leaf
    handle = SharedParams(shm.name, tuple(specs), offset)
    return ParamsShare(shm, handle)


# ---------------------------------------------------------------------------
# process backend
# ---------------------------------------------------------------------------
def process_isolation_refusal() -> str | None:
    """Why process containers cannot start here, or None when they can.
    Each child imports jax and needs the device; an accelerator belongs
    to one process, and this parent already holds it (a TPU child would
    fail or hang). Process containers are the CPU-core mechanism; on an
    accelerator host ``SubmeshBackend`` is the container."""
    import jax
    platform = jax.default_backend()
    if platform == "tpu":
        return ("process isolation needs a CPU parent: this process holds "
                f"the {platform} device, which one process owns at a time; "
                "use submesh placement (--submesh / SubmeshBackend) for "
                "containers on an accelerator")
    return None


def _engine_config_wire(config: EngineConfig) -> dict:
    """EngineConfig as a dict of picklable primitives. Pickling the
    dataclass itself would make the child unpickle (hence import
    repro.serving.engine, hence jax) at process bootstrap — BEFORE
    ``spawn_pinned`` applies the cpuset — so the config crosses the pipe
    as plain fields with the dtype by name instead."""
    kw = dataclasses.asdict(config)
    kw["dtype"] = np.dtype(kw["dtype"]).name
    return kw


class ProcessBackend:
    """One pinned OS process per container (the paper's ``--cpus``
    shares), behind the streaming ContainerBackend protocol. Children
    spawn lazily at first submit and stay warm until ``close()`` —
    engines, compiled executables and params survive across waves and
    streams, which is what makes process isolation affordable inside an
    online loop.

    Supervision: a child that dies (exitcode decoded via
    ``serving.faults.describe_exitcode``), reports a step error, or goes
    silent past the heartbeat timeout is *failed*, not raised — ``poll``
    surfaces a ``ContainerFailure`` carrying its in-flight rids, and
    while the respawn budget lasts a replacement child is launched
    *non-blocking* (exponential backoff; the pending handshake is
    promoted from later ``poll`` calls, so healthy containers keep
    serving through a respawn's jax import + warmup). The params handoff
    re-runs through the same path as the original spawn, so keep the
    ``.npz`` file / shared-memory segment alive while the backend is.
    After ``max_respawns`` replacements a container's circuit breaker
    trips: ``alive()`` stays False and the Router routes around it.
    ``drain`` keeps the wave contract — any failure tears down the wave
    with an exception, since waves have no per-request recovery."""

    kind = "process"

    def __init__(self, cfg, n_containers: int,
                 n_slots_per_container: int = 4, max_len: int = 512,
                 total_cores: int | None = None,
                 params_seed: int = 0, params_path: str | None = None,
                 params_shm: SharedParams | None = None,
                 greedy: bool = True, seed: int = 0,
                 chunked: bool = True, chunk_tokens: int | None = None,
                 allow_shared_cores: bool = False,
                 start_timeout_s: float = 600.0,
                 config: EngineConfig | None = None,
                 fault_plan: FaultPlan | None = None,
                 max_respawns: int = 2,
                 respawn_backoff_s: float = 0.25,
                 heartbeat_s: float = 0.5,
                 heartbeat_timeout_s: float | None = 60.0):
        refusal = process_isolation_refusal()
        if refusal:
            raise RuntimeError(refusal)
        self.cfg = cfg
        self.capacity = n_containers
        self.config = config or EngineConfig(
            n_slots=n_slots_per_container, max_len=max_len, greedy=greedy,
            seed=seed, chunked=chunked, chunk_tokens=chunk_tokens)
        # legacy attribute surface (readers predate EngineConfig)
        self.n_slots = self.config.n_slots
        self.max_len = self.config.max_len
        self.greedy = self.config.greedy
        self.seed = self.config.seed
        self.chunked = self.config.chunked
        self.chunk_tokens = self.config.chunk_tokens
        self.params_seed = params_seed
        self.params_path = params_path
        self.params_shm = params_shm
        if params_path and params_shm:
            raise ValueError("pass params_path or params_shm, not both")
        self.start_timeout_s = start_timeout_s
        self.fault_plan = fault_plan
        self.max_respawns = max_respawns
        self.respawn_backoff_s = respawn_backoff_s
        self.heartbeat_s = heartbeat_s
        self.heartbeat_timeout_s = (
            heartbeat_timeout_s if heartbeat_s > 0 else None)
        # fail fast, before any spawn: more containers than cores cannot
        # be disjoint (see core/testbed.assign_core_sets)
        self.core_sets = assign_core_sets(n_containers,
                                         total_cores=total_cores,
                                         allow_shared=allow_shared_cores)
        self.reported_core_sets: list[frozenset[int]] | None = None
        # workers[cid] is (proc, conn) while serving, None while dead or
        # respawning (the pending handshake lives in _spawning[cid])
        self.workers: list[tuple[Any, Any] | None] | None = None
        self._events: deque[Event] = deque()
        self.failures: list[ContainerFailure] = []
        # rid-sets, not counts: a lost container must say WHICH requests
        # died with it, and cancel() must be race-safe against a
        # completion already in the pipe
        self._inflight: list[set[int]] = [set() for _ in range(n_containers)]
        self._alive = [True] * n_containers
        self._respawns = [0] * n_containers
        self._incarnation = [0] * n_containers
        self._backoff = [respawn_backoff_s] * n_containers
        self._next_spawn = [0.0] * n_containers
        self._spawning: list[tuple[Any, Any] | None] = [None] * n_containers
        self._last_msg = [0.0] * n_containers
        # child counters restart at zero each incarnation; stats() adds
        # the accumulated pre-failure base so window deltas stay monotone
        self._stats_child = [(0.0, 0)] * n_containers
        self._stats_base = [(0.0, 0)] * n_containers

    # -- lifecycle ------------------------------------------------------
    def warm(self) -> None:
        """Public warm-up: spawn + handshake the children now, so a wave
        shim (or a latency-sensitive caller) can pay the spawn+compile
        cost outside its timed region."""
        self._ensure_workers()

    def _spawn_one(self, cid: int, incarnation: int) -> tuple[Any, Any]:
        ctx = mp.get_context("spawn")
        return spawn_pinned(
            _serving_child, self.core_sets[cid],
            args=(cid, self.cfg, self.params_seed, self.params_path,
                  self.params_shm, _engine_config_wire(self.config),
                  incarnation, self.fault_plan, self.heartbeat_s),
            ctx=ctx)

    def _ensure_workers(self) -> None:
        """Spawn + handshake all children once; engines stay warm across
        waves (the per-count pool caches rely on this). The INITIAL spawn
        stays fail-fast (blocking handshake, raise on any startup error)
        — supervision begins once a container has served."""
        if self.workers is not None:
            return
        workers = [self._spawn_one(cid, 0) for cid in range(self.capacity)]
        reported = []
        try:
            for cid, (proc, conn) in enumerate(workers):
                msg = self._recv(proc, conn, self.start_timeout_s)
                if msg[0] != "ready":
                    raise RuntimeError(
                        f"container {cid} failed to start:\n{msg[1]}")
                reported.append(frozenset(msg[1]))
        except BaseException:
            for proc, _ in workers:
                proc.terminate()
            raise
        self.workers = list(workers)
        self.reported_core_sets = reported
        now = time.perf_counter()
        self._alive = [True] * self.capacity
        self._last_msg = [now] * self.capacity

    @staticmethod
    def _recv(proc, conn, timeout_s: float | None):
        """recv that notices a dead child instead of blocking forever."""
        deadline = (None if timeout_s is None
                    else time.perf_counter() + timeout_s)
        while not conn.poll(_READY_POLL_S):
            if not proc.is_alive():
                raise RuntimeError(
                    f"container process died (exit {proc.exitcode}) "
                    "before replying")
            if deadline is not None and time.perf_counter() > deadline:
                raise TimeoutError("container start/serve timed out")
        return conn.recv()

    def close(self) -> None:
        """Shut the warm children down (idempotent), including any
        respawn still mid-handshake — nothing may orphan. Cached backends
        evicted by adaptive facades call this so children never leak."""
        if self.workers is None:
            return
        workers, self.workers = self.workers, None
        spawning, self._spawning = (self._spawning,
                                    [None] * self.capacity)
        self._events.clear()
        self._inflight = [set() for _ in range(self.capacity)]
        # reopened (lazily respawned) children restart their counters at
        # zero — stale cumulatives would make the next wave's deltas
        # negative
        self._stats_child = [(0.0, 0)] * self.capacity
        self._stats_base = [(0.0, 0)] * self.capacity
        self._alive = [True] * self.capacity
        self._respawns = [0] * self.capacity
        self._incarnation = [0] * self.capacity
        self._backoff = [self.respawn_backoff_s] * self.capacity
        self._next_spawn = [0.0] * self.capacity
        for w in workers:
            if w is None:
                continue
            try:
                w[1].send(("close",))
            except (BrokenPipeError, OSError):
                pass
        for w in workers:
            if w is None:
                continue
            proc, conn = w
            proc.join(timeout=10)
            if proc.is_alive():
                proc.terminate()
                proc.join(timeout=5)
            conn.close()
        for sp in spawning:
            if sp is None:
                continue
            proc, conn = sp
            proc.terminate()
            proc.join(timeout=5)
            conn.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    # -- supervision ----------------------------------------------------
    def alive(self, cid: int) -> bool:
        """Dispatchable right now. True before first spawn (children are
        lazy); False while dead, respawning, or circuit-broken."""
        return self._alive[cid]

    def cancel(self, cid: int, rid: int) -> None:
        """Forget ``rid`` parent-side and ask the child to drop it. Safe
        against the race where its DoneEvent is already in the pipe: the
        rid is discarded (not asserted present), the child's cancel of a
        finished request is a no-op, and the stale DoneEvent is still
        delivered (the canceller's event routing must tolerate it)."""
        self._inflight[cid].discard(rid)
        w = self.workers[cid] if self.workers is not None else None
        if w is not None and self._alive[cid]:
            try:
                w[1].send(("cancel", rid))
            except (BrokenPipeError, OSError):
                pass                    # death is _pump's to notice

    def _fail(self, cid: int, kind: str, message: str,
              exitcode: int | None = None) -> None:
        """Record one container failure: emit the typed event (with the
        lost rids), fold the dead incarnation's counters into the stats
        base, reap the child, and schedule a bounded respawn."""
        now = time.perf_counter()
        lost = tuple(sorted(self._inflight[cid]))
        self._inflight[cid] = set()
        base_b, base_t = self._stats_base[cid]
        child_b, child_t = self._stats_child[cid]
        self._stats_base[cid] = (base_b + child_b, base_t + child_t)
        self._stats_child[cid] = (0.0, 0)
        fail = ContainerFailure(
            container_id=cid, kind=kind,
            message=f"container {cid} {kind}: {message}",
            time_s=now, exitcode=exitcode, lost_rids=lost)
        self.failures.append(fail)
        self._events.append(fail)
        self._alive[cid] = False
        w = self.workers[cid] if self.workers is not None else None
        if w is not None:
            proc, conn = w
            self.workers[cid] = None
            try:
                conn.close()
            except OSError:
                pass
            if proc.is_alive():
                proc.terminate()
            proc.join(timeout=5)
        if self._respawns[cid] < self.max_respawns:
            self._next_spawn[cid] = now + self._backoff[cid]
            self._backoff[cid] = min(self._backoff[cid] * 2, 30.0)

    def _record_start_failure(self, cid: int, detail: str,
                              exitcode: int | None) -> None:
        now = time.perf_counter()
        fail = ContainerFailure(
            container_id=cid, kind="start",
            message=f"container {cid} respawn failed to start: {detail}",
            time_s=now, exitcode=exitcode, lost_rids=())
        self.failures.append(fail)
        self._events.append(fail)
        self._next_spawn[cid] = now + self._backoff[cid]
        self._backoff[cid] = min(self._backoff[cid] * 2, 30.0)

    def _service_respawns(self) -> None:
        """Non-blocking respawn driver, run on every pump: launch
        replacements whose backoff expired, promote pending handshakes
        that completed — healthy containers never wait on a respawning
        one's jax import + engine build."""
        if self.workers is None:
            return
        now = time.perf_counter()
        for cid in range(self.capacity):
            if self._alive[cid]:
                continue
            sp = self._spawning[cid]
            if sp is not None:
                proc, conn = sp
                msg = None
                try:
                    if conn.poll(0):
                        msg = conn.recv()
                except (EOFError, OSError):
                    msg = ("error", "handshake pipe closed")
                if msg is not None and msg[0] == "ready":
                    self._spawning[cid] = None
                    self.workers[cid] = (proc, conn)
                    if self.reported_core_sets is not None:
                        self.reported_core_sets[cid] = frozenset(msg[1])
                    self._alive[cid] = True
                    self._last_msg[cid] = now
                    self._backoff[cid] = self.respawn_backoff_s
                elif msg is not None or not proc.is_alive():
                    self._spawning[cid] = None
                    detail = (msg[1] if msg is not None
                              else describe_exitcode(proc.exitcode))
                    exitcode = proc.exitcode
                    if proc.is_alive():
                        proc.terminate()
                    proc.join(timeout=5)
                    try:
                        conn.close()
                    except OSError:
                        pass
                    self._record_start_failure(cid, detail, exitcode)
                continue
            if (self._respawns[cid] >= self.max_respawns
                    or now < self._next_spawn[cid]):
                continue                # circuit-broken, or backing off
            self._respawns[cid] += 1
            self._incarnation[cid] += 1
            self._spawning[cid] = self._spawn_one(
                cid, self._incarnation[cid])

    # -- streaming ------------------------------------------------------
    def submit(self, cid: int, req: Request) -> None:
        self.submit_many(cid, [req])

    def submit_many(self, cid: int, reqs: Sequence[Request]) -> None:
        if not reqs:
            return
        self._ensure_workers()
        assert self.workers is not None
        if not self._alive[cid]:
            raise RuntimeError(
                f"container {cid} is not serving (dead, respawning or "
                "circuit-broken — check alive() before dispatch)")
        # inflight BEFORE send: if the pipe breaks mid-send the rids ride
        # the ContainerFailure's lost_rids and the Router's normal retry
        # path recovers them — no separate submit-error path
        self._inflight[cid].update(r.rid for r in reqs)
        _, conn = self.workers[cid]
        try:
            conn.send(("submit", list(reqs)))
        except (BrokenPipeError, OSError) as e:
            self._fail(cid, "dead", f"submit pipe broke: {e}")

    def _route_ready(self, cid: int, conn) -> bool:
        """Drain every buffered message from one serving child. Never
        raises: a closed pipe just ends the drain (death is the liveness
        scan's to classify, with the exitcode in hand)."""
        got = False
        while True:
            try:
                if not conn.poll(0):
                    return got
                msg = conn.recv()
            except (EOFError, OSError):
                return got
            got = True
            self._last_msg[cid] = time.perf_counter()
            if msg[0] == "hb":
                continue
            if msg[0] == "error":
                self._fail(cid, "error",
                           f"engine step raised:\n{msg[1]}",
                           exitcode=None)
                return got
            _, events, busy, toks = msg
            self._stats_child[cid] = (busy, toks)
            for ev in events:
                if isinstance(ev, (DoneEvent, FailedEvent)):
                    self._inflight[cid].discard(ev.rid)
                self._events.append(ev)

    def _pump(self, block_s: float = 0.0) -> bool:
        """Drain every ready child message into the event buffer; with
        ``block_s`` wait up to that long for the first one. Container
        failures (death, step error, heartbeat silence) become
        ``ContainerFailure`` events in the buffer — never exceptions —
        and replacements are serviced, all without blocking healthy
        containers."""
        if self.workers is None:
            return False
        self._service_respawns()
        conn_map = {w[1]: cid for cid, w in enumerate(self.workers)
                    if w is not None and self._alive[cid]}
        if conn_map and block_s > 0:
            from multiprocessing.connection import wait as conn_wait
            conn_wait(list(conn_map), block_s)
        got = False
        for conn, cid in list(conn_map.items()):
            got |= self._route_ready(cid, conn)
        now = time.perf_counter()
        for cid in range(self.capacity):
            w = self.workers[cid]
            if w is None or not self._alive[cid]:
                continue
            proc, conn = w
            if not proc.is_alive():
                # the child may have flushed replies (even its "error"
                # report) right before dying — consume them first so no
                # completed request is counted lost
                self._route_ready(cid, conn)
                if self._alive[cid]:
                    self._fail(
                        cid, "dead",
                        "child process exited mid-serve "
                        f"({describe_exitcode(proc.exitcode)}) with "
                        f"{len(self._inflight[cid])} requests in flight",
                        exitcode=proc.exitcode)
            elif (self.heartbeat_timeout_s is not None
                  and now - self._last_msg[cid] > self.heartbeat_timeout_s):
                self._fail(
                    cid, "hung",
                    f"no message for {now - self._last_msg[cid]:.1f}s "
                    f"(heartbeat timeout {self.heartbeat_timeout_s:g}s)")
        return got

    def poll(self) -> list[Event]:
        self._pump()
        out = list(self._events)
        self._events.clear()
        return out

    def load(self, cid: int) -> int:
        return len(self._inflight[cid])

    def stats(self, cid: int) -> tuple[float, int]:
        base_b, base_t = self._stats_base[cid]
        child_b, child_t = self._stats_child[cid]
        return base_b + child_b, base_t + child_t

    @property
    def outstanding(self) -> int:
        return sum(len(s) for s in self._inflight)

    # -- wave shim ------------------------------------------------------
    def drain(self, concurrent: bool | None = None
              ) -> list[tuple[list[Completion], float, float, int]]:
        """Pump until every in-flight request completed; per-container
        results for ``assemble_wave``. ``concurrent`` is accepted for
        protocol compatibility and ignored — processes always overlap
        (that is the point of this backend). Wall/busy/token deltas are
        measured from the buffered stats at call entry, so a warm backend
        reports per-wave numbers, not lifetime cumulatives.

        Waves have no per-request recovery: any ``ContainerFailure``
        surfaced while draining tears the wave down with an exception
        (children closed — their pipes hold replies for a wave that no
        longer exists) instead of hanging on requests that died with
        their container."""
        del concurrent
        n_fail0 = len(self.failures)
        stats0 = [self.stats(cid) for cid in range(self.capacity)]
        t0 = time.perf_counter()
        comps: list[list[Completion]] = [[] for _ in range(self.capacity)]
        last = [t0] * self.capacity
        # route events already buffered (e.g. zero-budget completions
        # flushed before drain was called) plus everything still to come
        pending = list(self._events)
        self._events.clear()
        while True:
            for ev in pending:
                if isinstance(ev, DoneEvent):
                    comps[ev.container_id].append(ev.completion)
                    last[ev.container_id] = time.perf_counter()
            if len(self.failures) > n_fail0:
                fail = self.failures[-1]
                self.close()
                raise RuntimeError(f"wave failed: {fail.message}")
            if self.outstanding <= 0:
                break
            self._pump(block_s=_IDLE_POLL_S)
            pending = list(self._events)
            self._events.clear()
        return [(comps[cid], last[cid] - t0,
                 self.stats(cid)[0] - stats0[cid][0],
                 self.stats(cid)[1] - stats0[cid][1])
                for cid in range(self.capacity)]
