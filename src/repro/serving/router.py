"""Request-level streaming Router — continuous admission over containers.

The paper's workload is continuous (video frames arriving over time), but
the wave API serves it in batch: hand over a complete wave, block until
the slowest container drains. The ``Router`` replaces that surface with
per-request admission and typed per-chunk events:

    router = Router(ThreadBackend(model, params, n))
    handle = router.submit(Request(...))          # returns immediately
    for ev in handle.stream():                    # ChunkEvent... DoneEvent
        ...

Dispatch is **least-loaded + bucket-aware**: a request goes to the
container with the fewest queued+active requests, ties broken toward a
container already holding requests in the same prompt-length admission
bucket (those prefill together in one compiled call — see the engine's
batched bucket admission). Works identically over every
``ContainerBackend`` (thread, process, submesh).

With a scheduler attached, the Router closes the paper's online loop at
**window** granularity instead of wave granularity: completions
accumulate into a sliding window of observed (wall, energy, tokens/s,
time-to-first-chunk, latency) stats; at each window boundary the
``DivideAndSaveScheduler`` observes the window and re-picks the container
count, and the Router swaps to the (cached, warm) backend for that count
as soon as the stream drains — no explicit waves anywhere.

The wave API survives as a thin shim: ``serve_wave`` = submit-all +
drain, reconstructing ``ContainerResult`` accounting via the existing
``pool.assemble_wave``, so wave callers and benchmarks keep working.

All latency/ttfc stamps are taken router-side (one clock domain even for
process backends): time-to-first-chunk is measured from ``submit()`` to
the arrival of the request's first ``ChunkEvent`` at the router.

Fault tolerance (see serving/events.py for the event taxonomy):

* **Retry** — a ``ContainerFailure`` surfaced by a supervising backend
  carries the rids lost with the container; the Router re-dispatches
  each to a healthy container (``max_retries`` bound), streaming a
  ``RetryEvent`` so consumers discard the aborted attempt's chunks.
* **Deadlines** — ``Request.deadline_s`` (or the Router-wide
  ``request_deadline_s`` default) rides into the engine, which expires
  it exactly where resources are freed; the Router keeps an authoritative
  backstop clock so a dead/silent container cannot outlive a deadline.
* **Load-shedding** — admission rejects (typed ``RejectedEvent`` with a
  retry-after hint) when ``max_queue`` in-flight requests exist or the
  recent ttfc p95 crosses ``shed_p95_s``, so overload degrades into
  fast rejections instead of an unbounded latency tail.

``stream()`` yields a request's terminal event and then *raises*
(``RequestFailed`` / ``RequestRejected``, both RuntimeError) so code
that only calls ``result()`` cannot mistake a failed request for a
hung one.

**SLO mode** (pass an ``workload.slo.SLOSpec``): requests carry a
priority class and tenant; admission enters a rank-ordered router-side
backlog instead of a container FIFO (``dispatch_depth`` bounds how deep
each container's own queue may get, so ordering happens where ranks
exist), shed thresholds and queue shares derive from each class
(``queue_limit`` / ``shed_ttfc_threshold``), per-tenant in-flight
quotas reject hogs with ``RejectedEvent(kind="tenant")``, and each
window's ``WindowStats.per_class`` carries per-class tails + SLO
attainment. The scheduler observation then includes the constraint
class's ttfc p95 so ``energy_under_slo`` can pick the cheapest count
whose predicted tail meets target. Without an SLOSpec every code path
above is byte-identical to the pre-SLO router.
"""
from __future__ import annotations

import dataclasses
import heapq
import time
from collections import Counter, defaultdict, deque
from typing import Any, Callable, Iterator, Sequence

from jax.profiler import TraceAnnotation

from repro.core.scheduler import DivideAndSaveScheduler
from repro.serving.engine import Completion, Request, _bucket
from repro.serving.events import (ChunkEvent, ContainerFailure, DoneEvent,
                                  Event, FailedEvent, RejectedEvent,
                                  RetryEvent)
from repro.serving.pool import (ContainerResult, EnergyProxy, _warn_wave_shim,
                                assemble_wave, latency_percentiles,
                                percentiles)
from repro.workload.slo import (SLOSpec, censored_ttfc_p95, class_window,
                                queue_limit, shed_ttfc_threshold)

_IDLE_SLEEP_S = 0.002


class RequestFailed(RuntimeError):
    """Raised by ``stream()``/``result()`` after a terminal
    ``FailedEvent`` — deadline expiry, retries exhausted, cancellation.
    The event rides on ``.event``; the message embeds its reason (which
    for container failures includes the original traceback)."""

    def __init__(self, event):
        super().__init__(
            f"request {event.rid} failed ({event.kind}): {event.reason}")
        self.event = event


class RequestRejected(RequestFailed):
    """Raised after a terminal ``RejectedEvent`` (admission shed the
    request). ``event.retry_after_s`` is the backpressure hint."""

    def __init__(self, event):
        RuntimeError.__init__(
            self,
            f"request {event.rid} rejected: {event.reason} "
            f"(retry after {event.retry_after_s:.2f}s)")
        self.event = event


@dataclasses.dataclass
class WindowStats:
    """One scheduler observation window of streamed serving — the
    request-level analogue of ``adaptive.WaveResult``."""
    window: int
    n_containers: int
    wall_s: float
    energy_j: float
    n_requests: int
    n_tokens: int = 0
    tokens_per_s: float = 0.0
    ttfc_p50_s: float = 0.0       # time-to-first-chunk, median
    ttfc_p95_s: float = 0.0       # time-to-first-chunk, tail
    latency_p50_s: float = 0.0
    latency_p95_s: float = 0.0
    n_retries: int = 0            # re-dispatches after container failures
    n_failed: int = 0             # terminal FailedEvents in the window
    n_shed: int = 0               # admission rejections in the window
    prefix_hit_tokens: int = 0    # prompt tokens served from the prefix
                                  # cache instead of prefill (paged only)
    # per-SLO-class slice of the window (name -> workload.slo.ClassWindow
    # with tails + attainment); empty unless the Router runs with an
    # SLOSpec
    per_class: dict = dataclasses.field(default_factory=dict)


class CompletionHandle:
    """Live view of one submitted request. ``stream()`` yields the
    request's typed events as they arrive (pumping the router while it
    waits); ``result()`` drains the stream and returns the Completion —
    or raises ``RequestFailed``/``RequestRejected`` if the request ended
    without one."""

    def __init__(self, rid: int, router: "Router"):
        self.rid = rid
        self._router = router
        self._pending: deque[Event] = deque()
        self.completion: Completion | None = None
        self.failure: Any = None            # terminal Failed/RejectedEvent
        self.attempts: int = 0              # retries so far (0 = first try)
        self.ttfc_s: float | None = None    # submit → first ChunkEvent
        self.container_id: int | None = None  # where dispatch placed it
        self.done_at: float | None = None   # DoneEvent arrival stamp
        self.priority: str = "default"      # resolved SLO class name
        self.tenant: str = ""

    @property
    def done(self) -> bool:
        """The terminal event arrived at the router (it may still be
        waiting in this handle's queue for ``stream()`` to consume)."""
        return self.completion is not None or self.failure is not None

    def stream(self) -> Iterator[Event]:
        """Yield this request's events: ChunkEvents (and RetryEvents —
        discard accumulated chunks at each one), then exactly one
        terminal event. After yielding a DoneEvent it stops; after a
        FailedEvent/RejectedEvent it raises ``RequestFailed`` /
        ``RequestRejected`` — the terminal event is always *yielded
        first*, so event-driven consumers see it even if they stop
        iterating there. Raises RuntimeError if the router is closed
        mid-stream instead of blocking forever; a second stream() over a
        consumed handle yields nothing more (and re-raises for a failed
        request — the terminal state is kept on the handle)."""
        while True:
            while self._pending:
                ev = self._pending.popleft()
                yield ev
                if isinstance(ev, DoneEvent):
                    return
                if isinstance(ev, RejectedEvent):
                    raise RequestRejected(ev)
                if isinstance(ev, FailedEvent):
                    raise RequestFailed(ev)
            if self.completion is not None:
                return                 # already fully consumed
            if self.failure is not None:
                if isinstance(self.failure, RejectedEvent):
                    raise RequestRejected(self.failure)
                raise RequestFailed(self.failure)
            if self._router._closed:
                raise RuntimeError(
                    f"router closed while request {self.rid} was "
                    "mid-stream")
            self._router._pump(block=True)

    def result(self) -> Completion:
        """Block (pumping the router) until done; the Completion. Raises
        ``RequestFailed``/``RequestRejected`` on a failed request."""
        for _ in self.stream():
            pass
        assert self.completion is not None
        return self.completion

    def tokens(self) -> list[int]:
        """Convenience: the completion's tokens (drains the stream)."""
        return list(self.result().tokens)


class Router:
    """Continuous-admission facade over a ``ContainerBackend``.

    Fixed mode: pass ``backend``. Adaptive mode: pass ``backend_factory``
    (count -> backend) plus ``feasible_counts`` (and optionally a
    ``scheduler``/``objective``); the Router starts at the scheduler's
    pick and resizes between windows. Backends built by the factory are
    cached per count and stay warm across resizes; ``close()`` releases
    all of them.
    """

    def __init__(self, backend=None, *,
                 backend_factory: Callable[[int], Any] | None = None,
                 feasible_counts: Sequence[int] | None = None,
                 scheduler: DivideAndSaveScheduler | None = None,
                 objective: str = "energy",
                 epsilon: float = 0.0, seed: int = 0,
                 deadline_s: float | None = None,
                 window: int = 16,
                 window_s: float | None = None,
                 energy: EnergyProxy | None = None,
                 max_retries: int = 1,
                 request_deadline_s: float | None = None,
                 deadline_grace_s: float = 0.5,
                 max_queue: int | None = None,
                 shed_p95_s: float | None = None,
                 shed_window_s: float = 30.0,
                 slo: SLOSpec | None = None,
                 tenant_quota: int | None = None,
                 dispatch_depth: int = 4):
        if backend is None and backend_factory is None:
            raise ValueError("need a backend or a backend_factory")
        self.energy = energy or EnergyProxy()
        self.window = window
        # time-based window close (None = completion count only): sparse
        # traffic then still produces scheduler observations instead of
        # stalling adaptation below the count threshold forever
        self.window_s = window_s
        self.scheduler = scheduler
        # fault-tolerance knobs: bounded re-dispatch after container
        # failures, a default per-request deadline (``deadline_s`` above
        # is the *scheduler objective* constraint, a different thing),
        # the router-side backstop grace over engine-side expiry, and
        # the two admission-control thresholds
        self.max_retries = max_retries
        self.request_deadline_s = request_deadline_s
        self.deadline_grace_s = deadline_grace_s
        self.max_queue = max_queue
        self.shed_p95_s = shed_p95_s
        self.shed_window_s = shed_window_s
        # SLO mode (workload/slo.py): priority-ordered dispatch through a
        # router-side backlog (``dispatch_depth`` bounds backend-side
        # queueing so ordering happens HERE, where ranks exist), shed
        # thresholds derived per class, per-tenant in-flight quotas, and
        # per-class window stats
        self.slo = slo
        self.tenant_quota = tenant_quota
        self.dispatch_depth = dispatch_depth
        self._factory = backend_factory
        self._backends: dict[int, Any] = {}
        if backend_factory is not None:
            if scheduler is None:
                if not feasible_counts:
                    raise ValueError(
                        "adaptive mode needs feasible_counts (or an "
                        "explicit scheduler)")
                slo_kw = ({"objective": "energy_under_slo",
                           "slo_ttfc_p95_s": slo.constraint.ttfc_p95_s}
                          if slo is not None
                          and objective == "energy_under_slo"
                          else {"objective": objective})
                self.scheduler = DivideAndSaveScheduler(
                    list(feasible_counts),
                    deadline_s=deadline_s, epsilon=epsilon, seed=seed,
                    **slo_kw)
            n0 = self.scheduler.pick()
            backend = self._backend_for(n0)
        self.backend = backend
        self._closed = False
        self._handles: dict[int, CompletionHandle] = {}
        self._rid_cid: dict[int, int] = {}
        self._requests: dict[int, Request] = {}   # for re-dispatch
        self._submit_t: dict[int, float] = {}
        self._deadline_abs: dict[int, float] = {}  # router backstop clock
        # priority backlog (SLO mode): (rank, submit seq, rid) heap of
        # registered-but-undispatched requests; entries whose rid left
        # ``_handles`` (terminal) or entered ``_rid_cid`` (placed) are
        # skipped lazily
        self._backlog: list[tuple[int, int, int]] = []
        self._subseq = 0
        self._tenants: Counter = Counter()      # in-flight per tenant
        # per-container multiset of in-flight admission buckets (the
        # bucket-aware half of dispatch)
        self._cid_buckets: list[Counter] = [Counter()
                                            for _ in range(backend.capacity)]
        self.history: list[WindowStats] = []
        self.container_failures: list[ContainerFailure] = []
        self.retry_total = 0
        self.failed_total = 0
        self.shed_total = 0
        # always-on ttfc tail sample for the shed threshold (the window
        # accumulators only run under a scheduler). Entries are
        # (stamp, seconds) so the shed check can age out samples older
        # than shed_window_s — a p95 frozen on a past spike would keep
        # shedding forever after the overload drains
        self._recent_ttfc: deque[tuple[float, float]] = deque(maxlen=64)
        # per-class tail samples (SLO mode): each class sheds against its
        # OWN recent p95, so one class's blown tail cannot shed another's
        self._recent_ttfc_cls: dict[str, deque] = defaultdict(
            lambda: deque(maxlen=64))
        self._target_n: int | None = None    # resize awaiting a drain
        # SLO mode: the first window after a resize is a warm-up — its
        # (loss-censored) tail describes the transition, not the new
        # count — recorded in history but withheld from the scheduler
        self._warmup_window = False
        self._new_window()

    # -- plumbing -------------------------------------------------------
    def _backend_for(self, n: int):
        if n not in self._backends:
            assert self._factory is not None
            self._backends[n] = self._factory(n)
        return self._backends[n]

    def _new_window(self) -> None:
        self._window_t0 = time.perf_counter()
        self._window_stats0 = [self.backend.stats(cid)
                               for cid in range(self.backend.capacity)]
        self._window_done: list[Completion] = []
        self._window_ttfc: list[float] = []
        self._window_retries = 0
        self._window_failed = 0
        self._window_shed = 0
        # per-SLO-class accumulators (only filled in scheduler mode, like
        # _window_done — a fixed router must stay O(1) per request)
        self._window_cls: dict[str, dict] = defaultdict(
            lambda: {"ttfc": [], "lat": [], "shed": 0, "failed": 0})

    @property
    def in_flight(self) -> int:
        return len(self._handles)

    @property
    def n_containers(self) -> int:
        return self.backend.capacity

    # -- admission ------------------------------------------------------
    def _alive_cids(self) -> list[int]:
        """Containers the backend reports ``alive`` (discovered with
        getattr — structural test backends without a supervision surface
        count as all-alive)."""
        alive = getattr(self.backend, "alive", None)
        return [cid for cid in range(self.backend.capacity)
                if alive is None or alive(cid)]

    def _dispatch(self, req: Request,
                  max_load: int | None = None) -> int | None:
        """Pick a container: least-loaded, ties toward a bucket hit.
        None if every container is dead/respawning — or, with
        ``max_load`` (the SLO backlog's bounded-depth dispatch), if
        every live container already holds that many requests: the
        request then stays in the priority backlog instead of burying
        rank order inside a container's FIFO."""
        cids = self._alive_cids()
        if not cids:
            return None
        load = self.backend.load
        if max_load is not None:
            cids = [cid for cid in cids if load(cid) < max_load]
            if not cids:
                return None
        bucket = _bucket(len(req.prompt))

        def key(cid: int):
            return (load(cid),
                    0 if self._cid_buckets[cid][bucket] else 1,
                    cid)
        cid = min(cids, key=key)
        self._cid_buckets[cid][bucket] += 1
        return cid

    def note_ttfc(self, seconds: float, at: float | None = None,
                  priority: str = "default") -> None:
        """Record one time-to-first-chunk sample for the shed-threshold
        p95 (stamped now unless ``at`` is given — tests inject history
        through here rather than poking the deque's tuple layout)."""
        stamp = time.perf_counter() if at is None else at
        self._recent_ttfc.append((stamp, seconds))
        if self.slo is not None:
            self._recent_ttfc_cls[priority].append((stamp, seconds))

    @staticmethod
    def _aged_p95(samples: deque, horizon: float) -> float | None:
        """p95 over a (stamp, value) deque after aging out entries older
        than ``horizon``: a ttfc spike must stop tripping the threshold
        once it leaves the window, or one past burst sheds traffic
        forever after recovery. None below 8 samples (too noisy)."""
        while samples and samples[0][0] < horizon:
            samples.popleft()
        if len(samples) < 8:
            return None
        _, p95 = percentiles([v for _, v in samples])
        return p95

    def _shed_reason(self, req: Request,
                     cls=None) -> tuple[str, str] | None:
        """(kind, reason) when admission should shed ``req``, else None.
        kind ∈ {"tenant", "queue", "slo"} — with an SLO class the queue
        bound and the ttfc threshold are the *class's* (lower-rank
        classes get a smaller queue slice and their own tail sample), so
        batch overload cannot starve interactive admission."""
        if (self.tenant_quota is not None and req.tenant
                and self._tenants[req.tenant] >= self.tenant_quota):
            return ("tenant",
                    f"tenant {req.tenant!r} at quota: "
                    f"{self._tenants[req.tenant]} in flight >= "
                    f"tenant_quota={self.tenant_quota}")
        if self.max_queue is not None:
            limit = (queue_limit(cls, self.max_queue)
                     if cls is not None else self.max_queue)
            if len(self._handles) >= limit:
                extra = (f" (class {cls.name!r} share of "
                         f"max_queue={self.max_queue})" if cls is not None
                         and limit != self.max_queue else "")
                return ("queue",
                        f"queue full: {len(self._handles)} in flight >= "
                        f"{limit}{extra}")
        threshold = (shed_ttfc_threshold(cls, self.shed_p95_s)
                     if cls is not None else self.shed_p95_s)
        if threshold is not None:
            horizon = time.perf_counter() - self.shed_window_s
            samples = (self._recent_ttfc_cls[cls.name]
                       if cls is not None else self._recent_ttfc)
            p95 = self._aged_p95(samples, horizon)
            if p95 is not None and p95 > threshold:
                scope = (f"class {cls.name!r} " if cls is not None else "")
                return ("slo",
                        f"{scope}ttfc p95 {p95:.3f}s over shed threshold "
                        f"{threshold:g}s")
        return None

    def _retry_after_hint(self) -> float:
        """Backpressure hint for shed requests: roughly one median
        request latency (the shortest wait after which the picture can
        have changed), floored so clients cannot hot-loop."""
        if self.history and self.history[-1].latency_p50_s > 0:
            return max(0.05, self.history[-1].latency_p50_s)
        return 0.25

    def _terminal_handle(self, req: Request, ev: Any) -> CompletionHandle:
        """A handle born terminal (shed, or nowhere to dispatch): never
        registered in ``_handles``, its single event already pending."""
        handle = CompletionHandle(req.rid, self)
        handle.failure = ev
        handle._pending.append(ev)
        return handle

    def submit(self, req: Request) -> CompletionHandle:
        """Admit one request now; returns immediately with a handle whose
        ``stream()`` yields the request's events. Under overload the
        handle may come back already shed (its stream yields one
        ``RejectedEvent`` and raises ``RequestRejected``). With an
        ``SLOSpec`` the request enters a rank-ordered backlog instead of
        going straight to a container queue: dispatch happens in
        priority order as containers free up below ``dispatch_depth``."""
        if self._closed:
            raise RuntimeError("router is closed")
        if req.rid in self._handles:
            raise ValueError(f"request id {req.rid} is already in flight")
        now = time.perf_counter()
        cls = self.slo.cls(req.priority) if self.slo is not None else None
        shed = self._shed_reason(req, cls)
        if shed is not None:
            kind, reason = shed
            self.shed_total += 1
            self._window_shed += 1
            pri = cls.name if cls is not None else "default"
            if self.scheduler is not None and self.slo is not None:
                self._window_cls[pri]["shed"] += 1
            return self._terminal_handle(req, RejectedEvent(
                req.rid, reason, self._retry_after_hint(), now,
                kind=kind, priority=pri))
        if req.deadline_s is None and self.request_deadline_s is not None:
            req = dataclasses.replace(
                req, deadline_s=self.request_deadline_s)
        handle = CompletionHandle(req.rid, self)
        if cls is not None:
            handle.priority = cls.name
            handle.tenant = req.tenant
        if self.slo is None:
            # non-SLO path: dispatch immediately (unchanged behaviour)
            cid = self._dispatch(req)
            if cid is None:
                self.failed_total += 1
                self._window_failed += 1
                return self._terminal_handle(req, FailedEvent(
                    req.rid, -1, "container",
                    "no healthy container to dispatch to "
                    "(all circuit-broken or respawning)", now))
            handle.container_id = cid
            self._rid_cid[req.rid] = cid
        self._handles[req.rid] = handle
        self._requests[req.rid] = req
        self._submit_t[req.rid] = now
        if req.deadline_s is not None:
            self._deadline_abs[req.rid] = now + req.deadline_s
        if self.slo is None:
            self.backend.submit(handle.container_id, req)
        else:
            if req.tenant:
                self._tenants[req.tenant] += 1
            heapq.heappush(self._backlog,
                           (cls.rank, self._subseq, req.rid))
            self._subseq += 1
            self._drain_backlog()
        return handle

    def _drain_backlog(self) -> None:
        """Dispatch backlog entries in (rank, arrival) order while a
        live container has load below ``dispatch_depth``. Entries whose
        rid already left ``_handles`` (terminal: deadline backstop,
        cancel) or entered ``_rid_cid`` (already placed) are lazy-
        deleted. Stops at the first undispatchable entry — skipping
        past it would invert priority order."""
        while self._backlog:
            rank, seq, rid = self._backlog[0]
            handle = self._handles.get(rid)
            if handle is None or rid in self._rid_cid:
                heapq.heappop(self._backlog)
                continue
            req = self._requests[rid]
            cid = self._dispatch(req, max_load=self.dispatch_depth)
            if cid is None:
                if not self._alive_cids():
                    # nothing to ever dispatch to: fail rather than
                    # strand the backlog behind dead containers
                    heapq.heappop(self._backlog)
                    self._fail_request(
                        rid, "container",
                        "no healthy container to dispatch to "
                        "(all circuit-broken or respawning)")
                    continue
                break                  # all live containers at depth
            heapq.heappop(self._backlog)
            handle.container_id = cid
            self._rid_cid[rid] = cid
            self.backend.submit(cid, req)

    # -- event pump -----------------------------------------------------
    def _pump(self, block: bool = False) -> list[Event]:
        """Advance the backend and route its events to handles —
        including ``ContainerFailure`` records (retry/fail the lost
        requests) and the router-side deadline backstop. With ``block``
        and nothing to route, naps briefly so process-backend waits
        don't spin."""
        with TraceAnnotation("router.poll"):
            events = self.backend.poll()
            self._route(events)
        if block and not events:
            time.sleep(_IDLE_SLEEP_S)
        return events

    def _route(self, events: list[Event]) -> None:
        """Route one poll's events to their handles, then run the
        deadline backstop, the SLO backlog and the window rotation."""
        now = time.perf_counter()
        for ev in events:
            if isinstance(ev, ContainerFailure):
                self._on_container_failure(ev)
                continue
            handle = self._handles.get(ev.rid)
            if handle is None:          # stale event for a dropped handle
                continue
            cid = getattr(ev, "container_id", None)
            if cid is not None and cid != self._rid_cid.get(ev.rid):
                # stale event from an abandoned incarnation: the request
                # was re-dispatched elsewhere after a container failure,
                # and the old container's late chunks/terminals must not
                # leak into the retried stream (a stale DoneEvent would
                # even pop the router backstop while the live incarnation
                # is still running)
                continue
            handle._pending.append(ev)
            if isinstance(ev, ChunkEvent) and handle.ttfc_s is None:
                handle.ttfc_s = now - self._submit_t[ev.rid]
                self.note_ttfc(handle.ttfc_s, at=now,
                               priority=handle.priority)
            elif isinstance(ev, DoneEvent):
                self._on_done(handle, ev)
            elif isinstance(ev, FailedEvent):
                # engine-side terminal (deadline expired inside the
                # container, resources already freed there): the event is
                # in the handle's queue, just release the router's state
                self._forget(ev.rid)
                handle.failure = ev
                self.failed_total += 1
                self._window_failed += 1
                if self.scheduler is not None and self.slo is not None:
                    self._window_cls[handle.priority]["failed"] += 1
        self._expire_deadlines(now)
        if self.slo is not None:
            # completions freed container slots; pull the backlog forward
            self._drain_backlog()
        if self.scheduler is not None:
            self._maybe_rotate_window()

    def poll(self) -> list[Event]:
        """Public pump: advance containers, route events, return the
        routed batch (a tap — the events still reach their handles)."""
        return self._pump(block=False)

    def _forget(self, rid: int) -> None:
        """Release every router-side record of ``rid`` (the handle's
        terminal state is the caller's to set). Backlog entries are
        lazy-deleted (``_drain_backlog`` skips rids no longer in
        ``_handles``)."""
        cid = self._rid_cid.pop(rid, None)
        req = self._requests.pop(rid, None)
        if cid is not None and req is not None:
            self._cid_buckets[cid][_bucket(len(req.prompt))] -= 1
        handle = self._handles.pop(rid, None)
        if handle is not None and handle.tenant:
            self._tenants[handle.tenant] -= 1
            if self._tenants[handle.tenant] <= 0:
                del self._tenants[handle.tenant]
        self._submit_t.pop(rid, None)
        self._deadline_abs.pop(rid, None)

    def _fail_request(self, rid: int, kind: str, reason: str) -> None:
        """Terminal FailedEvent for an in-flight request (router-side
        origin: retries exhausted, backstop deadline, cancel)."""
        handle = self._handles.get(rid)
        cid = self._rid_cid.get(rid, -1)
        self._forget(rid)
        if handle is None:
            return
        ev = FailedEvent(rid, cid if cid is not None else -1, kind,
                         reason, time.perf_counter())
        handle.failure = ev
        handle._pending.append(ev)
        self.failed_total += 1
        self._window_failed += 1
        if self.scheduler is not None and self.slo is not None:
            self._window_cls[handle.priority]["failed"] += 1

    def _expire_deadlines(self, now: float) -> None:
        """Authoritative deadline backstop: the engine expires deadlines
        itself (that frees slots/blocks exactly where they live), but a
        dead, hung or reply-dropping container can't — so past the grace
        the router cancels backend-side and fails the request here."""
        if not self._deadline_abs:
            return
        expired = [rid for rid, t in self._deadline_abs.items()
                   if now > t + self.deadline_grace_s]
        for rid in expired:
            cid = self._rid_cid.get(rid)
            cancel = getattr(self.backend, "cancel", None)
            if cancel is not None and cid is not None:
                cancel(cid, rid)
            self._fail_request(
                rid, "deadline",
                "deadline exceeded (router backstop, "
                f"{self.deadline_grace_s:g}s past the engine's own expiry)")

    def _on_container_failure(self, fail: ContainerFailure) -> None:
        """Re-dispatch (bounded) or fail every request lost with a
        container. The dead container's bucket counters for these rids
        are released; requests that still fit their deadline go to the
        least-loaded healthy container with a RetryEvent in the stream
        and their *remaining* deadline budget."""
        self.container_failures.append(fail)
        reason = fail.message.splitlines()[0]
        for rid in fail.lost_rids:
            handle = self._handles.get(rid)
            if handle is None:
                continue
            req = self._requests.get(rid)
            old_cid = self._rid_cid.pop(rid, None)
            if old_cid is not None and req is not None:
                self._cid_buckets[old_cid][_bucket(len(req.prompt))] -= 1
            now = time.perf_counter()
            deadline_abs = self._deadline_abs.get(rid)
            handle.attempts += 1
            if req is None:
                self._fail_request(rid, "container",
                                   f"lost to {reason}; request body "
                                   "unknown (cannot re-dispatch)")
                continue
            if deadline_abs is not None and now >= deadline_abs:
                self._fail_request(rid, "deadline",
                                   f"deadline expired while lost to "
                                   f"{reason}")
                continue
            if handle.attempts > self.max_retries:
                self._fail_request(
                    rid, "container",
                    f"retries exhausted after {handle.attempts} attempts; "
                    f"last failure: {fail.message}")
                continue
            cid = self._dispatch(req)
            if cid is None:
                self._fail_request(
                    rid, "container",
                    f"no healthy container left to retry on; "
                    f"last failure: {fail.message}")
                continue
            self._rid_cid[rid] = cid
            handle.container_id = cid
            if deadline_abs is not None:
                # re-arm the router backstop for the new incarnation: the
                # first incarnation's terminal may already have popped
                # _deadline_abs, and a retry onto a reply-dropping
                # container would otherwise hang with only the engine's
                # (unreachable) expiry guarding it
                self._deadline_abs[rid] = deadline_abs
            self.retry_total += 1
            self._window_retries += 1
            handle._pending.append(RetryEvent(
                rid, cid, handle.attempts, reason, now))
            resubmit = req
            if deadline_abs is not None:
                # the retry inherits the REMAINING budget, not a fresh
                # deadline — end-to-end means across attempts
                resubmit = dataclasses.replace(
                    req, deadline_s=deadline_abs - now)
            try:
                self.backend.submit(cid, resubmit)
            except RuntimeError as e:
                self._fail_request(rid, "container",
                                   f"re-dispatch to container {cid} "
                                   f"failed: {e}")

    def _on_done(self, handle: CompletionHandle, ev: DoneEvent) -> None:
        comp = ev.completion
        handle.completion = comp
        handle.done_at = time.perf_counter()
        submit_t = self._submit_t.get(handle.rid)
        self._forget(handle.rid)
        if self.scheduler is not None:
            # window accumulators only exist to feed the scheduler; a
            # fixed-capacity router must not retain one Completion per
            # request forever (the lists are only reset at rotation)
            self._window_done.append(comp)
            if handle.ttfc_s is not None:
                self._window_ttfc.append(handle.ttfc_s)
            if self.slo is not None:
                acc = self._window_cls[handle.priority]
                if handle.ttfc_s is not None:
                    acc["ttfc"].append(handle.ttfc_s)
                if submit_t is not None:
                    acc["lat"].append(handle.done_at - submit_t)

    def cancel(self, rid: int, reason: str = "cancelled by caller") -> bool:
        """Cancel an in-flight request: backend-side removal (slot and
        paged blocks freed via the engine's cancel path) plus a terminal
        ``FailedEvent(kind="cancelled")`` on the handle. Returns whether
        the request was still in flight."""
        if rid not in self._handles:
            return False
        cid = self._rid_cid.get(rid)
        cancel = getattr(self.backend, "cancel", None)
        if cancel is not None and cid is not None:
            cancel(cid, rid)
        self._fail_request(rid, "cancelled", reason)
        return True

    def drain(self) -> None:
        """Pump until every in-flight request reached a terminal event
        (their handles still hold any unconsumed events). Failed
        requests leave ``_handles`` too, so a drain over failures
        terminates instead of hanging."""
        while self._handles:
            self._pump(block=True)

    # -- windowed adaptation -------------------------------------------
    def _maybe_rotate_window(self) -> None:
        """Sliding-window adaptation, split in two so continuous traffic
        still adapts: the *stats window* closes on completion count — or,
        with ``window_s``, on elapsed wall time, so sparse traffic still
        produces scheduler observations instead of stalling adaptation
        below the count threshold forever — while the *backend swap*
        waits for the stream to drain (resizing under a live request
        would strand its slot). A time-expired window with zero
        completions just restarts its clock: observing it would feed the
        scheduler an all-idle sample with no latency content."""
        time_up = (self.window_s is not None
                   and time.perf_counter() - self._window_t0
                   >= self.window_s)
        if len(self._window_done) >= self.window:
            self._observe_window()
        elif time_up:
            if self._window_done:
                self._observe_window()
            else:
                self._new_window()       # idle window: restart the clock
        if self._target_n is None or self._handles:
            return
        if self._target_n != self.backend.capacity \
                and self._factory is not None:
            if self._window_done:
                # the partial window ran entirely on the outgoing
                # backend; record it before its stats0 go stale
                self._observe_window(repick=False)
            self.backend = self._backend_for(self._target_n)
            self._cid_buckets = [Counter()
                                 for _ in range(self.backend.capacity)]
            # shed-threshold tails described the OUTGOING backend; kept
            # across the resize they would shed (and loss-censor) the new
            # count's first windows and brand it infeasible forever
            self._recent_ttfc.clear()
            self._recent_ttfc_cls.clear()
            # SLO mode only: mean observations average a transition
            # away, but one loss-censored tail sample from the swap
            # window can brand the incoming count infeasible
            self._warmup_window = self.slo is not None
            self._new_window()
        self._target_n = None

    def _observe_window(self, repick: bool = True) -> None:
        n = self.backend.capacity
        wall = time.perf_counter() - self._window_t0
        busy = [self.backend.stats(cid)[0] - self._window_stats0[cid][0]
                for cid in range(n)]
        toks = sum(self.backend.stats(cid)[1] - self._window_stats0[cid][1]
                   for cid in range(n))
        energy_j = sum(self.energy.container_energy(wall, b, n)
                       for b in busy)
        ttfc50, ttfc95 = percentiles(self._window_ttfc)
        lat50, lat95 = latency_percentiles(self._window_done)
        per_class: dict = {}
        if self.slo is not None:
            per_class = {
                name: class_window(self.slo.cls(name), name,
                                   acc["ttfc"], acc["lat"],
                                   acc["shed"], acc["failed"])
                for name, acc in sorted(self._window_cls.items())}
        self.history.append(WindowStats(
            len(self.history), n, wall, energy_j, len(self._window_done),
            toks, toks / wall if wall > 0 else 0.0, ttfc50, ttfc95,
            lat50, lat95, n_retries=self._window_retries,
            n_failed=self._window_failed, n_shed=self._window_shed,
            prefix_hit_tokens=sum(getattr(c, "prefix_hit_tokens", 0)
                                  for c in self._window_done),
            per_class=per_class))
        assert self.scheduler is not None
        if self._warmup_window:
            # transition window (see __init__): keep the stats, withhold
            # the scheduler observation and keep the current pick
            self._warmup_window = False
            self._new_window()
            return
        done = len(self._window_done)
        scale = 1.0
        if self.window_s is not None and 0 < done < self.window:
            # time-closed short window: normalise wall/energy to the
            # canonical window size so observations stay comparable
            # across sparse and busy windows (per-request cost is the
            # quantity the convex fit models)
            scale = self.window / done
        # the scheduler's tail sample is the CONSTRAINT class's p95 (the
        # tightest target — that is what energy_under_slo guards),
        # shed-censored: admission pins the admitted p95 at the shed
        # threshold, so shed arrivals must count as violations or every
        # count looks feasible. Overall window p95 when no SLO is set
        q95: float | None = ttfc95 if self._window_ttfc else None
        if self.slo is not None:
            cname = self.slo.constraint.name
            acc = self._window_cls.get(cname)
            if acc is not None:
                q95 = censored_ttfc_p95(
                    acc["ttfc"], acc["shed"] + acc["failed"],
                    2.0 * self.slo.constraint.ttfc_p95_s)
        self.scheduler.observe(n, wall * scale, energy_j * scale,
                               ttfc_p95_s=q95)
        if repick:
            self._target_n = self.scheduler.pick()
        self._new_window()

    @property
    def choice(self) -> int:
        """Exploitation-only container count (what a converged deployment
        runs); only meaningful in adaptive mode."""
        assert self.scheduler is not None
        return self.scheduler.best()

    # -- wave shim ------------------------------------------------------
    def serve_wave(self, requests: list[Request]
                   ) -> tuple[list[Completion], list[ContainerResult],
                              float, float]:
        """The legacy wave API on top of streaming: submit-all + drain,
        per-container accounting reconstructed with the existing
        ``assemble_wave``. Completions come back in submission order."""
        _warn_wave_shim("Router.serve_wave")
        # pin the backend for the whole wave: an adaptive window boundary
        # inside drain() may swap self.backend, and this wave's stats
        # deltas must come from the backend that served it
        backend = self.backend
        stats0 = [backend.stats(cid) for cid in range(backend.capacity)]
        t0 = time.perf_counter()
        handles = [self.submit(r) for r in requests]
        self.drain()
        wall = time.perf_counter() - t0
        failed = [h.rid for h in handles if h.completion is None]
        if failed:
            # waves have no per-request failure surface: a request that
            # ended in a FailedEvent (even after retries) fails the wave
            raise RuntimeError(
                f"wave failed: requests {failed} ended without a "
                "completion (see router.container_failures)")
        capacity = backend.capacity
        segments: list[list[Request]] = [[] for _ in range(capacity)]
        comps: list[list[Completion]] = [[] for _ in range(capacity)]
        # _rid_cid entries are popped on completion; reconstruct the
        # dispatch segments from the handles' completions instead
        by_rid = {h.rid: h.completion for h in handles}
        # per-container wall: submit → last DoneEvent arrival for that
        # container (matching the pool contract, where a fast container
        # reports its own wall, not the slowest sibling's)
        last = [0.0] * capacity
        for r, h in zip(requests, handles):
            cid = h.container_id
            segments[cid].append(r)
            comps[cid].append(by_rid[r.rid])
            if h.done_at is not None:
                last[cid] = max(last[cid], h.done_at - t0)
        out = [(comps[cid], last[cid],
                backend.stats(cid)[0] - stats0[cid][0],
                backend.stats(cid)[1] - stats0[cid][1])
               for cid in range(capacity)]
        _, results, energy = assemble_wave(out, segments, wall, self.energy)
        ordered = [by_rid[r.rid] for r in requests]
        return ordered, results, wall, energy

    def serve(self, requests: list[Request]
              ) -> tuple[list[Completion], list[ContainerResult]]:
        ordered, results, _, _ = self.serve_wave(requests)
        return ordered, results

    # -- lifecycle ------------------------------------------------------
    def close(self) -> None:
        """Close the backend (and every cached adaptive backend). Handles
        still mid-stream raise rather than hang."""
        if self._closed:
            return
        self._closed = True
        backends = set(self._backends.values()) | {self.backend}
        for b in backends:
            b.close()
        self._backends = {}

    def __enter__(self) -> "Router":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
