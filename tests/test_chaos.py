"""Chaos suite: every injected fault ends in a correct completion or a
typed failure — never a hang, a stuck CompletionHandle, leaked paged
blocks, or an orphaned child process.

The faults come from the test-only ``FaultPlan`` harness
(serving/faults.py): containers are killed mid-stream, engines raise,
reply pipes drop messages, block allocation is refused. The assertions
are the fault-tolerance contract of ISSUE 7:

* requests lost with a container are retried (``RetryEvent``) and
  complete *bit-correct* on the survivor/respawn, or fail typed
  (``RequestFailed``) once retries/containers run out;
* deadlines cut through silent containers (router backstop) and free
  paged blocks with exact conservation;
* overload sheds (``RequestRejected``) instead of queueing unboundedly;
* process children always exit with a classified nonzero code and are
  reaped — ``close()`` leaves no live descendants.
"""
from __future__ import annotations

import multiprocessing as mp
import time

import numpy as np
import pytest

from repro.serving import (Completion, ContainerFailure, DoneEvent,
                           EngineConfig, FailedEvent, Fault, FaultPlan,
                           RejectedEvent, Request, RequestFailed,
                           RequestRejected, RetryEvent, Router)
from repro.serving.backend import ProcessBackend, ThreadBackend
from repro.serving.engine import ServingEngine
from repro.serving.faults import (EXIT_FAULT_KILL, EXIT_STEP_ERROR,
                                  FaultInjector, InjectedFault,
                                  describe_exitcode)

pytestmark = pytest.mark.filterwarnings(
    "ignore::DeprecationWarning")


def _requests(cfg, plens_max_new, seed=0, deadline_s=None):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, (plen,),
                                        dtype=np.int32),
                    max_new_tokens=mn, deadline_s=deadline_s)
            for i, (plen, mn) in enumerate(plens_max_new)]


def _clone(reqs):
    return [Request(r.rid, r.prompt.copy(), r.max_new_tokens,
                    deadline_s=r.deadline_s) for r in reqs]


def _blocking_tokens(model, params, reqs):
    eng = ServingEngine(model, params,
                        EngineConfig(n_slots=2, max_len=64))
    eng.submit_many(_clone(reqs))
    return {c.rid: list(c.tokens) for c in eng.run()}


def _paged_conserved(engine) -> bool:
    cb = engine.cache_backend
    return (cb.allocator.n_free + cb.n_live_blocks
            == cb.layout.max_blocks)


# ---------------------------------------------------------------------------
# harness unit tests (no engines)
# ---------------------------------------------------------------------------
def test_fault_plan_scopes_by_container_and_incarnation():
    plan = FaultPlan((Fault("kill", container_id=0, after_steps=2),
                      Fault("error", container_id=1, incarnation=None),
                      Fault("drop_replies", container_id=0,
                            incarnation=1, count=3)))
    assert len(plan.for_container(0, 0)) == 1          # kill only
    assert len(plan.for_container(0, 1)) == 1          # drop only
    assert len(plan.for_container(1, 0)) == 1          # error, any inc
    assert len(plan.for_container(1, 5)) == 1
    assert plan.for_container(2, 0) == ()


def test_fault_injector_kill_fires_after_threshold():
    plan = FaultPlan((Fault("kill", container_id=0, after_steps=2),))
    inj = FaultInjector(plan, 0, 0)
    assert inj.armed
    inj.on_step(1)
    inj.on_step(2)
    with pytest.raises(InjectedFault) as ei:
        inj.on_step(3)
    assert ei.value.fault.kind == "kill"
    # incarnation 1 is out of scope: unarmed, hooks are no-ops
    inj1 = FaultInjector(plan, 0, 1)
    assert not inj1.armed
    inj1.on_step(99)


def test_fault_injector_counted_hooks_drain():
    plan = FaultPlan((Fault("drop_replies", container_id=0, count=2),
                      Fault("delay_replies", container_id=0, count=1,
                            delay_s=0.25),
                      Fault("refuse_blocks", container_id=0, count=3)))
    inj = FaultInjector(plan, 0, 0)
    assert [inj.drop_reply() for _ in range(4)] == [True, True,
                                                   False, False]
    assert inj.reply_delay() == 0.25
    assert inj.reply_delay() == 0.0
    assert [inj.refuse_alloc() for _ in range(5)] == [True, True, True,
                                                      False, False]


def test_unknown_fault_kind_rejected():
    with pytest.raises(ValueError, match="unknown fault kind"):
        Fault("segfault", container_id=0)


def test_describe_exitcode():
    assert "injected fault kill" in describe_exitcode(EXIT_FAULT_KILL)
    assert "engine step error" in describe_exitcode(EXIT_STEP_ERROR)
    assert "signal 9" in describe_exitcode(-9)
    assert "unknown" in describe_exitcode(None)


# ---------------------------------------------------------------------------
# thread backend: kill / respawn / retry / circuit breaker
# ---------------------------------------------------------------------------
def test_thread_kill_midstream_retries_bitcorrect(reduced_models):
    """Kill container 0 (incarnation 0 only) mid-stream: its in-flight
    requests ride a RetryEvent to a healthy home and every request's
    completion still bit-matches the blocking reference."""
    model, params = reduced_models["qwen3-0.6b"]
    cfg = model.cfg
    reqs = _requests(cfg, [(6, 4), (9, 4), (5, 4), (7, 4)], seed=5)
    want = _blocking_tokens(model, params, reqs)
    plan = FaultPlan((Fault("kill", container_id=0, after_steps=2),))
    # chunk_tokens=1: one token per macro-step, so the step-count fault
    # is guaranteed to fire while requests are still in flight (roofline
    # chunking could finish a 4-token request inside one step)
    config = EngineConfig(n_slots=2, max_len=64, chunk_tokens=1)
    backend = ThreadBackend(model, params, 2, config=config,
                            fault_plan=plan, max_respawns=2)
    with Router(backend, max_retries=2) as router:
        handles = [router.submit(r) for r in _clone(reqs)]
        events = {}
        for h in handles:
            events[h.rid] = list(h.stream())     # raises on any failure
        got = {h.rid: list(h.completion.tokens) for h in handles}
    assert got == want
    # the kill surfaced as exactly one typed container failure, its lost
    # requests were re-dispatched, and their post-retry chunk concat is
    # the completion (pre-retry chunks belong to the aborted attempt)
    assert len(router.container_failures) == 1
    fail = router.container_failures[0]
    assert fail.kind == "error" and fail.container_id == 0
    assert "injected fault: kill" in fail.message
    retried = set()
    for rid, evs in events.items():
        assert isinstance(evs[-1], DoneEvent)
        retries = [i for i, e in enumerate(evs)
                   if isinstance(e, RetryEvent)]
        if retries:
            retried.add(rid)
            tail = [t for e in evs[retries[-1] + 1:-1] for t in e.tokens]
            assert tail == got[rid]
    assert retried == set(fail.lost_rids)
    assert router.retry_total == len(fail.lost_rids) > 0
    assert backend.alive(0)                      # respawned, serving


def test_thread_circuit_breaker_trips_to_typed_failure(reduced_models):
    """A container that dies every incarnation exhausts its respawn
    budget; the request exhausts retries and fails typed — no hang."""
    model, params = reduced_models["qwen3-0.6b"]
    plan = FaultPlan((Fault("kill", container_id=0, incarnation=None),))
    backend = ThreadBackend(model, params, 1, n_slots_per_container=2,
                            max_len=64, fault_plan=plan, max_respawns=1)
    with Router(backend, max_retries=5) as router:
        h = router.submit(_requests(model.cfg, [(6, 4)], seed=7)[0])
        with pytest.raises(RequestFailed) as ei:
            h.result()
        assert ei.value.event.kind == "container"
        assert h.failure is not None and h.completion is None
        assert not backend.alive(0)
        # original + 1 respawn, both killed
        assert len(router.container_failures) == 2
        with pytest.raises(RuntimeError, match="circuit-broken"):
            backend.submit(0, _requests(model.cfg, [(5, 2)], seed=8)[0])
        with pytest.raises(RuntimeError, match="circuit-broken"):
            backend.drain()
        # a NEW submission sees no healthy container: fails typed at
        # admission instead of dispatching into the dead backend
        h2 = router.submit(_requests(model.cfg, [(5, 2)], seed=9)[0])
        with pytest.raises(RequestFailed, match="no healthy container"):
            h2.result()


def test_thread_refuse_blocks_stalls_then_serves(reduced_models):
    """Injected paged-pool exhaustion: admission stalls while the fault
    has budget, then the same requests admit and complete bit-correct;
    block conservation holds throughout."""
    model, params = reduced_models["qwen3-0.6b"]
    cfg = model.cfg
    reqs = _requests(cfg, [(6, 3), (9, 4), (5, 2)], seed=11)
    want = _blocking_tokens(model, params, reqs)
    plan = FaultPlan((Fault("refuse_blocks", container_id=0, count=4),))
    config = EngineConfig(n_slots=2, max_len=64, cache="paged",
                          block_size=8)
    backend = ThreadBackend(model, params, 1, config=config,
                            fault_plan=plan)
    with Router(backend) as router:
        handles = [router.submit(r) for r in _clone(reqs)]
        got = {h.rid: h.tokens() for h in handles}
        assert got == want
        assert _paged_conserved(backend.engines[0])


# ---------------------------------------------------------------------------
# deadlines / cancellation / shedding
# ---------------------------------------------------------------------------
def test_deadline_expiry_fails_typed_and_conserves_blocks(reduced_models):
    model, params = reduced_models["qwen3-0.6b"]
    cfg = model.cfg
    config = EngineConfig(n_slots=2, max_len=64, cache="paged",
                          block_size=8)
    backend = ThreadBackend(model, params, 1, config=config)
    with Router(backend, request_deadline_s=1e-4) as router:
        h = router.submit(_requests(cfg, [(6, 30)], seed=13)[0])
        with pytest.raises(RequestFailed) as ei:
            h.result()
        assert ei.value.event.kind == "deadline"
        assert isinstance(h.failure, FailedEvent)
        # the stack still serves: an undeadlined request admits into the
        # freed blocks and completes
        ok = Request(rid=100, prompt=_requests(cfg, [(6, 3)],
                                               seed=13)[0].prompt,
                     max_new_tokens=3)
        assert len(router.submit(ok).tokens()) == 3
        eng = backend.engines[0]
        assert _paged_conserved(eng)
        assert not eng.has_work                 # nothing stuck in a slot


def test_mid_decode_deadline_frees_slot(reduced_models):
    """A deadline that lands mid-decode (not queued) frees the slot and
    emits the typed failure with progress in the reason."""
    model, params = reduced_models["qwen3-0.6b"]
    cfg = model.cfg
    backend = ThreadBackend(model, params, 1, n_slots_per_container=2,
                            max_len=64)
    # a huge grace keeps the router backstop out of the race: the first
    # step (admit + compile) can exceed deadline+grace on a cold process,
    # and the backstop would then cancel before the ENGINE's own expiry —
    # the path under test here — ever gets to emit its typed failure
    with Router(backend, deadline_grace_s=60.0) as router:
        h = router.submit(Request(rid=0,
                                  prompt=np.arange(6, dtype=np.int32),
                                  max_new_tokens=500, deadline_s=0.35))
        router.poll()                            # admit + first chunk
        with pytest.raises(RequestFailed) as ei:
            h.result()
        assert ei.value.event.kind == "deadline"
        assert "mid-decode" in ei.value.event.reason
        assert not backend.engines[0].has_work


def test_router_cancel_frees_resources(reduced_models):
    model, params = reduced_models["qwen3-0.6b"]
    cfg = model.cfg
    backend = ThreadBackend(model, params, 1, n_slots_per_container=2,
                            max_len=64)
    with Router(backend) as router:
        h = router.submit(Request(rid=0,
                                  prompt=np.arange(6, dtype=np.int32),
                                  max_new_tokens=500))
        router.poll()                            # mid-decode
        assert router.cancel(0, "user went away")
        assert not router.cancel(0)              # already gone
        with pytest.raises(RequestFailed) as ei:
            h.result()
        assert ei.value.event.kind == "cancelled"
        assert not backend.engines[0].has_work   # slot actually freed
        # the freed slot serves the next request normally
        h1 = router.submit(_requests(cfg, [(6, 3)], seed=17)[0])
        assert len(h1.tokens()) == 3
    assert router.failed_total == 1          # the cancel, counted once


def test_max_queue_sheds_with_retry_after(reduced_models):
    model, params = reduced_models["qwen3-0.6b"]
    cfg = model.cfg
    backend = ThreadBackend(model, params, 1, n_slots_per_container=2,
                            max_len=64)
    reqs = _requests(cfg, [(6, 6), (7, 6), (5, 3)], seed=19)
    with Router(backend, max_queue=2) as router:
        keep = [router.submit(r) for r in reqs[:2]]
        shed = router.submit(reqs[2])
        evs = []
        with pytest.raises(RequestRejected) as ei:
            for ev in shed.stream():
                evs.append(ev)
        assert len(evs) == 1 and isinstance(evs[0], RejectedEvent)
        assert ei.value.event.retry_after_s > 0
        assert "queue full" in ei.value.event.reason
        assert router.shed_total == 1
        # shed request never reached a container; the admitted ones
        # complete untouched
        for h in keep:
            assert len(h.tokens()) == 6
        # queue drained: the SAME request admits now
        retry = Request(rid=99, prompt=reqs[2].prompt.copy(),
                        max_new_tokens=3)
        assert len(router.submit(retry).tokens()) == 3


def test_shed_p95_threshold_sheds_under_slow_ttfc(reduced_models):
    """Synthetic ttfc history over the shed threshold makes admission
    reject with the typed event (windowed tail shedding)."""
    model, params = reduced_models["qwen3-0.6b"]
    backend = ThreadBackend(model, params, 1, n_slots_per_container=2,
                            max_len=64)
    with Router(backend, shed_p95_s=0.5) as router:
        for _ in range(16):                      # observed slow tail
            router.note_ttfc(2.0)
        h = router.submit(_requests(model.cfg, [(6, 2)], seed=23)[0])
        with pytest.raises(RequestRejected, match="shed threshold"):
            h.result()
        assert router.shed_total == 1


def test_shed_p95_recovers_once_spike_leaves_window(reduced_models):
    """Burst → drain → admitted again: the shed-threshold ttfc sample is
    bounded by time, so a past overload spike stops tripping
    ``shed_p95_s`` once it ages past ``shed_window_s``. Pre-fix the
    sample never aged out and one burst shed traffic forever."""
    model, params = reduced_models["qwen3-0.6b"]
    backend = ThreadBackend(model, params, 1, n_slots_per_container=2,
                            max_len=64)
    with Router(backend, shed_p95_s=0.5, shed_window_s=0.25) as router:
        for _ in range(16):                 # the burst's slow tail
            router.note_ttfc(2.0)
        shed = router.submit(_requests(model.cfg, [(6, 2)], seed=23)[0])
        with pytest.raises(RequestRejected, match="shed threshold"):
            shed.result()
        assert router.shed_total == 1
        time.sleep(0.3)                     # spike leaves the window
        ok = router.submit(Request(rid=50, prompt=np.arange(
            6, dtype=np.int32), max_new_tokens=2))
        assert len(ok.tokens()) == 2        # admitted and served
        assert router.shed_total == 1


# ---------------------------------------------------------------------------
# stale events from abandoned incarnations (scripted structural backend)
# ---------------------------------------------------------------------------
class _ScriptedBackend:
    """Structural backend replaying a poll() tape: stages the
    cross-incarnation races (a stale terminal arriving AFTER the request
    was re-homed by a retry) that real backends only produce under
    timing-dependent chaos. ``loads`` steer ``Router._dispatch``."""

    def __init__(self, capacity, tape):
        self.capacity = capacity
        self._tape = list(tape)
        self.submitted: list[tuple[int, int]] = []
        self._load = [0] * capacity

    def submit(self, cid, req):
        self.submitted.append((cid, req.rid))
        self._load[cid] += 1

    def poll(self):
        return self._tape.pop(0) if self._tape else []

    def load(self, cid):
        return self._load[cid]

    def stats(self, cid):
        return (0.0, 0)

    def cancel(self, cid, rid):
        pass

    def close(self):
        pass


def test_stale_terminal_after_retry_is_ignored_and_backstop_fires():
    """A request retried off a hung container must not be terminated by
    the old incarnation's late DoneEvent (wrong tokens, and it would pop
    the router backstop while the live incarnation still runs). With the
    new home silent, the re-armed backstop is what ends it — typed."""
    req = Request(rid=7, prompt=np.arange(6, dtype=np.int32),
                  max_new_tokens=4, deadline_s=0.2)
    stale = DoneEvent(7, 0, Completion(7, [1, 2, 3, 4], 6, 0.01), 0.0)
    tape = [
        [ContainerFailure(0, "hung", "heartbeat timeout", 0.0,
                          lost_rids=(7,))],
        [stale],                       # container 0 wakes up too late
    ]
    backend = _ScriptedBackend(2, tape)
    with Router(backend, deadline_grace_s=0.1, max_retries=2) as router:
        h = router.submit(req)
        assert backend.submitted == [(0, 7)]
        router.poll()                  # failure -> retry, re-homed to c1
        assert backend.submitted[-1] == (1, 7)
        router.poll()                  # stale DoneEvent from container 0
        assert h.completion is None, (
            "aborted incarnation's completion leaked into the retried "
            "stream")
        with pytest.raises(RequestFailed) as ei:
            h.result()                 # c1 stays silent: backstop fires
        assert ei.value.event.kind == "deadline"
        assert "backstop" in ei.value.event.reason
        assert h.attempts == 1


# ---------------------------------------------------------------------------
# process backend chaos (slow: real spawns)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_process_kill_child_respawns_and_recovers(reduced_models):
    """Kill 1 of n=2 pinned children mid-stream: all in-flight requests
    complete bit-correct (survivor or respawn), the failure is typed
    with the injected exitcode, and close() leaves no orphans."""
    model, params = reduced_models["qwen3-0.6b"]
    cfg = model.cfg
    reqs = _requests(cfg, [(6, 4), (9, 4), (5, 4), (7, 4)], seed=29)
    want = _blocking_tokens(model, params, reqs)
    plan = FaultPlan((Fault("kill", container_id=0, after_steps=1),))
    # chunk_tokens=1 (see the thread kill test): the step-count fault
    # must land while requests are in flight
    backend = ProcessBackend(cfg, 2, n_slots_per_container=2, max_len=64,
                             params_seed=0, allow_shared_cores=True,
                             chunk_tokens=1, fault_plan=plan,
                             max_respawns=2, respawn_backoff_s=0.05)
    t_fail = t_recover = None
    with Router(backend, max_retries=2) as router:
        handles = [router.submit(r) for r in _clone(reqs)]
        got, events = {}, {}
        for h in handles:
            events[h.rid] = list(h.stream())
            got[h.rid] = list(h.completion.tokens)
        assert got == want
        fails = [f for f in router.container_failures if f.kind == "dead"]
        assert len(fails) == 1
        assert fails[0].exitcode == EXIT_FAULT_KILL
        assert "injected fault kill" in fails[0].message
        assert set(fails[0].lost_rids) == {
            rid for rid, evs in events.items()
            if any(isinstance(e, RetryEvent) for e in evs)}
        t_fail = fails[0].time_s
        # the respawn must come back: pump until container 0 serves again
        deadline = time.perf_counter() + 120
        while not backend.alive(0):
            assert time.perf_counter() < deadline, "respawn never landed"
            router.poll()
            time.sleep(0.05)
        t_recover = time.perf_counter()
        # ... and serve bit-correct on incarnation 1 (fault was inc-0)
        again = Request(rid=50, prompt=reqs[0].prompt.copy(),
                        max_new_tokens=4)
        backend.submit(0, again)
        done = {}
        deadline = time.perf_counter() + 120
        while 50 not in done:
            assert time.perf_counter() < deadline, "respawn never served"
            for ev in backend.poll():
                if isinstance(ev, DoneEvent):
                    done[ev.rid] = list(ev.completion.tokens)
            time.sleep(0.01)
        assert done[50] == want[0]
    assert t_recover - t_fail < 120
    # no orphaned processes: every child (including the respawn) reaped
    for p in mp.active_children():
        p.join(timeout=10)
    assert mp.active_children() == []


@pytest.mark.slow
def test_process_drop_replies_caught_by_deadline_backstop(reduced_models):
    """A child that silently swallows every reply (message loss) cannot
    hang the stream: heartbeats keep it 'alive', but the router-side
    deadline backstop cancels and fails the request typed."""
    model, params = reduced_models["qwen3-0.6b"]
    cfg = model.cfg
    plan = FaultPlan((Fault("drop_replies", container_id=0, count=-1),))
    backend = ProcessBackend(cfg, 1, n_slots_per_container=2, max_len=64,
                             params_seed=0, allow_shared_cores=True,
                             fault_plan=plan, max_respawns=0)
    with Router(backend, request_deadline_s=2.0,
                deadline_grace_s=0.5, max_retries=0) as router:
        h = router.submit(_requests(cfg, [(6, 400)], seed=31)[0])
        t0 = time.perf_counter()
        with pytest.raises(RequestFailed) as ei:
            h.result()
        assert ei.value.event.kind == "deadline"
        assert "backstop" in ei.value.event.reason
        assert time.perf_counter() - t0 < 60
    for p in mp.active_children():
        p.join(timeout=10)
    assert mp.active_children() == []


@pytest.mark.slow
def test_process_retry_onto_drop_replies_hits_backstop(reduced_models):
    """Kill the first incarnation's container so the request is retried
    onto a container that silently drops every reply: the router-side
    backstop must stay armed across the re-dispatch and end the retried
    incarnation typed — never a hang."""
    model, params = reduced_models["qwen3-0.6b"]
    cfg = model.cfg
    plan = FaultPlan((Fault("kill", container_id=0, after_steps=1),
                      Fault("drop_replies", container_id=1, count=-1)))
    backend = ProcessBackend(cfg, 2, n_slots_per_container=2, max_len=64,
                             params_seed=0, allow_shared_cores=True,
                             chunk_tokens=1, fault_plan=plan,
                             max_respawns=0)
    # the deadline must outlive child spawn + prefill compile + the kill
    # -> retry hop, or the backstop fires on the FIRST incarnation and
    # the test stops exercising the re-dispatch path it is pinning
    with Router(backend, request_deadline_s=30.0, deadline_grace_s=1.0,
                max_retries=2) as router:
        h = router.submit(_requests(cfg, [(6, 400)], seed=41)[0])
        t0 = time.perf_counter()
        with pytest.raises(RequestFailed) as ei:
            h.result()
        assert h.attempts == 1                   # it WAS re-dispatched
        assert ei.value.event.kind == "deadline"
        assert "backstop" in ei.value.event.reason
        assert time.perf_counter() - t0 < 120
    for p in mp.active_children():
        p.join(timeout=10)
    assert mp.active_children() == []


@pytest.mark.slow
def test_process_step_error_reports_classified_exit(reduced_models):
    """An engine error in the child crosses the pipe as a typed 'error'
    failure (traceback included) and the child exits nonzero — no more
    silent exit-0 sharing with clean shutdown."""
    model, params = reduced_models["qwen3-0.6b"]
    cfg = model.cfg
    plan = FaultPlan((Fault("error", container_id=0),))
    backend = ProcessBackend(cfg, 1, n_slots_per_container=2, max_len=64,
                             params_seed=0, allow_shared_cores=True,
                             fault_plan=plan, max_respawns=0)
    with Router(backend, max_retries=0) as router:
        h = router.submit(_requests(cfg, [(6, 4)], seed=37)[0])
        with pytest.raises(RequestFailed, match="injected fault: error"):
            h.result()
        fails = router.container_failures
        assert fails and fails[0].kind == "error"
        assert not backend.alive(0)              # max_respawns=0: broken
        # the child's own exit is classified, observable once reaped
        deadline = time.perf_counter() + 30
        while mp.active_children() and time.perf_counter() < deadline:
            time.sleep(0.05)
    for p in mp.active_children():
        p.join(timeout=10)
    assert mp.active_children() == []


def test_prefill_error_fails_request_instead_of_hanging(reduced_models,
                                                        monkeypatch):
    """A step that raises inside admission (a prefill that fails to
    compile) has already popped its requests off the queue: they must
    still be reported lost and end typed, not wait forever."""
    model, params = reduced_models["qwen3-0.6b"]

    def broken_prefill(self, n_seqs, bl):
        def fn(*args):
            raise RuntimeError("prefill failed to compile")
        return fn
    monkeypatch.setattr(ServingEngine, "_prefill_fn", broken_prefill)
    backend = ThreadBackend(model, params, 1, n_slots_per_container=2,
                            max_len=64, max_respawns=0)
    with Router(backend, max_retries=1) as router:
        h = router.submit(_requests(model.cfg, [(6, 4)], seed=41)[0])
        for _ in range(20):
            router.poll()
            if h.done:
                break
        assert h.done and h.failure is not None
        assert "prefill failed to compile" in backend.failures[0].message
        assert backend.failures[0].lost_rids == (h.rid,)
