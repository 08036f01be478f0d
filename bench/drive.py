"""Drive the system under test through its Router and log every request.

One thread does everything: it submits each request when it is due (open
loop) or when a client's last request finished (closed loop), and pumps
the Router, which steps the engines. Every call into the Router sits in a
``jax.profiler.TraceAnnotation`` span (``bench.submit``, ``bench.poll``,
``bench.wait``), so that a device trace can say what the host was doing
in each idle gap.

Times are the host's ``perf_counter``, relative to the window's opening.
A request's first token and each later token are stamped when the Router
hands their event over, which is when a client of the Router would see
them.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable

import numpy as np
from jax.profiler import TraceAnnotation

from bench.traffic import Req

# seconds past the close to wait for the window's requests' first tokens
# (open loop) before a request still without one counts as failed
LATE_LIMIT_S = 60.0
_NAP_S = 0.0005


@dataclasses.dataclass
class Record:
    rid: int
    due_s: float
    prompt_len: int
    max_new: int
    submit_s: float | None = None
    token_s: list = dataclasses.field(default_factory=list)  # per token
    tokens: list = dataclasses.field(default_factory=list)
    done_s: float | None = None
    failed: str | None = None

    @property
    def first_s(self) -> float | None:
        return self.token_s[0] if self.token_s else None


@dataclasses.dataclass
class Log:
    """What one run did: every request's record, the window, the
    generator's lateness and the traced interval, if any."""
    records: dict
    window_s: float
    loop: str = "open"
    trace_span: tuple | None = None
    # intervals in which the harness itself held the loop (starting and
    # stopping the profiler): requests due in them are late by its doing
    stalls: list = dataclasses.field(default_factory=list)
    compiles_in_window: int = 0
    prompts: dict = dataclasses.field(default_factory=dict)


def _route(events, recs: dict, now: float) -> list[int]:
    """Log ``events``; returns the rids that reached their end."""
    from repro.serving.events import (ChunkEvent, DoneEvent, FailedEvent,
                                      RejectedEvent, RetryEvent)
    ended = []
    for ev in events:
        rec = recs.get(getattr(ev, "rid", None))
        if rec is None:
            continue
        if isinstance(ev, ChunkEvent):
            rec.tokens.extend(ev.tokens)
            rec.token_s.extend([now] * len(ev.tokens))
        elif isinstance(ev, DoneEvent):
            rec.done_s = now
            ended.append(rec.rid)
            if list(ev.completion.tokens) != rec.tokens:
                rec.failed = "streamed tokens differ from the completion"
        elif isinstance(ev, RetryEvent):
            rec.failed = f"retried: {ev.reason}"
        elif isinstance(ev, (FailedEvent, RejectedEvent)):
            rec.failed = f"{type(ev).__name__}: {ev.reason}"
            rec.done_s = now
            ended.append(rec.rid)
    return ended


def run(router, reqs: list[Req], *, loop: str, window_s: float,
        ramp_s: float, clients: int = 0,
        on_tick: Callable[[float], None] | None = None,
        late_limit_s: float = LATE_LIMIT_S) -> Log:
    """Offer ``reqs`` to ``router`` and log them until the window has
    closed and (open loop) every request due in it has its first token.
    ``on_tick(t)`` is called between Router calls with the time from the
    window's opening (the traced run starts and stops the profiler
    there)."""
    from repro.serving import Request
    recs: dict[int, Record] = {}
    prompts: dict[int, np.ndarray] = {}
    t0 = time.perf_counter() + ramp_s
    end = t0 + window_s

    def submit(r: Req, due_abs: float) -> None:
        rec = Record(r.rid, due_abs - t0, len(r.prompt), r.max_new)
        recs[r.rid] = rec
        prompts[r.rid] = r.prompt
        with TraceAnnotation("bench.submit"):
            router.submit(Request(rid=r.rid, prompt=r.prompt,
                                  max_new_tokens=r.max_new))
        rec.submit_s = time.perf_counter() - t0

    nxt = 0
    pending_first = set()       # open loop: window requests without a token
    if loop == "closed":
        start = time.perf_counter()
        for _ in range(clients):
            submit(reqs[nxt % len(reqs)], start)
            nxt += 1
    while True:
        now = time.perf_counter()
        if on_tick is not None:
            on_tick(now - t0)
        if loop == "open":
            while nxt < len(reqs) and t0 + reqs[nxt].due_s <= now:
                r = reqs[nxt]
                submit(r, t0 + r.due_s)
                if 0.0 <= r.due_s < window_s:
                    pending_first.add(r.rid)
                nxt += 1
        with TraceAnnotation("bench.poll"):
            events = router.poll()
        stamp = time.perf_counter()
        ended = _route(events, recs, stamp - t0)
        if loop == "closed":
            for _ in ended:
                r = reqs[nxt % len(reqs)]
                submit(dataclasses.replace(r, rid=nxt), stamp)
                nxt += 1
        else:
            pending_first = {rid for rid in pending_first
                             if not recs[rid].token_s
                             and recs[rid].failed is None}
        if stamp >= end:
            if loop == "closed" or not pending_first:
                break
            if stamp >= end + late_limit_s:
                for rid in pending_first:
                    recs[rid].failed = "no first token within the limit"
                break
        if not events and router.in_flight == 0:
            with TraceAnnotation("bench.wait"):
                if loop == "open" and nxt < len(reqs):
                    wake = t0 + reqs[nxt].due_s
                    while time.perf_counter() < wake:
                        time.sleep(_NAP_S)
                else:
                    time.sleep(_NAP_S)
    return Log(records=recs, window_s=window_s, loop=loop, prompts=prompts)
