"""The serving program's own spans, read from a reduced profiler trace.

The program marks its phases with ``jax.profiler.TraceAnnotation`` spans
(``router.poll`` … ``engine.emit`` below), which land on the trace's host
plane on the device ops' clock. ``bench.trace.load`` keeps a span's name
and times but not its thread (every Python thread's line is named
``python``), so nesting is read by time: a span lies in another when the
other's interval covers it, whichever threads ran them. Each metric
returns None where its spans are missing, as in a trace of a program
that has none.
"""
from __future__ import annotations

import statistics

from bench.trace import busy_ns, device_ops, union

ROUTER_POLL = "router.poll"     # one Router poll: every engine step, routing
STEP = "engine.step"            # one engine step that found work
ADMIT = "engine.admit"          # the step's admission round
FLUSH = "cache.flush"           # a scrub of freed cache rows
PREFILL = "engine.prefill"      # one prefill batch, to its first tokens
DECODE = "engine.decode"        # a decode chunk's state and dispatch
READBACK = "engine.readback"    # the chunk's one device-to-host copy
EMIT = "engine.emit"            # the per-row loop over the chunk's tokens
PROGRAM = (ROUTER_POLL, STEP, ADMIT, FLUSH, PREFILL, DECODE, READBACK, EMIT)
# idle time under no program span: the harness's own, between polls
OUTSIDE = "outside router.poll"
# a decode chunk's ops run back to back (gaps under a microsecond on a
# v5e); an idle gap this long ends it
CHUNK_GAP_NS = 1_000_000


def spans(trace, name: str) -> list:
    """The host spans called ``name``, by start."""
    return sorted((e for e in trace.host if e.name == name),
                  key=lambda e: e.start_ns)


def within(trace, e) -> bool:
    """Does ``e`` lie wholly inside the traced interval?"""
    return trace.lo_ns <= e.start_ns and e.end_ns <= trace.hi_ns


def covers(outer, inner) -> bool:
    return outer.start_ns <= inner.start_ns and inner.end_ns <= outer.end_ns


def container(candidates: list, e):
    """The shortest of ``candidates`` whose interval covers ``e``'s."""
    found = [c for c in candidates if covers(c, e)]
    return min(found, key=lambda c: c.dur_ns) if found else None


def _median_ms(values_ns: list) -> float | None:
    return statistics.median(values_ns) / 1e6 if values_ns else None


def _busy_ns(trace, lo: int, hi: int) -> float:
    """Device-busy ns inside ``[lo, hi]``: the union of each chip's leaf
    ops there, averaged over the chips."""
    return sum(busy_ns(device_ops([e for e in ops if e.start_ns < hi
                                   and e.end_ns > lo], plane, lo, hi))
               for plane, ops in trace.ops.items()) / len(trace.ops)


def _idle(trace, ops) -> list[tuple[int, int]]:
    """The stretches of the traced interval in which none of ``ops``
    ran."""
    out, cur = [], trace.lo_ns
    for s, t in union([(e.start_ns, e.end_ns) for e in ops]):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, t)
    if trace.hi_ns > cur:
        out.append((cur, trace.hi_ns))
    return out


def _steps(ctx):
    """(decode, readback) of each engine step inside the traced interval
    that holds exactly one of each; steps with none (admission only) or
    with several (containers stepping at once on other threads) are left
    out."""
    decodes, reads = spans(ctx.trace, DECODE), spans(ctx.trace, READBACK)
    for step in spans(ctx.trace, STEP):
        if not within(ctx.trace, step):
            continue
        d = [e for e in decodes if covers(step, e)]
        r = [e for e in reads if covers(step, e)]
        if len(d) == 1 and len(r) == 1:
            yield d[0], r[0]


def first_token_hold_ms(ctx) -> float | None:
    """Median time a picked first token waits to reach the caller: for
    each prefill ending in the traced interval, the end of the Router
    poll that contains it minus the prefill's end."""
    if ctx.trace is None:
        return None
    tr = ctx.trace
    polls = spans(tr, ROUTER_POLL)
    holds = []
    for p in spans(tr, PREFILL):
        poll = container(polls, p)
        if poll is not None and tr.lo_ns <= p.end_ns <= tr.hi_ns:
            holds.append(poll.end_ns - p.end_ns)
    return _median_ms(holds)


def prefill_ms(ctx) -> float | None:
    """Median length of the prefills inside the traced interval: batch,
    dispatch, row insert and first tokens on the host."""
    if ctx.trace is None:
        return None
    return _median_ms([e.dur_ns for e in spans(ctx.trace, PREFILL)
                       if within(ctx.trace, e)])


def decode_step_ms(ctx) -> float | None:
    """Median decode step: from the chunk's state being built to its
    tokens on the host (readback end minus decode start)."""
    if ctx.trace is None:
        return None
    return _median_ms([r.end_ns - d.start_ns for d, r in _steps(ctx)])


def step_host_gap_ms(ctx) -> float | None:
    """Median device idle inside an engine step: each step's length in
    the traced interval minus the device-busy time inside it."""
    if ctx.trace is None:
        return None
    return _median_ms([e.dur_ns - _busy_ns(ctx.trace, e.start_ns, e.end_ns)
                       for e in spans(ctx.trace, STEP)
                       if within(ctx.trace, e)])


def readback_alignment(ctx) -> float | None:
    """Share of decode steps whose chunk's last device op ends inside
    the step's readback span, on every chip: a check that host spans and
    device ops share a clock. A chunk's ops run back to back; its last
    op is the one before the device's first idle gap of ``CHUNK_GAP_NS``
    or more (or the interval's end) after the decode span opens."""
    if ctx.trace is None:
        return None
    hi = ctx.trace.hi_ns
    ends = [[s for s, t in _idle(ctx.trace, ops)
             if t - s >= CHUNK_GAP_NS or t == hi]
            for ops in ctx.trace.ops.values()]
    hits = n = 0
    for d, r in _steps(ctx):
        last = [next((t for t in chip if t > d.start_ns), None)
                for chip in ends]
        if None in last:
            continue
        n += 1
        hits += all(r.start_ns <= t <= r.end_ns for t in last)
    return hits / n if n else None


def idle_by_span(ctx) -> dict | None:
    """The traced interval's device idle time in ms (mean over chips),
    each stretch put down to the innermost program span over it: its
    name, or ``OUTSIDE`` where no program span is open."""
    if ctx.trace is None:
        return None
    tr = ctx.trace
    prog = [e for e in tr.host if e.name in PROGRAM]
    if not prog:
        return None
    # the traced interval cut at every span boundary, each piece
    # labelled by the shortest span covering it
    cuts = sorted({tr.lo_ns, tr.hi_ns} | {
        x for e in prog for x in (e.start_ns, e.end_ns)
        if tr.lo_ns < x < tr.hi_ns})
    pieces = []
    for a, b in zip(cuts, cuts[1:]):
        over = [e for e in prog if e.start_ns <= a and b <= e.end_ns]
        inner = min(over, key=lambda e: e.dur_ns) if over else None
        pieces.append((a, b, inner.name if inner else OUTSIDE))
    out: dict[str, float] = {}
    for ops in tr.ops.values():
        j = 0
        for s, t in _idle(tr, ops):
            while pieces[j][1] <= s:
                j += 1
            k = j
            while k < len(pieces) and pieces[k][0] < t:
                a, b, label = pieces[k]
                ov = min(b, t) - max(a, s)
                out[label] = out.get(label, 0.0) + ov / 1e6 / len(tr.ops)
                k += 1
    return out
