"""The end-to-end metrics' arithmetic over a run's request log."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least ``q``
    percent of all samples at or below it. Always an observed sample."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no samples")
    k = max(1, math.ceil(q / 100.0 * len(v)))
    return v[k - 1]


def window_requests(log) -> list:
    """Requests due inside the window (open loop: by their schedule)."""
    return [r for r in log.records.values()
            if 0.0 <= r.due_s < log.window_s]


def ttft_s(rec) -> float:
    """Time to first token, from when the request was due."""
    return rec.first_s - rec.due_s


def tpot_ms(rec, until_s: float) -> float | None:
    """Per request: (last token - first token) / (tokens - 1), over the
    tokens that reached the client by ``until_s``; None under two."""
    ts = [t for t in rec.token_s if t <= until_s]
    if len(ts) < 2:
        return None
    return (ts[-1] - ts[0]) / (len(ts) - 1) * 1e3


def tokens_in_window(log) -> int:
    """Output tokens that reached their clients inside the window."""
    return sum(1 for r in log.records.values() for t in r.token_s
               if 0.0 <= t < log.window_s)


def end_to_end(log) -> dict:
    """ttft_p95_s over every request due in the window, tpot_p95_ms over
    every request of the window with two tokens by its close, and
    tokens_per_s over the whole window."""
    reqs = window_requests(log)
    out = {"tokens_per_s": tokens_in_window(log) / log.window_s}
    firsts = [ttft_s(r) for r in reqs if r.first_s is not None]
    if firsts:
        out["ttft_p95_s"] = percentile(firsts, 95)
    tpots = [x for x in (tpot_ms(r, log.window_s) for r in reqs)
             if x is not None]
    if tpots:
        out["tpot_p95_ms"] = percentile(tpots, 95)
    return out
