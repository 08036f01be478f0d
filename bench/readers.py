"""Arithmetic shared by the per-layer readers in ``bench/metrics/``.

Each reader gets a ``Context``: the run's request log, the reduced trace
(None in a run without one), the configuration's work model (its model
file's ``work(cfg)``), the device's peaks and the number of chips. Work
is counted from the request log over the traced interval, on the host
clock the log is stamped with, by the family's own formulas: nothing
here knows a model family.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

from bench import stats


@dataclasses.dataclass
class Context:
    log: object              # bench.drive.Log
    trace: object | None     # bench.trace.Reduced
    work: object             # the model file's work(cfg)
    peaks: dict
    chips: int
    kv_bytes: int            # bytes per cached K or V element
    act_bytes: int           # bytes per activation element

    def traced_tokens(self):
        """(record, token index) of every token that reached its client
        inside the traced interval."""
        lo, hi = self.log.trace_span
        for rec in self.log.records.values():
            for i, t in enumerate(rec.token_s):
                if lo <= t <= hi:
                    yield rec, i


def model_flops(ctx: Context) -> int:
    """Forward operations of the tokens served in the traced interval:
    a request's first token carries its whole prefill, each later token
    one decode step at its position."""
    total = 0
    for rec, i in ctx.traced_tokens():
        if i == 0:
            total += ctx.work.prefill_flops(rec.prompt_len)
        else:
            total += ctx.work.token_flops(rec.prompt_len + i)
    return total


def mfu(ctx: Context) -> float | None:
    """Model operations over the device-busy seconds of every chip at
    its bf16 peak, in percent."""
    if ctx.trace is None or ctx.trace.busy_s <= 0:
        return None
    return 100.0 * model_flops(ctx) / (
        ctx.trace.busy_s * ctx.chips * ctx.peaks["bf16_flops"])


def roofline(ctx: Context, flops: int, data: int, kernel: str
             ) -> float | None:
    """Least time the chip could take for the work (operations at peak
    or bytes at bandwidth, whichever is longer) over the kernel's summed
    device time, in percent. None where the kernel did not run."""
    if ctx.trace is None:
        return None
    seconds, _ = ctx.trace.kernel_s(kernel)
    if seconds <= 0 or flops <= 0:
        return None
    least = max(flops / ctx.peaks["bf16_flops"],
                data / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds


def decode_roofline(ctx: Context, kernel: str,
                    work_fn: Callable[[int, int], tuple[int, int]]
                    ) -> float | None:
    """``kernel``'s share of its roofline over the traced interval's
    decoded tokens: ``work_fn(keys, kv_bytes)`` gives the operations and
    bytes of one token decoded against ``keys`` keys."""
    flops = data = 0
    for rec, i in ctx.traced_tokens():
        if i > 0:
            f, b = work_fn(rec.prompt_len + i, ctx.kv_bytes)
            flops, data = flops + f, data + b
    return roofline(ctx, flops, data, kernel)


def prefill_roofline(ctx: Context, kernel: str,
                     work_fn: Callable[[int, int], tuple[int, int]]
                     ) -> float | None:
    """``kernel``'s share of its roofline over the traced interval's
    prefills: ``work_fn(prompt_len, act_bytes)`` gives the operations and
    bytes of one prompt's prefill."""
    flops = data = 0
    for rec, i in ctx.traced_tokens():
        if i == 0:
            f, b = work_fn(rec.prompt_len, ctx.act_bytes)
            flops, data = flops + f, data + b
    return roofline(ctx, flops, data, kernel)


def idle_share(ctx: Context) -> float | None:
    """Percent of the traced interval in which no op ran, averaged over
    the chips."""
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)


def gen_lag_p99_ms(ctx: Context) -> float | None:
    """How late the generator submitted the window's requests against
    their schedule: the 99th percentile, open loop only. Requests due
    while the harness started or stopped the profiler are left out: that
    stall is the tracer's, not the system's."""
    stalls = ctx.log.stalls
    lags = [(r.submit_s - r.due_s) * 1e3
            for r in stats.window_requests(ctx.log)
            if r.submit_s is not None
            and not any(a <= r.due_s <= b for a, b in stalls)]
    if not lags or ctx.log.loop != "open":
        return None
    return stats.percentile(lags, 99)
