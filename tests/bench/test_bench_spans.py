"""The readers of the serving program's spans: their arithmetic on
synthetic traces, and their values on a slice of a recorded one."""
from __future__ import annotations

import json
import pathlib

import pytest

from bench import readers, spans, spec
from bench import trace as tl
from bench.trace import Event

DATA = pathlib.Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"
US = 1000                       # ns in a microsecond
METRICS = ("first_token_hold_ms", "prefill_ms", "decode_step_ms",
           "step_host_gap_ms")


def _host(name, start, dur, line="python"):
    return Event("/host:CPU", line, name, start * US, dur * US)


def _op(start, dur, plane=DEV, name="fusion.1"):
    return Event(plane, tl.OPS_LINE, f"%{name} = bf16[8] fusion()",
                 start * US, dur * US)


def _step(t, prefill=True, line="python"):
    """One Router poll at ``t`` µs: an engine step (on a worker thread,
    ``line``) that admits one prefill, then decodes; and the device ops
    the two dispatch. Times in µs from ``t``."""
    ev = [_host("router.poll", t, 500),
          _host("engine.step", t + 10, 480, line),
          _host("engine.admit", t + 20, 100, line),
          _host("engine.decode", t + 130, 20, line),
          _host("engine.readback", t + 150, 300, line),
          _host("engine.emit", t + 450, 30, line),
          _op(t + 145, 300)]                     # the chunk: 145 .. 445
    if prefill:
        ev += [_host("cache.flush", t + 25, 10, line),
               _host("engine.prefill", t + 40, 60, line),
               _op(t + 45, 50, name="flash_attention.3")]
    return ev


def _ctx(events):
    return readers.Context(log=None, trace=tl.reduce(events), work=None,
                           peaks={}, chips=1, kv_bytes=2, act_bytes=2)


def _one_step():
    return [_host("bench.traced", 0, 1000)] + _step(100, line="worker")


def test_spans_nest_by_time_across_threads():
    ctx = _ctx(_one_step())
    # the prefill ends at 200 µs, its poll (on the main thread) at 600
    assert spans.first_token_hold_ms(ctx) == pytest.approx(0.400)
    assert spans.prefill_ms(ctx) == pytest.approx(0.060)
    # readback end 550 minus decode start 230
    assert spans.decode_step_ms(ctx) == pytest.approx(0.320)
    # a 480 µs step in which the device ran 50 + 300 µs
    assert spans.step_host_gap_ms(ctx) == pytest.approx(0.130)
    assert spans.readback_alignment(ctx) == 1.0


def test_idle_is_put_down_to_the_innermost_span():
    idle = spans.idle_by_span(_ctx(_one_step()))
    assert idle == pytest.approx({
        spans.OUTSIDE: 0.100 + 0.400,   # [0, 100) and [600, 1000)
        "router.poll": 0.010 + 0.010,   # [100, 110) and [590, 600)
        "engine.step": 0.010 * 3,       # [110, 120), [220, 230), [580, 590)
        "engine.admit": 0.005 + 0.005 + 0.020,
        "cache.flush": 0.010,
        "engine.prefill": 0.005 + 0.005,
        "engine.decode": 0.015,         # [230, 245)
        "engine.readback": 0.005,       # [545, 550)
        "engine.emit": 0.030,
        })
    # and every idle microsecond of the interval is put somewhere
    assert sum(idle.values()) == pytest.approx(1.0 - 0.350)


def test_spans_are_clipped_at_the_traced_interval():
    # the interval opens inside the first poll's prefill and closes
    # inside the third poll's step
    ev = ([_host("bench.traced", 130, 1200)] + _step(0) + _step(600)
          + _step(1200, prefill=False))
    ctx = _ctx(ev)
    # the first prefill ends before the interval opens: only the second
    # counts, in both readers
    assert spans.prefill_ms(ctx) == pytest.approx(0.060)
    assert spans.first_token_hold_ms(ctx) == pytest.approx(0.400)
    # one whole step inside [130, 1330): the second
    assert spans.decode_step_ms(ctx) == pytest.approx(0.320)
    assert spans.step_host_gap_ms(ctx) == pytest.approx(0.130)


def test_a_hold_counts_when_its_poll_closes_after_the_interval():
    ev = [_host("bench.traced", 0, 250)] + _step(100)
    ctx = _ctx(ev)
    assert spans.first_token_hold_ms(ctx) == pytest.approx(0.400)
    assert spans.prefill_ms(ctx) == pytest.approx(0.060)
    assert spans.decode_step_ms(ctx) is None


def test_the_median_is_taken_over_steps():
    ev = [_host("bench.traced", 0, 2000)] + _step(0) + _step(600) + [
        _host("router.poll", 1200, 700),
        _host("engine.step", 1210, 680),
        _host("engine.decode", 1220, 20),
        _host("engine.readback", 1240, 500),
        _op(1235, 500)]
    ctx = _ctx(ev)
    # 320, 320 and 520 µs
    assert spans.decode_step_ms(ctx) == pytest.approx(0.320)
    assert spans.step_host_gap_ms(ctx) == pytest.approx(0.130)


def test_steps_of_containers_stepping_at_once_are_left_out():
    # two containers step at once, each on its own worker thread, inside
    # one poll: their decodes cannot be told apart by time
    ev = [_host("bench.traced", 0, 2000), _host("router.poll", 0, 600),
          _host("engine.step", 10, 500, "w0"),
          _host("engine.step", 20, 500, "w1"),
          _host("engine.decode", 30, 20, "w0"),
          _host("engine.decode", 40, 20, "w1"),
          _host("engine.readback", 60, 400, "w0"),
          _host("engine.readback", 70, 400, "w1"),
          _op(50, 400)] + _step(1000)
    ctx = _ctx(ev)
    assert spans.decode_step_ms(ctx) == pytest.approx(0.320)
    assert spans.readback_alignment(ctx) == 1.0


def test_misaligned_clocks_show_in_the_readback_check():
    ev = _one_step()
    # the chunk's ops read 200 µs late against the host spans
    ev = [e if e.name != "%fusion.1 = bf16[8] fusion()" else
          Event(e.plane, e.line, e.name, e.start_ns + 200 * US, e.dur_ns)
          for e in ev]
    assert spans.readback_alignment(_ctx(ev)) == 0.0


def test_busy_time_is_averaged_over_chips():
    ev = _one_step() + [_op(110, 480, plane="/device:TPU:1")]
    # chip 0 idles 130 µs of the step, chip 1 none of it
    assert spans.step_host_gap_ms(_ctx(ev)) == pytest.approx(0.065)


@pytest.mark.parametrize("name", METRICS)
def test_a_trace_without_program_spans_reads_nothing(name):
    """A build of the program without the spans: each reader returns
    None, as it does for a run without a trace."""
    read = spec.metric_reader(name)
    bare = [e for e in _one_step()
            if e.plane != "/host:CPU" or e.name.startswith("bench.")]
    assert read(_ctx(bare)) is None
    assert read(readers.Context(None, None, {}, {}, 1, 2, 2)) is None


@pytest.mark.parametrize("name", METRICS)
def test_recorded_spans(name):
    """A slice of a trace recorded on a v5e in a traced run of the cell,
    with the program's spans."""
    rec = json.loads((DATA / "spans_qwen3-0.6b.chat.json").read_text())
    ctx = _ctx([Event(*row) for row in rec["events"]])
    assert spec.metric_reader(name)(ctx) == pytest.approx(
        rec["expect"][name], rel=1e-9)


def test_recorded_device_ops_end_inside_their_readback():
    """On the chip, host spans and device ops share one clock: each
    decode chunk's device work ends while the host waits for it."""
    rec = json.loads((DATA / "spans_qwen3-0.6b.chat.json").read_text())
    ctx = _ctx([Event(*row) for row in rec["events"]])
    assert spans.readback_alignment(ctx) == 1.0
