"""The reduction from a profiler trace to busy time, kernel time and
idle gaps."""
from __future__ import annotations

import json
import pathlib

import pytest

from bench import trace as tl
from bench.trace import Event

DATA = pathlib.Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"


def _op(name, start, dur, plane=DEV):
    return Event(plane, tl.OPS_LINE, f"%{name} = bf16[8] fusion()", start,
                 dur)


def _host(name, start, dur):
    return Event("/host:CPU", "python", name, start, dur)


def _events():
    return [
        _host("bench.traced", 100, 1000),
        _host("bench.poll", 100, 600),
        _host("bench.wait", 700, 400),
        _op("while.3", 90, 500),                   # a container: dropped
        _op("paged_decode_attention.12", 120, 200),
        _op("copy_bitcast_fusion.7", 300, 100),    # overlaps the kernel
        _op("paged_decode_attention.12", 500, 100),
        _op("fusion.1", 1050, 100),                # clipped at 1100
        _op("fusion.2", 10, 50),                   # before the interval
    ]


def test_busy_is_the_union_of_op_intervals_in_the_interval():
    r = tl.reduce(_events())
    assert (r.lo_ns, r.hi_ns) == (100, 1100)
    # [120, 400) + [500, 600) + [1050, 1100)
    assert r.busy_s == pytest.approx(430e-9)
    assert r.window_s == pytest.approx(1000e-9)


def test_kernel_time_matches_by_name_without_its_number():
    r = tl.reduce(_events())
    seconds, names = r.kernel_s("paged_decode_attention")
    assert seconds == pytest.approx(300e-9)
    assert names == ["paged_decode_attention.12"]
    assert r.kernel_s("flash_attention") == (0.0, [])


def test_breakdown_top_ops_and_labelled_idle_gaps():
    b = tl.reduce(_events()).breakdown()
    assert b["device_ops"][0] == ["paged_decode_attention", 300e-9]
    assert all(name != "while" for name, _ in b["device_ops"])
    gaps = b["idle_gaps"]
    # [600, 1050) mostly under bench.wait, [400, 500) under bench.poll,
    # [100, 120) under bench.poll
    assert gaps[0] == ["bench.wait", pytest.approx(450e-9)]
    assert gaps[1] == ["bench.poll", pytest.approx(100e-9)]
    assert len(gaps) == 3


def test_four_chips_average_their_busy_time():
    ev = _events() + [_op("fusion.9", 100, 1000, plane="/device:TPU:1")]
    r = tl.reduce(ev)
    assert sorted(r.ops) == [DEV, "/device:TPU:1"]
    assert r.busy_s == pytest.approx((430e-9 + 1000e-9) / 2)


def test_a_trace_without_its_span_or_device_is_refused():
    with pytest.raises(ValueError):
        tl.reduce([e for e in _events() if e.name != "bench.traced"])
    with pytest.raises(ValueError):
        tl.reduce([e for e in _events() if e.plane != DEV])


@pytest.mark.parametrize("path", sorted(DATA.glob("trace_*.json")),
                         ids=lambda p: p.stem)
def test_recorded_trace(path):
    """A slice of a trace recorded on a v5e in a cell's traced run."""
    rec = json.loads(path.read_text())
    ev = [Event(*row) for row in rec["events"]]
    r = tl.reduce(ev)
    want = rec["expect"]
    # expected busy time counted on a 100 ns grid, independently
    assert r.busy_s == pytest.approx(want["busy_s"], rel=1e-3)
    s, names = r.kernel_s("paged_decode_attention")
    assert s == pytest.approx(want["paged_decode_s"], rel=1e-9)
    assert names
    assert 0 < r.busy_s <= r.window_s
    assert r.breakdown()["device_ops"][0][0] == want["top_op"]


def test_load_reads_a_profiler_file(tmp_path):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.traced"):
        jax.block_until_ready(jnp.arange(8.0) * 2)
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    ev = tl.load(str(path))
    assert any(e.name == "bench.traced" for e in ev)
    lo, hi = tl.traced_interval(ev)
    assert hi > lo
