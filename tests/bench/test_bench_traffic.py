"""The one traffic generator: every seed does the same work in another
order."""
from __future__ import annotations

import numpy as np
import pytest

from bench import traffic
from bench.traffic import Lengths, Mix

CHAT = ({"weight": 1.0, "prompt": Lengths(512, 0.8, 32, 1536),
         "output": Lengths(128, 0.7, 16, 512)},)


def _mix(**kw):
    base = dict(name="m", loop="open", classes=CHAT, ramp_s=5.0,
                rate_rps=2.0)
    base.update(kw)
    return Mix(**base)


def test_same_seed_same_schedule_and_large_seeds_work():
    m = _mix()
    a = traffic.schedule(m, 3_000_000_001, 30.0, 1000)
    b = traffic.schedule(m, 3_000_000_001, 30.0, 1000)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))


def test_every_seed_gets_the_same_sizes_and_gaps():
    m = _mix()
    a = traffic.schedule(m, 1, 30.0, 1000)
    b = traffic.schedule(m, 2, 30.0, 1000)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt)
                                                      for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    ga = np.sort(np.diff([-m.ramp_s] + [r.due_s for r in a]))
    gb = np.sort(np.diff([-m.ramp_s] + [r.due_s for r in b]))
    np.testing.assert_allclose(ga, gb, rtol=1e-9)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


def test_lengths_follow_the_mix():
    reqs = traffic.schedule(_mix(), 1, 300.0, 1000)
    p = np.array([len(r.prompt) for r in reqs])
    o = np.array([r.max_new for r in reqs])
    assert p.min() >= 32 and p.max() <= 1536
    assert o.min() >= 16 and o.max() <= 512
    assert np.median(p) == pytest.approx(512, rel=0.02)
    assert np.median(o) == pytest.approx(128, rel=0.02)
    span = reqs[-1].due_s - reqs[0].due_s
    assert len(reqs) / span == pytest.approx(2.0, rel=0.02)
    assert reqs[0].due_s >= -5.0


def test_bursts_raise_the_rate_inside_them():
    m = _mix(rate_rps=1.0, burst_rate_rps=5.0, burst_s=3.0, period_s=12.0)
    assert traffic.mean_rate(m) == pytest.approx((15 + 9) / 12)
    due = np.array([r.due_s for r in traffic.schedule(m, 1, 600.0, 100)])
    phase = np.mod(due + m.ramp_s, 12.0)
    inside = np.sum(phase < 3.0) / 3.0
    outside = np.sum(phase >= 3.0) / 9.0
    assert inside / outside == pytest.approx(5.0, rel=0.1)


def test_closed_loop_and_prefixes():
    m = _mix(loop="closed", clients_per_row=1.25,
             prefix={"groups": 2, "share": 1.0,
                     "length": {"median": 64, "sigma": 0.1, "min": 16,
                                "max": 128}})
    reqs = traffic.schedule(m, 4, 10.0, 1000, n=50)
    assert len(reqs) == 50
    heads = {tuple(r.prompt[:16]) for r in reqs}
    assert len(heads) == 2
    with pytest.raises(ValueError):
        traffic.schedule(m, 4, 10.0, 1000)
