"""The end-to-end metrics' arithmetic over a request log."""
from __future__ import annotations

import pytest

from bench import stats
from bench.drive import Log, Record


def _log(recs, window_s=10.0):
    return Log(records={r.rid: r for r in recs}, window_s=window_s)


def test_percentile_is_nearest_rank_over_all_samples():
    v = list(range(1, 101))
    assert stats.percentile(v, 95) == 95
    assert stats.percentile(v, 99) == 99
    assert stats.percentile([3.0], 95) == 3.0
    # never below an observed sample's rank: 20 samples, p95 is the 19th
    assert stats.percentile(list(range(20)), 95) == 18
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_ttft_runs_from_the_due_time_not_the_submit():
    # the generator submitted 0.5 s late; the wait counts
    r = Record(1, due_s=2.0, prompt_len=8, max_new=4, submit_s=2.5,
               token_s=[2.7, 2.8])
    assert stats.ttft_s(r) == pytest.approx(0.7)


def test_ttft_p95_covers_every_request_due_in_the_window():
    recs = [Record(i, due_s=float(i) / 10, prompt_len=8, max_new=2,
                   token_s=[float(i) / 10 + 0.01 * (i + 1)])
            for i in range(100)]
    # due before the window opens or after it closes: not counted
    recs.append(Record(1000, due_s=-1.0, prompt_len=8, max_new=2,
                       token_s=[99.0]))
    recs.append(Record(1001, due_s=10.5, prompt_len=8, max_new=2,
                       token_s=[99.0]))
    out = stats.end_to_end(_log(recs))
    assert out["ttft_p95_s"] == pytest.approx(0.95)


def test_tpot_counts_tokens_by_the_close_and_ignores_chunking():
    # four tokens arrive in one chunk at 1.0, then one each at 2.0, 3.0:
    # (3.0 - 1.0) / (6 - 1) = 0.4 s, not the gap between events
    r = Record(1, due_s=0.5, prompt_len=8, max_new=6,
               token_s=[1.0, 1.0, 1.0, 1.0, 2.0, 3.0, 11.0])
    assert stats.tpot_ms(r, until_s=10.0) == pytest.approx(400.0)
    assert stats.tpot_ms(Record(2, 0.0, 8, 4, token_s=[1.0]), 10.0) is None


def test_tokens_per_s_is_over_the_whole_window():
    recs = [Record(1, 0.0, 8, 4, token_s=[0.5, 1.0, 9.9, 10.1]),
            Record(2, -3.0, 8, 4, token_s=[-0.1, 2.0])]
    out = stats.end_to_end(_log(recs, window_s=10.0))
    assert out["tokens_per_s"] == pytest.approx(4 / 10.0)
