"""Percentile, rate and lateness arithmetic on a synthetic schedule."""

import numpy as np
import pytest

import gen
import stats


def record(due, sent, done, status):
    return {"due": np.asarray(due, float), "sent": np.asarray(sent, float),
            "done": np.asarray(done, float),
            "status": np.asarray(status, np.int8)}


def test_latency_runs_from_due_time_and_failures_are_infinite():
    # four requests due every 0.25 s; the third sent 0.1 s late; the
    # fourth failed
    c = record([0, .25, .5, .75], [0, .25, .6, .75], [.01, .27, .63, .8],
               [0, 0, 0, 1])
    lat = stats.latencies_ms(c)
    assert lat[:3] == pytest.approx([10, 20, 130])
    assert np.isinf(lat[3])
    assert stats.percentile_ms(c, 50) == pytest.approx(75.0)
    assert stats.lateness_ms(c)[:3] == pytest.approx([0, 0, 100])
    assert stats.failed(c) == 1


def test_p95_over_all_requests():
    lat = np.arange(1, 101) / 1e3               # 1..100 ms
    c = record(np.zeros(100), np.zeros(100), lat, np.zeros(100))
    assert stats.percentile_ms(c, 95) == pytest.approx(95.05)


def test_qps_counts_replies_inside_the_window_only():
    c = record([0, 1, 2, 9.5], [0, 1, 2, 9.5], [0.5, 1.5, 2.5, 10.5],
               [0, 0, 1, 0])
    # the error and the reply after the close do not count
    assert stats.qps(c, 10.0) == pytest.approx(0.2)


def test_arrivals_same_gaps_for_every_seed():
    a = gen.arrivals(400, 10.0, 2**31 + 7)
    b = gen.arrivals(400, 10.0, 12)
    assert 0 < a[0] and a[-1] < 10.0
    assert not np.allclose(a, b)
    gaps = np.diff(np.r_[0.0, a])
    assert np.sort(gaps) == pytest.approx(np.sort(np.diff(np.r_[0.0, b])),
                                          rel=1e-9, abs=1e-12)
    # an exponential renewal: mean gap 1/rate, coefficient of variation 1
    assert gaps.mean() == pytest.approx(10.0 / 400, rel=0.01)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.1)


def test_sample_is_seeded_and_skips_failures():
    c = record(np.zeros(50), np.zeros(50), np.ones(50),
               np.r_[np.zeros(40), np.ones(10)])
    s1, s2 = stats.sample(c, 16, 5), stats.sample(c, 16, 5)
    assert np.array_equal(s1, s2) and len(s1) == 16
    assert np.all(s1 < 40)
