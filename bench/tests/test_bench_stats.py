"""Percentile and rate arithmetic, and the serving traffic."""

import math
import statistics

import pytest

from bench import stats
from bench.drivers import serve_open_loop as serve


def test_percentile_is_nearest_rank():
    xs = [5.0, 1.0, 4.0, 2.0, 3.0]
    assert stats.percentile(xs, 0.5) == 3.0
    assert stats.percentile(xs, 0.0) == 1.0
    assert stats.percentile(xs, 1.0) == 5.0
    # rank round(0.95 * 19) = 18 of 0..19
    assert stats.percentile(list(range(20)), 0.95) == 18
    with pytest.raises(ValueError):
        stats.percentile([], 0.5)


def test_rate_takes_all_work_over_all_time():
    assert stats.rate(300, 1.5) == 200.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


PARAMS = {"rate_per_s": 30.0, "size_min": 1, "size_max": 256,
          "classes": [{"deadline_ms": 250.0, "share": 0.2},
                      {"deadline_ms": 1000.0, "share": 0.6},
                      {"deadline_ms": None, "share": 0.2}]}


def test_every_seed_gets_the_same_work_in_another_order():
    a = serve.traffic(PARAMS, 2**31 + 11, 10.0)
    b = serve.traffic(PARAMS, 12345, 10.0)
    assert len(a) == len(b) == 300
    assert sorted(r[1] for r in a) == sorted(r[1] for r in b)
    assert [r[1] for r in a] != [r[1] for r in b]
    # the same gaps too: all but one of them lie between the arrivals
    gaps = lambda t: sorted(y[0] - x[0] for x, y in zip(t, t[1:]))
    common = set(round(g, 9) for g in gaps(a)) & set(round(g, 9) for g in gaps(b))
    assert len(common) >= len(a) - 3


def test_traffic_shape():
    reqs = serve.traffic(PARAMS, 7, 10.0)
    due = [r[0] for r in reqs]
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < 12.0
    sizes = [r[1] for r in reqs]
    assert min(sizes) == 1 and 250 <= max(sizes) <= 256
    # log-uniform: the median size is near sqrt(256) = 16
    assert 12 <= statistics.median(sizes) <= 20
    deadlines = [r[2] for r in reqs]
    assert {d for d in deadlines} <= {250.0, 1000.0, math.inf}
    assert 0.45 < deadlines.count(1000.0) / len(reqs) < 0.75
    assert all(0 <= r[3] < 2**31 for r in reqs)
