"""The traffic generator: the same schedule for the same seed, another for
another, and the same amount of work for every seed."""

import json
import os

import numpy as np
import pytest

import bench_testlib
from benchmark import datagen, loadgen

ONLINE = json.load(open(os.path.join(
    bench_testlib.ROOT, "benchmark", "traffic", "serve-online.json")))


def schedule(seed, seconds=5.0):
    return loadgen.open_loop_schedule(ONLINE, seconds, seed, 2_500_000)


def test_same_seed_same_schedule():
    a1, r1 = schedule(7)
    a2, r2 = schedule(7)
    assert np.array_equal(a1, a2)
    assert all(np.array_equal(x, y) for x, y in zip(r1, r2))


def test_another_seed_another_schedule_same_work():
    a1, r1 = schedule(7)
    a2, r2 = schedule(8)
    assert len(a1) == len(a2) and not np.array_equal(a1, a2)
    s1, s2 = [len(x) for x in r1], [len(x) for x in r2]
    assert s1 != s2 and sorted(s1) == sorted(s2)
    assert not np.array_equal(np.concatenate(r1), np.concatenate(r2))
    # the same gaps in another order: equal offered load over equal time
    assert a1[-1] == pytest.approx(a2[-1])
    assert np.allclose(np.sort(np.diff(a1, prepend=0.0)),
                       np.sort(np.diff(a2, prepend=0.0)))


def test_a_seed_over_2_to_the_31_is_taken():
    a, r = schedule(2**31 + 12345)
    assert len(a) == len(r) > 0
    assert datagen.seed_key(2**31 + 12345) is not None
    k1 = np.asarray(datagen.seed_key(5))
    k2 = np.asarray(datagen.seed_key(2**31 + 5))
    assert not np.array_equal(k1, k2)


def test_offered_rate_and_request_sizes():
    a, r = schedule(3, seconds=20.0)
    users = sum(len(x) for x in r)
    assert users / 20.0 == pytest.approx(ONLINE["offered_users_per_s"],
                                         rel=0.03)
    sizes = np.array([len(x) for x in r])
    assert sizes.min() == 1 and sizes.max() <= 16
    assert (sizes == 1).mean() == pytest.approx(0.7, abs=0.03)
    assert np.all(np.diff(a) >= 0) and a[-1] < 20.0
    assert all(x.min() >= 0 and x.max() < 2_500_000 for x in r)


def test_bursty_arrivals_keep_the_mean_rate_order():
    rng = np.random.default_rng(0)
    a = loadgen.make_arrivals("bursty", 4000, 100.0, rng)
    assert np.all(np.diff(a) > 0)
    with pytest.raises(ValueError):
        loadgen.make_arrivals("square", 10, 1.0, rng)


def test_closed_loop_requests_are_fixed_size_and_seeded():
    t = {"request_users": {"fixed": 64}}
    g1 = loadgen.closed_loop_requests(t, 9, 1000)
    g2 = loadgen.closed_loop_requests(t, 9, 1000)
    g3 = loadgen.closed_loop_requests(t, 10, 1000)
    x1, x2, x3 = next(g1), next(g2), next(g3)
    assert len(x1) == 64 and np.array_equal(x1, x2)
    assert not np.array_equal(x1, x3)


class _Engine:
    """Answers every submitted request at flush, with a fixed delay."""

    def __init__(self, delay):
        self.delay, self.pending = delay, []

    def submit(self, ids):
        self.pending.append(ids)

    def flush(self):
        import time

        time.sleep(self.delay)
        out, self.pending = [(x, x) for x in self.pending], []
        return out


def test_open_loop_latency_runs_from_the_scheduled_arrival():
    from benchmark.spans import Spans

    arrivals = np.array([0.0, 0.001, 0.002, 0.2])
    requests = [np.arange(3)] * 4
    out = loadgen.run_open_loop(_Engine(0.02), requests, arrivals,
                                flush_rows=1000, deadline_s=0.05,
                                spans=Spans())
    lat = out["latency"]
    # the first three wait for the deadline of the oldest, then the flush
    assert lat[0] >= 0.05 + 0.02 and lat[0] < 0.12
    assert lat[0] > lat[1] > lat[2]
    assert out["queue_wait"][0] >= 0.05
    assert len(out["flushes"]) == 2 and out["flushes"][0][0] == 9
    assert out["wall"] >= 0.2
    assert all(r is not None for r in out["results"])
