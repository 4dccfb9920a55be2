"""The diagnosis tools' own arithmetic, with an engine that is a stand-in:
which flushes were slow, and what the serving thread was doing in them."""

import gc
import time

import numpy as np

import bench_testlib  # noqa: F401  (puts the checkout on sys.path)
from benchmark import loadgen
from benchmark.tools import bulk_probe, stall_watch


class SleepyEngine:
    """Answers every request with its own ids; every ``every``-th flush
    sleeps first."""

    def __init__(self, every: int, sleep_s: float):
        self.every, self.sleep_s = every, sleep_s
        self.pending, self.flushes = [], 0

    def submit(self, ids):
        self.pending.append(ids)

    def flush(self):
        self.flushes += 1
        if self.flushes % self.every == 0:
            self.stalled_here()
        out, self.pending = [(ids, ids) for ids in self.pending], []
        return out

    def stalled_here(self):
        time.sleep(self.sleep_s)


def test_stall_watch_names_the_slow_flushes_and_what_ran_in_them():
    traffic = {"arrivals": "poisson", "offered_users_per_s": 2000.0,
               "request_users": {"p_one": 0.7, "lo": 2, "hi": 16},
               "shape_seed": 3}
    arrivals, requests = loadgen.open_loop_schedule(traffic, 1.5, 7, 1000)
    engine = SleepyEngine(every=10, sleep_s=0.12)
    got = stall_watch.watched_window(engine, requests, arrivals,
                                     flush_rows=64, deadline_s=0.01,
                                     slow_s=0.05)
    assert all(loadgen.answered(r) for r in got["out"]["results"])
    assert len(got["flush_ms"]) == engine.flushes
    slow = got["slow"]
    assert len(slow) == engine.flushes // 10 >= 2
    for s in slow:
        assert s["wall_ms"] >= 120 and s["rows"] > 0
        # the thread slept: the process used little CPU meanwhile, and the
        # watcher was not kept from waking for the length of the stall
        assert s["cpu_ms"] < s["wall_ms"] / 2
        assert s["watcher_gap_ms"] < s["wall_ms"]
        top = s["stacks"][0]
        assert top["seen"] >= 1
        assert any("stalled_here" in line for line in top["stack"])
        # the profile function names the C call the thread was inside
        assert [g["from"] for g in s["gaps"]] == ["c_call sleep"]
        assert s["gaps"][0]["to"] == "c_return sleep"
        assert s["gaps"][0]["ms"] >= 120
    assert np.median(got["flush_ms"]) < 50


def test_outside_ticker_reports_only_late_wakeups_and_is_stopped():
    ticker = stall_watch.OutsideTicker(tick_s=0.005, late_s=10.0)
    time.sleep(0.2)
    assert ticker.stop() == []
    assert ticker.proc.poll() is not None


def test_gc_pauses_are_recorded_by_generation():
    with stall_watch.GcPauses(min_s=0.0) as pauses:
        gc.collect()
    assert pauses.pauses and pauses.pauses[-1][1] == 2
    assert pauses not in gc.callbacks


def test_bulk_probe_counts_the_flushes_the_machine_stood_still_in():
    wall = [147.0] * 40 + [147.4, 146.6, 230.0, 260.5]
    got = bulk_probe.flush_summary(wall)
    assert got["flushes"] == 44 and got["flush_p50_ms"] == 147.0
    assert got["slow_flushes"] == 2 and got["slow_ms"] == [260.5, 230.0]
    assert abs(got["slow_excess_ms"] - (83.0 + 113.5)) < 1e-9
    assert abs(got["flush_sum_s"] * 1e3 - sum(wall)) < 1e-6


def test_bulk_probe_reads_the_kernels_counters_where_there_are_any():
    before = bulk_probe.kernel_counters()
    sum(i * i for i in range(200_000))
    after = bulk_probe.kernel_counters()
    assert after["process.user_s"] >= before["process.user_s"]
    assert all(isinstance(v, float) for v in after.values())
