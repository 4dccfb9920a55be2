"""Say what the serving thread was doing while a flush of an open-loop cell
stalled (PERF.md, Open questions: the tail of the online cell).

    python3 benchmark/tools/stall_watch.py --workload <cell> --windows 6 --seconds 51 --slow-ms 60

One process builds the engine once and drives ``--windows`` open-loop
windows at the cell's own load through the cell's own ``run_open_loop``.
Three witnesses, for every flush that lasts longer than ``--slow-ms``:

- a profile function on the serving thread (``sys.setprofile``, inside
  flushes only) keeps every pair of consecutive call and return events
  that lie further apart than ``--slow-ms``: the Python or C function the
  thread was inside for that long (``gaps``). (``faulthandler``'s watchdog
  would say the same with less overhead, but reading the thread states of
  a process whose runtime threads come and go ended the process, silently,
  at the first slow flush on the chip.);
- a Python thread that wakes every few milliseconds keeps the serving
  thread's stack from then on and how long it was itself kept from waking
  (``watcher_gap_ms``: as long as the stall when the interpreter lock was
  held, or the whole process stood still);
- a child process that never touches JAX ticks on the same clock and
  reports its own late wake-ups (``outside_gaps``): a gap there at the
  same instant means the machine stood still, not this process
  (``--idle-seconds``: the same child alone first, on an idle machine).

Beside them the flush's wall, the CPU time the process used inside it and
the cyclic collector's pauses. A diagnosis by hand, never part of a run.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import subprocess
import sys
import threading
import time
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.spans import Spans  # noqa: E402


def _short(path: str) -> str:
    """A file of the checkout by its path in it, any other by its last two
    parts."""
    if path.startswith(ROOT + os.sep):
        return os.path.relpath(path, ROOT)
    return os.sep.join(path.split(os.sep)[-2:])


def _event_name(event: str, code, arg) -> str:
    """A profile event by the function it enters or leaves."""
    if event.startswith("c_"):
        return f"{event} {getattr(arg, '__qualname__', repr(arg))}"
    return (f"{event} {_short(code.co_filename)}:{code.co_firstlineno} "
            f"{code.co_name}")


class GcPauses:
    """Collections of the cyclic collector that took longer than
    ``min_s``: ``(start, generation, seconds)``."""

    def __init__(self, min_s: float = 0.005):
        self.min_s = min_s
        self.pauses: list[tuple[float, int, float]] = []
        self._t = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            took = time.perf_counter() - self._t
            if took >= self.min_s:
                self.pauses.append((self._t, info["generation"], took))

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


class WatchedSpans(Spans):
    """Spans that also tell a watcher thread which span is open."""

    def __init__(self, watch: str, slow_s: float):
        super().__init__()
        self.watch, self.slow_s = watch, slow_s
        self.open_since = None   # perf_counter at the watched span's start
        self.cpu_at_open = None
        self.cpu_used: list[float] = []
        self.gaps: list[tuple] = []  # (start, seconds, event before, after)
        self._last = None

    def _profile(self, frame, event, arg):
        # as little as can be done per event: names are made for gaps only
        now = time.perf_counter()
        last = self._last
        if last is not None and now - last[0] >= self.slow_s:
            self.gaps.append((last[0], now - last[0], _event_name(*last[1:]),
                              _event_name(event, frame.f_code, arg)))
        self._last = (now, event, frame.f_code, arg)

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        if name != self.watch:
            with super().span(name, **attrs):
                yield
            return
        self.cpu_at_open = time.process_time()
        self.open_since = time.perf_counter()
        self._last = None
        sys.setprofile(self._profile)
        try:
            with super().span(name, **attrs):
                yield
        finally:
            sys.setprofile(None)
            self.open_since = None
            self.cpu_used.append(time.process_time() - self.cpu_at_open)


class Watcher(threading.Thread):
    """Samples one thread's stack while the watched span has been open for
    longer than ``slow_s``; records how late each of its own wake-ups was."""

    def __init__(self, spans: WatchedSpans, thread_id: int, slow_s: float,
                 tick_s: float = 0.005, frames: int = 8):
        super().__init__(daemon=True)
        self.spans, self.thread_id = spans, thread_id
        self.slow_s, self.tick_s, self.frames = slow_s, tick_s, frames
        self.samples: dict[float, dict] = {}  # span start -> what was seen
        self.stop = threading.Event()

    def run(self):
        last = time.perf_counter()
        while not self.stop.wait(self.tick_s):
            now = time.perf_counter()
            gap, last = now - last, now
            since = self.spans.open_since
            if since is None or now - since < self.slow_s:
                continue
            seen = self.samples.setdefault(
                since, {"stacks": {}, "watcher_gap_s": 0.0})
            seen["watcher_gap_s"] = max(seen["watcher_gap_s"], gap)
            frame = sys._current_frames().get(self.thread_id)
            if frame is None:
                continue
            stack = tuple(
                f"{_short(f.filename)}:{f.lineno} {f.name}"
                for f in traceback.extract_stack(frame)[-self.frames:])
            seen["stacks"][stack] = seen["stacks"].get(stack, 0) + 1


TICKER = """
import os, sys, time
tick, late, parent = float(sys.argv[1]), float(sys.argv[2]), os.getppid()
last = time.perf_counter()
while os.getppid() == parent:
    time.sleep(tick)
    now = time.perf_counter()
    if now - last - tick >= late:
        print(repr(last), repr(now - last), flush=True)
    last = now
"""


class OutsideTicker:
    """A child process that never touches JAX: it sleeps ``tick_s`` at a
    time and prints every wake-up that came ``late_s`` late or later, on
    the clock this process reads too (``perf_counter`` is the machine's
    monotonic clock)."""

    def __init__(self, tick_s: float = 0.005, late_s: float = 0.03):
        self.proc = subprocess.Popen(
            [sys.executable, "-c", TICKER, str(tick_s), str(late_s)],
            stdout=subprocess.PIPE, text=True)

    def stop(self) -> list[tuple[float, float]]:
        """``(start of the gap, its length)`` for every late wake-up."""
        self.proc.terminate()
        text, _ = self.proc.communicate()
        return [tuple(map(float, line.split()))
                for line in text.splitlines() if len(line.split()) == 2]


def _near(table: dict, start: float, within_s: float = 1e-3):
    """The entry keyed by the instant nearest ``start`` (the keys were
    read from the clock a moment before the span's own start)."""
    key = min(table, key=lambda t: abs(t - start), default=None)
    return (table[key] if key is not None and abs(key - start) < within_s
            else None)


def slow_flushes(spans: WatchedSpans, watcher: Watcher, slow_s: float,
                 t0: float) -> list[dict]:
    """One record per watched span that lasted longer than ``slow_s``."""
    flushes = [(a, b, attrs) for name, a, b, attrs in spans.records
               if name == spans.watch]
    out = []
    for (a, b, attrs), cpu in zip(flushes, spans.cpu_used):
        if b - a < slow_s:
            continue
        seen = _near(watcher.samples, a)
        out.append({
            "at_s": round(a - t0, 3), "wall_ms": round((b - a) * 1e3, 2),
            "cpu_ms": round(cpu * 1e3, 2), "rows": attrs.get("rows"),
            "gaps": [{"ms": round(took * 1e3, 2), "from": before,
                      "to": after}
                     for t, took, before, after in spans.gaps
                     if a <= t <= b],
            "watcher_gap_ms": (round(seen["watcher_gap_s"] * 1e3, 2)
                               if seen else None),
            "stacks": ([{"seen": n, "stack": list(s)} for s, n in sorted(
                seen["stacks"].items(), key=lambda kv: -kv[1])[:3]]
                       if seen else [])})
    return out


def watched_window(engine, requests, arrivals, flush_rows: int,
                   deadline_s: float, slow_s: float) -> dict:
    """One open-loop window with the watcher on, under the same frozen
    collector as the cell's own window."""
    from benchmark import loadgen

    spans = WatchedSpans("serving/flush", slow_s)
    watcher = Watcher(spans, threading.get_ident(), slow_s)
    gc.collect()
    gc.freeze()
    t0 = time.perf_counter()
    watcher.start()
    try:
        with GcPauses() as collector:
            out = loadgen.run_open_loop(engine, requests, arrivals,
                                        flush_rows, deadline_s, spans)
    finally:
        watcher.stop.set()
        watcher.join()
        gc.unfreeze()
    return {"out": out, "t0": t0,
            "slow": slow_flushes(spans, watcher, slow_s, t0),
            "flush_ms": [d * 1e3 for d in spans.durations(spans.watch)],
            "gc_pauses": [[round(t - t0, 3), gen, round(took * 1e3, 2)]
                          for t, gen, took in collector.pauses]}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--windows", type=int, default=6)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--slow-ms", type=float, default=100.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--idle-seconds", type=float, default=0.0,
                    help="first let the outside ticker run alone for this "
                         "long, before this process touches JAX")
    args = ap.parse_args()
    if args.idle_seconds > 0:
        ticker, t0 = OutsideTicker(), time.perf_counter()
        time.sleep(args.idle_seconds)
        print("idle_gaps", json.dumps({
            "seconds": args.idle_seconds,
            "gaps": [[round(t - t0, 3), round(g * 1e3, 2)]
                     for t, g in ticker.stop()]}), flush=True)

    import numpy as np

    from benchmark import datagen, harness, loadgen
    from benchmark.runners import serve

    cell = harness.resolve_cell(args.workload)
    harness.start_on_chip(cell.chips)
    cfg, traffic = cell.config, cell.traffic
    U, V = datagen.serving_factors(args.seed, num_users=cfg["num_users"],
                                   num_items=cfg["num_items"],
                                   rank=cfg["num_factors"])
    engine = serve.build_engine(cfg, U, V)
    rng = np.random.default_rng(0)
    for rows in serve.buckets_reached(cfg, traffic):
        engine.submit(rng.integers(0, cfg["num_users"], rows))
        engine.flush()
    ticker = OutsideTicker()
    try:
        spans_of_windows = drive_windows(args, engine, cfg, traffic)
    finally:
        gaps = ticker.stop()
    for seed, a, b in spans_of_windows:
        print("outside_gaps", json.dumps({
            "seed": seed, "gaps": [[round(t - a, 3), round(g * 1e3, 2)]
                                   for t, g in gaps if a <= t < b]}),
              flush=True)
    return 0


def drive_windows(args, engine, cfg, traffic) -> list[tuple]:
    """``(seed, start, end)`` of every window, on ``perf_counter``."""
    import numpy as np

    from benchmark import loadgen

    spans_of_windows = []
    for w in range(args.windows):
        arrivals, requests = loadgen.open_loop_schedule(
            traffic, args.seconds, args.seed + w, cfg["num_users"])
        got = watched_window(engine, requests, arrivals,
                             int(traffic["flush_rows"]),
                             float(traffic["deadline_ms"]) / 1e3,
                             args.slow_ms / 1e3)
        lat = np.where(np.isnan(got["out"]["latency"]), np.inf,
                       got["out"]["latency"]) * 1e3
        print("window", json.dumps({
            "seed": args.seed + w, "flushes": len(got["flush_ms"]),
            "flush_ms_p50": float(np.median(got["flush_ms"])),
            "flush_ms_max": float(np.max(got["flush_ms"])),
            "p95_ms": float(np.percentile(lat, 95)),
            "slow_flushes": len(got["slow"]),
            "gc_pauses": got["gc_pauses"]}), flush=True)
        for s in got["slow"]:
            print("slow", json.dumps(s), flush=True)
        spans_of_windows.append((args.seed + w, got["t0"],
                                 got["t0"] + got["out"]["wall"]))
    return spans_of_windows


if __name__ == "__main__":
    sys.exit(main())
