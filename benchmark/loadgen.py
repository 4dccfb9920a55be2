"""The one general traffic generator and the two serving drivers.

``make_arrivals`` and ``run_open_loop`` are copies of
``scripts/serving_bench.py::make_arrivals`` / ``run_traffic_level`` (the
originals are listed in PERF.md for a later PR to delete): latency runs from
the SCHEDULED arrival, a flush goes out when ``flush_rows`` rows are
pending or the oldest pending request has waited ``deadline_ms``. What a
traffic file may set is documented in ``benchmark/README.md``.

Every seed gets the same multiset of request sizes and of arrival gaps, in
another order: the sizes and gaps are drawn once from the traffic file's own
``shape_seed`` and only permuted by ``--seed`` (the user ids are drawn from
``--seed``). So two seeds differ in order, never in the amount of work.
"""

from __future__ import annotations

import time

import numpy as np


def make_arrivals(pattern: str, n: int, rate: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Arrival offsets in seconds, sorted, for ``n`` requests at a mean of
    ``rate`` requests a second: ``poisson`` (exponential gaps) or
    ``bursty`` (alternating 4x bursts and 0.25x lulls)."""
    if pattern == "poisson":
        gaps = rng.exponential(1.0 / rate, n)
    elif pattern == "bursty":
        burst = int(max(8, n // 8))
        on = (np.arange(n) // burst) % 2 == 0
        gaps = rng.exponential(1.0, n) / (rate * np.where(on, 4.0, 0.25))
    else:
        raise ValueError(f"unknown arrival pattern {pattern!r}")
    return np.cumsum(gaps)


def request_sizes(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """Users per request. ``{"fixed": m}`` or ``{"p_one": p, "lo": a,
    "hi": b}``: one user with probability ``p``, else log-uniform in
    ``[a, b]``."""
    if "fixed" in spec:
        return np.full(n, int(spec["fixed"]), np.int64)
    lo, hi = float(spec["lo"]), float(spec["hi"])
    many = np.exp(rng.uniform(np.log(lo), np.log(hi + 1.0), n))
    many = np.clip(np.floor(many), lo, hi).astype(np.int64)
    return np.where(rng.random(n) < float(spec["p_one"]), 1, many)


def open_loop_schedule(traffic: dict, seconds: float, seed: int,
                       num_users: int):
    """``(arrivals, requests)`` for an open-loop window of ``seconds``.
    The number of requests is fixed by the rate and the window; the last
    arrival is scaled onto the window's end so every seed offers exactly
    the same load over exactly the same time."""
    mean_users = mean_request_users(traffic["request_users"])
    req_rate = float(traffic["offered_users_per_s"]) / mean_users
    n = max(int(round(req_rate * seconds)), 1)
    shape = np.random.default_rng(int(traffic.get("shape_seed", 0)))
    gaps = np.diff(make_arrivals(traffic.get("arrivals", "poisson"), n,
                                 req_rate, shape), prepend=0.0)
    sizes = request_sizes(traffic["request_users"], n, shape)
    rng = np.random.default_rng(int(seed))
    if traffic.get("arrivals", "poisson") == "poisson":
        gaps = rng.permutation(gaps)  # bursty keeps its phases in place
    sizes = rng.permutation(sizes)
    arrivals = np.cumsum(gaps)
    arrivals *= seconds * (n / (n + 1.0)) / arrivals[-1]
    ids = rng.integers(0, num_users, int(sizes.sum()), dtype=np.int64)
    requests = np.split(ids, np.cumsum(sizes)[:-1])
    return arrivals, requests


def mean_request_users(spec: dict) -> float:
    """Mean users per request under ``request_sizes``' distribution, by a
    fixed large draw (no closed form for the floored log-uniform)."""
    if "fixed" in spec:
        return float(spec["fixed"])
    return float(request_sizes(
        spec, 200_000, np.random.default_rng(12345)).mean())


def closed_loop_requests(traffic: dict, seed: int, num_users: int):
    """An endless stream of fixed-size requests of uniform user ids."""
    size = int(traffic["request_users"]["fixed"])
    rng = np.random.default_rng(int(seed))
    while True:
        yield rng.integers(0, num_users, size, dtype=np.int64)


def answered(result) -> bool:
    """A request's result: answered, not failed and not left pending."""
    return result is not None and not isinstance(result, Exception)


def run_closed_loop(engine, requests, seconds: float, spans):
    """One caller, back to back: ``engine.serve`` of one request at a time
    for ``seconds``. Returns per-request records ``(ids, result | error,
    t_start, t_end)`` and the window's wall."""
    records = []
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter()
        if now - t0 >= seconds:
            break
        ids = next(requests)
        with spans.span("serving/flush", rows=len(ids)):
            try:
                res = engine.serve([ids])[0]
            except Exception as e:  # a failed request is counted, not fatal
                res = e
        records.append((ids, res, now - t0, time.perf_counter() - t0))
    return records, time.perf_counter() - t0


def run_open_loop(engine, requests, arrivals, flush_rows: int,
                  deadline_s: float, spans, drain_s: float = 60.0):
    """Submit each request at its scheduled offset; flush when
    ``flush_rows`` rows are pending or the oldest pending request has
    waited ``deadline_s``. Returns per-request ``latency`` (completion −
    scheduled arrival; NaN: never answered), ``queue_wait`` (start of its
    flush − scheduled arrival), ``late`` (how late the generator handed the
    request over: submit − the later of its schedule and the end of the
    flush that was running then, since ``submit`` waits for the engine's
    lock while a flush holds it),
    ``results``, per-flush ``(rows, t_start, t_end)`` and the window's
    wall: from the first scheduled instant to the later of the last
    arrival's schedule and the last completion. After the last arrival
    the loop waits up to ``drain_s`` for what is still pending."""
    n = len(requests)
    lat = np.full(n, np.nan)
    queue_wait = np.full(n, np.nan)
    late = np.zeros(n)
    results: list = [None] * n
    flushes = []
    pending: list[int] = []
    pending_rows = 0
    t0 = time.perf_counter()
    i = 0
    end = float(arrivals[-1])
    flush_end = 0.0
    while i < n or pending:
        now = time.perf_counter() - t0
        while i < n and arrivals[i] <= now:
            try:
                engine.submit(requests[i])
                pending.append(i)
                pending_rows += len(requests[i])
            except Exception as e:
                results[i] = e
            late[i] = ((time.perf_counter() - t0)
                       - max(arrivals[i], flush_end))
            i += 1
        oldest = arrivals[pending[0]] if pending else None
        if pending and (pending_rows >= flush_rows
                        or now - oldest >= deadline_s or i >= n):
            t_f = time.perf_counter() - t0
            with spans.span("serving/flush", rows=pending_rows):
                try:
                    out = engine.flush()
                except Exception as e:
                    out = [e] * len(pending)
            done = time.perf_counter() - t0
            for idx, res in zip(pending, out):
                results[idx] = res
                if not isinstance(res, Exception):
                    lat[idx] = done - arrivals[idx]
                    queue_wait[idx] = t_f - arrivals[idx]
            flushes.append((pending_rows, t_f, done))
            flush_end = done
            pending, pending_rows = [], 0
            continue
        if now > end + drain_s:
            break
        # idle until the next edge: an arrival or the deadline
        next_t = arrivals[i] if i < n else np.inf
        if oldest is not None:
            next_t = min(next_t, oldest + deadline_s)
        sleep = min(max(next_t - (time.perf_counter() - t0), 0.0), 0.01)
        if sleep > 0:
            with spans.span("bench/between_flushes"):
                time.sleep(sleep)
    wall = max(time.perf_counter() - t0, end)
    return {"latency": lat, "late": late, "queue_wait": queue_wait,
            "results": results,
            "flushes": flushes, "wall": wall}
