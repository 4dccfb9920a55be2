"""Runner kinds ``serve_closed`` and ``serve_open``: top-k serving through
``ServingEngine`` over random factor tables made from the seed.

``serve_closed``: one caller, requests of a fixed size back to back through
``ServingEngine.serve`` for the whole window. ``serve_open``: requests
arrive on a schedule drawn from the seed, are submitted at their scheduled
time (``ServingEngine.submit``) and flushed (``ServingEngine.flush``) when
``flush_rows`` rows are pending or the oldest has waited ``deadline_ms``;
latency runs from the scheduled arrival. The answers are compared with the
plain reference the configuration names, ``reference/<reference>.py``
(``topk_ref`` where it names none).
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import compare, datagen, harness, loadgen

REFERENCE = "topk_ref"  # of a configuration that names none


def build_engine(cfg: dict, U, V):
    from large_scale_recommendation_tpu.data.blocking import flat_index
    from large_scale_recommendation_tpu.models.mf import MFModel
    from large_scale_recommendation_tpu.serving.engine import ServingEngine
    from large_scale_recommendation_tpu.serving.retrieval import (
        RetrievalConfig,
    )

    model = MFModel(
        U=U, V=V,
        users=flat_index(np.arange(cfg["num_users"], dtype=np.int64)),
        items=flat_index(np.arange(cfg["num_items"], dtype=np.int64)))
    ret = cfg["retrieval"]
    return ServingEngine(
        model, k=cfg["k"], max_batch=cfg["max_batch"],
        min_bucket=cfg["min_bucket"],
        retrieval=RetrievalConfig(overfetch=ret["overfetch"],
                                  n_clusters=ret["n_clusters"],
                                  max_bucket=ret["max_bucket"]))


def pow2_at_least(n: int, floor: int) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


def buckets_reached(cfg: dict, traffic: dict) -> list[int]:
    """The row buckets this cell's traffic can reach, and no others."""
    cap = min(cfg["max_batch"], cfg["retrieval"]["max_bucket"])
    lo = cfg["min_bucket"]
    if traffic["runner"] == "serve_closed":
        n = int(traffic["request_users"]["fixed"])
        rows = {cap} if n >= cap else set()
        if n % cap:
            rows.add(min(pow2_at_least(n % cap, lo), cap))
        return sorted(rows)
    out, b = [], lo
    while b <= cap:
        out.append(b)
        b *= 2
    return out


def sample_answers(requests, results, rng, want_users: int,
                   per_request: int = 256):
    """Drawn from the seed among the requests the window finished, with
    the longest in it: ``(user_ids, served_ids, served_scores)``."""
    done = [j for j, res in enumerate(results) if loadgen.answered(res)]
    if not done:
        return None
    longest = max(done, key=lambda j: len(requests[j]))
    order = [longest] + [j for j in rng.permutation(done) if j != longest]
    users, ids, scores, n = [], [], [], 0
    for j in order:
        if n >= want_users:
            break
        req = np.asarray(requests[j])
        pos = np.arange(len(req))
        if len(req) > per_request:
            pos = np.sort(rng.choice(len(req), per_request, replace=False))
        users.append(req[pos])
        ids.append(np.asarray(results[j][0])[pos])
        scores.append(np.asarray(results[j][1])[pos])
        n += len(pos)
    return (np.concatenate(users), np.concatenate(ids),
            np.concatenate(scores))


def run(cell, seed: int, seconds: float, trace: bool, device: dict,
        control: str | None = None) -> dict:
    cfg, traffic = cell.config, cell.traffic
    on_chip = device["platform"] == "tpu"
    nu, ni, rank = cfg["num_users"], cfg["num_items"], cfg["num_factors"]
    reference = harness.reference_for(cell, REFERENCE)

    # -- set-up: factors on the device from the seed, engine, warm buckets
    U, V = datagen.serving_factors(seed, num_users=nu, num_items=ni,
                                   rank=rank)
    engine = build_engine(cfg, U, V)
    warm_rng = np.random.default_rng(0)
    for rows in buckets_reached(cfg, traffic):
        engine.submit(warm_rng.integers(0, nu, rows, dtype=np.int64))
        engine.flush()
        engine.serve([warm_rng.integers(0, nu, rows, dtype=np.int64)])
    window = harness.Window(trace, harness.trace_dir_for(cell.name),
                            strict=on_chip)
    spans = window.spans
    open_loop = traffic["runner"] == "serve_open"
    if open_loop:
        arrivals, requests = loadgen.open_loop_schedule(
            traffic, seconds, seed, nu)
    else:
        stream = loadgen.closed_loop_requests(traffic, seed, nu)
    buckets_before = dict(engine.stats["buckets"])

    # -- the window
    series = {}
    with window.measure():
        if open_loop:
            out = loadgen.run_open_loop(
                engine, requests, arrivals, int(traffic["flush_rows"]),
                float(traffic["deadline_ms"]) / 1e3, spans)
            results, wall = out["results"], out["wall"]
        else:
            records, wall = loadgen.run_closed_loop(
                engine, stream, seconds, spans)
            requests = [rec[0] for rec in records]
            results = [rec[1] for rec in records]
    peak = harness.memory_peak_bytes()
    reduced = window.reduce()

    answered = [j for j, res in enumerate(results) if loadgen.answered(res)]
    users_answered = int(sum(len(requests[j]) for j in answered))
    attempted = len(requests)
    failed = attempted - len(answered)
    values = {"serve_users_per_s": users_answered / wall,
              "setup_s": window.setup_s}
    if open_loop:
        lat = np.where(np.isnan(out["latency"]), np.inf, out["latency"])
        # not an end-to-end metric of the manifest (PERF.md, Open questions
        # 2); kept in the notes of every run and as the per-layer metric
        # request_p95_ms of the traced run
        values["request_p95_ms"] = float(np.percentile(lat, 95)) * 1e3
        series["request_latency_ms"] = lat * 1e3
        series["queue_wait_ms"] = out["queue_wait"] * 1e3
        series["loadgen_late_ms"] = out["late"] * 1e3
        series["flush_rows"] = [f[0] for f in out["flushes"]]
    else:
        series["flush_rows"] = [len(r) for r in requests]
    series["flush_wall_ms"] = [d * 1e3 for d in
                               spans.durations("serving/flush")]
    series["bucket_rows"] = [
        b for b, n in engine.stats["buckets"].items()
        for _ in range(n - buckets_before.get(b, 0))]
    # the five slowest flushes, for whoever has to explain a tail:
    # [seconds into the window, wall ms, rows]
    t0 = window.t0
    slowest = sorted(
        ([round(a - t0, 3), round((b - a) * 1e3, 2), attrs.get("rows")]
         for name, a, b, attrs in spans.records if name == "serving/flush"),
        key=lambda f: -f[1])[:5]
    print(f"serve: {attempted} requests, {failed} failed, "
          f"{users_answered} users in {wall:.3f}s, "
          f"{len(series['flush_wall_ms'])} flushes", flush=True)

    # -- the comparison, once the window has closed, the peak has been
    # read and the program's state is freed
    del engine
    gc.collect()
    t_ref = time.perf_counter()
    sample = sample_answers(requests, results,
                            np.random.default_rng(int(seed) + 1),
                            int(traffic.get("check_users", 512)))
    if sample is None:
        raise SystemExit("serve: the window finished no request")
    s_users, s_ids, s_scores = sample
    if control == "int8":
        s_ids, s_scores = reference.int8_answers(U, V, s_users, cfg["k"])
    ref_top, _, ref_at, ref_std = reference.exact_topk(
        U, V, s_users, s_ids, cfg["k"])
    numbers = compare.topk_numbers(s_ids, s_scores, ref_top, ref_at,
                                   ref_std)
    print(f"serve: reference over {len(s_users)} users "
          f"{time.perf_counter() - t_ref:.1f}s", flush=True)
    numbers = {k: v for k, v in numbers.items() if k in cfg["limits"]}
    correct, compared = compare.judge(numbers, cfg["limits"])

    ctx = {
        "trace": reduced, "chips": cell.chips, "window_s": wall,
        "peaks": harness.peaks_for(device),
        "series": series,
        "counters": {"users_answered": users_answered},
        "sizes": {"num_users": nu, "num_items": ni, "rank": rank},
    }
    return {"correct": (correct and failed == 0
                        and window.compiles.count == 0),
            "compared": compared, "attempted": attempted, "failed": failed,
            "fatal": None, "values": values, "ctx": ctx,
            "memory_peak_bytes": peak, "reduced": reduced,
            "compiles_in_window": window.compiles.count,
            "notes": {"flushes": len(series["flush_wall_ms"]),
                      "checked_users": int(len(s_users)),
                      "slowest_flushes": slowest}}
