"""Serving bench: engine micro-bench + closed-loop traffic simulator.

Two modes, selected by ``SERVE_MODE``:

**micro** (default) — the PR-1 acceptance pin: a stream of mixed-size
recommend requests served by ``ServingEngine.serve`` vs one
``mesh_top_k_recommend`` call per request over the SAME prebuilt
catalog. ``value`` is engine users/s, ``vs_baseline`` the
engine/per-call speedup (bar ≥ 1.5).

**traffic** — the ROADMAP-item-3 acceptance harness: a traffic
simulator drives the two-stage quantized fast path
(``serving.retrieval``) and the exact full-catalog engine through
timed arrival streams (``SERVE_PATTERN``: poisson / diurnal / bursty)
over a *structured* synthetic catalog (a mixture of ``SERVE_CENTERS``
Gaussian centers — real embedding catalogs cluster, which is the
regime IVF routing is for; recall is MEASURED and reported either
way). It emits:

- saturation throughput for both engines (same bucket warmup) —
  ``fast_users_per_s`` / ``exact_users_per_s`` / ``fast_vs_exact``
  (the ≥3× @ 1M-items acceptance);
- ``recall_at_10`` of the fast path against the exact answers;
- a p99-latency-vs-offered-QPS curve (per-level p50/p99/achieved QPS/
  shed/degraded fractions) and ``qps_at_slo`` — the highest offered
  level whose p99 still met ``SERVE_SLO_MS``;
- an overload pass: offered load ≳3× capacity with admission control
  armed (``serving.admission``) — p99 of ACCEPTED requests stays
  bounded while load sheds (``overload_fast_p99_ms``,
  ``overload_shed_frac``, ``admission_transitions``), vs the
  admissionless exact baseline saturating (``overload_exact_p99_ms``);
- a rollout canary pass (``obs.budget``): a deliberately poisoned
  catalog version (row-shuffled item factors) served next to the
  healthy incumbent — per-version cohort rows, the service-level
  ``slo_burn_rate_fast`` / ``slo_burn_rate_slow`` pair, and
  ``verdict_latency_batches`` (canary batches until the verdict
  engine returns ROLLBACK on the poisoned leg).

Arrivals are open-loop (scheduled independently of completions — the
only shape that exposes saturation); the *control* loop is closed: the
engine's SLO tracker feeds the admission ladder which feeds back into
batching/degrade/shed decisions.

Contract (both modes): the LAST stdout line is one JSON object
``{"metric", "value", "unit", "vs_baseline", "extra"}``; stderr is
flushed before that line is printed, so ``2>&1``-merged wrappers always
parse it (the bench.py/pallas_probe/pod_dryrun hardening). Traffic-mode
rounds are committed as ``SERVING_r*.json`` and gated by
``scripts/bench_regress.py --family serving``.

Env knobs (micro): SERVE_USERS, SERVE_ITEMS, SERVE_RANK,
SERVE_REQUESTS, SERVE_REQ_MAX, SERVE_K, SERVE_MAX_BATCH, SERVE_DEVICES,
SERVE_FORCE_CPU (=1 → CPU with SERVE_DEVICES virtual devices — what the
contract tests and CI set; the default is jax's default backend, and the
result names the platform it ran on).
Traffic adds: SERVE_CENTERS, SERVE_CLUSTERS (0 = flat int8 stage 1),
SERVE_PROBE, SERVE_OVERFETCH, SERVE_PATTERN, SERVE_LEVELS (offered-QPS
multipliers of measured capacity), SERVE_SLO_MS, SERVE_DEADLINE_MS,
SERVE_TRAFFIC_REQUESTS, SERVE_RECALL_SAMPLE.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _emit_final(result: dict) -> None:
    """The machine-readable emit contract: flush stderr BEFORE printing
    the final JSON line, so a 2>&1-merged capture can always parse the
    last line (the same hardening bench.py / pallas_probe / pod_dryrun
    carry — an unflushed stderr write landing after the summary once
    cost a round its parsed result)."""
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def build_model(num_users: int, num_items: int, rank: int, seed: int = 0):
    """A seeded random-factor MFModel with identity id maps — serving
    cost does not depend on how the factors were fit."""
    import jax.numpy as jnp

    from large_scale_recommendation_tpu.data.blocking import flat_index
    from large_scale_recommendation_tpu.models.mf import MFModel

    rng = np.random.default_rng(seed)
    return MFModel(
        U=jnp.asarray(rng.normal(size=(num_users, rank)).astype(np.float32)),
        V=jnp.asarray(rng.normal(size=(num_items, rank)).astype(np.float32)),
        users=flat_index(np.arange(num_users, dtype=np.int64)),
        items=flat_index(np.arange(num_items, dtype=np.int64)),
    )


def run(num_users=20_000, num_items=8_192, rank=64, n_requests=400,
        req_max=64, k=10, max_batch=1024, n_dev=None, seed=0) -> dict:
    import jax

    from large_scale_recommendation_tpu.models.mf import MFModel  # noqa: F401
    from large_scale_recommendation_tpu.parallel.mesh import make_block_mesh
    from large_scale_recommendation_tpu.parallel.serving import (
        mesh_top_k_recommend,
        shard_catalog,
    )
    from large_scale_recommendation_tpu.serving.engine import ServingEngine

    model = build_model(num_users, num_items, rank, seed)
    mesh = make_block_mesh(n_dev)
    rng = np.random.default_rng(seed + 1)
    requests = [
        rng.integers(0, num_users, int(sz)).astype(np.int64)
        for sz in rng.integers(1, req_max + 1, n_requests)
    ]
    total_rows = sum(len(r) for r in requests)
    extra = {
        "device": str(jax.devices()[0]), "mesh_devices": len(mesh.devices),
        "catalog_rows": num_items, "num_users": num_users, "rank": rank,
        "requests": n_requests, "request_rows": total_rows,
        "req_size_max": req_max, "k": k, "max_batch": max_batch,
    }

    # ---- engine path FIRST: its executable-variant count must be its
    # own (the per-call baseline shares the per-mesh step cache, so
    # running it first would misattribute baseline compiles to the
    # engine) — and any shape the engine leaves warm only HELPS the
    # baseline below, keeping the reported speedup conservative
    engine = ServingEngine(model, k=k, mesh=mesh, max_batch=max_batch)
    engine.serve(requests[:4])  # warm the bucket family's hot entries
    # the published micro-batch/bucket evidence must describe the TIMED
    # run only — clear the warm-up's counters
    engine.stats.update(requests=0, rows=0, microbatches=0, buckets={})
    t0 = time.perf_counter()
    engine.serve(requests)
    engine_wall = time.perf_counter() - t0
    extra["engine_users_per_s"] = round(total_rows / engine_wall, 1)
    extra["engine_wall_s"] = round(engine_wall, 3)
    extra["engine_executable_variants"] = engine.executable_variants
    extra["engine_bucket_family_size"] = len(engine.bucket_family)
    extra["engine_microbatches"] = engine.stats["microbatches"]
    extra["engine_bucket_histogram"] = {
        str(b): c for b, c in sorted(engine.stats["buckets"].items())}

    # ---- per-call path: one mesh_top_k_recommend per request ----------
    # over a PREBUILT catalog and a device-RESIDENT U (what
    # model.recommend(mesh=...) holds), with every request-size bucket
    # pre-warmed — the strongest per-call baseline: its remaining cost
    # is per-request dispatch + undersized kernel calls, which is
    # exactly the overhead the engine claims to remove
    import jax.numpy as jnp

    catalog = shard_catalog(np.asarray(model.V), mesh)
    U = jnp.asarray(model.U)
    from large_scale_recommendation_tpu.utils.shapes import pow2_pad

    warm_sizes = sorted({min(pow2_pad(len(r)), 2048) for r in requests})
    for ws in warm_sizes:
        mesh_top_k_recommend(U, None, np.zeros(ws, np.int64), k=k,
                             catalog=catalog)
    t0 = time.perf_counter()
    for r in requests:
        mesh_top_k_recommend(U, None, r, k=k, catalog=catalog)
    percall_wall = time.perf_counter() - t0
    extra["percall_users_per_s"] = round(total_rows / percall_wall, 1)
    extra["percall_wall_s"] = round(percall_wall, 3)

    # ---- bf16 catalog rides along -------------------------------------
    bf16 = ServingEngine(model, k=k, mesh=mesh, max_batch=max_batch,
                         dtype="bfloat16")
    bf16.serve(requests[:4])
    t0 = time.perf_counter()
    bf16.serve(requests)
    extra["engine_bf16_users_per_s"] = round(
        total_rows / (time.perf_counter() - t0), 1)

    # ---- observability overhead: the SAME engine loop with the obs
    # layer live (registry + tracer, per-bucket histograms, spans) vs
    # the disabled run above — the acceptance pin is ≤3% regression,
    # and the disabled run costs nothing by construction (null layer)
    if os.environ.get("SERVE_OBS", "1") == "1":
        # Methodology matters more than the instrumentation here: (a) the
        # timed engine run above may still pay bucket-family compiles its
        # short warm-up missed, and the obs engine would inherit those
        # shapes warm (per-mesh step cache) — a serial comparison against
        # it misreads compile savings as negative overhead; (b) serial
        # passes also conflate machine drift with overhead (measured:
        # ±20% drift between identical disabled passes vs ~2% true
        # overhead). So: one obs-enabled engine, both fully warmed, then
        # INTERLEAVED timed passes, min-of-reps per side.
        from large_scale_recommendation_tpu import obs
        from large_scale_recommendation_tpu.obs.registry import (
            get_registry,
            set_registry,
        )
        from large_scale_recommendation_tpu.obs.trace import (
            get_tracer,
            set_tracer,
        )

        # save/restore whatever obs layer the CALLER had installed:
        # bench.py drives run() in-process, and clobbering a live
        # registry with the null layer would silently eat every metric
        # recorded after this section
        prev_reg, prev_tracer = get_registry(), get_tracer()
        reg, _tracer = obs.enable()
        try:
            oeng = ServingEngine(model, k=k, mesh=mesh,
                                 max_batch=max_batch)
            oeng.serve(requests)  # warm (all buckets, same shapes)
            engine.serve(requests)
            off_walls, on_walls = [], []
            for _ in range(int(os.environ.get("SERVE_OBS_REPS", 3))):
                t0 = time.perf_counter()
                engine.serve(requests)
                off_walls.append(time.perf_counter() - t0)
                t0 = time.perf_counter()
                oeng.serve(requests)
                on_walls.append(time.perf_counter() - t0)
            warm_wall, obs_wall = min(off_walls), min(on_walls)
            extra["engine_warm_users_per_s"] = round(
                total_rows / warm_wall, 1)
            extra["engine_obs_users_per_s"] = round(
                total_rows / obs_wall, 1)
            extra["obs_overhead_pct"] = round(
                100.0 * (obs_wall - warm_wall) / warm_wall, 2)
            extra["obs_metric_names"] = len(reg.names())
        finally:
            set_registry(prev_reg)
            set_tracer(prev_tracer)

    speedup = percall_wall / engine_wall
    return {
        "metric": (f"sustained serving users/s (engine vs per-call mesh "
                   f"path, {num_users}x{num_items} rank={rank}, "
                   f"{n_requests} requests ≤{req_max} users)"),
        "value": extra["engine_users_per_s"],
        "unit": "users/s",
        "vs_baseline": round(speedup, 2),
        "extra": extra,
    }


# --------------------------------------------------------------------------
# Traffic simulator (SERVE_MODE=traffic)
# --------------------------------------------------------------------------


def build_structured_model(num_users: int, num_items: int, rank: int,
                           n_centers: int = 256, spread: float = 2.0,
                           noise: float = 0.3, seed: int = 0):
    """A catalog with planted cluster structure: items drawn around
    ``n_centers`` Gaussian centers (the shape real embedding catalogs
    have — and the regime clustered MIPS routing exists for; the flat
    int8 path doesn't care). Queries stay isotropic Gaussian."""
    import jax.numpy as jnp

    from large_scale_recommendation_tpu.data.blocking import flat_index
    from large_scale_recommendation_tpu.models.mf import MFModel

    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(n_centers, rank)) * spread
    V = (centers[rng.integers(0, n_centers, num_items)]
         + noise * rng.normal(size=(num_items, rank))).astype(np.float32)
    U = rng.normal(size=(num_users, rank)).astype(np.float32)
    return MFModel(
        U=jnp.asarray(U), V=jnp.asarray(V),
        users=flat_index(np.arange(num_users, dtype=np.int64)),
        items=flat_index(np.arange(num_items, dtype=np.int64)))


def make_arrivals(pattern: str, n: int, qps: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Arrival offsets (seconds, sorted) for ``n`` requests at mean
    rate ``qps``: ``poisson`` (exponential gaps), ``diurnal`` (one
    compressed sinusoidal day — rate swings ±80% around the mean),
    ``bursty`` (alternating 4× on-bursts and 0.25× lulls)."""
    if pattern == "poisson":
        gaps = rng.exponential(1.0 / qps, n)
    elif pattern == "diurnal":
        # inhomogeneous Poisson by gap scaling: rate(t) tracks one
        # sine period over the stream
        gaps = np.empty(n)
        t = 0.0
        period = n / qps
        for i in range(n):
            rate = qps * (1.0 + 0.8 * np.sin(2 * np.pi * t / period))
            rate = max(rate, 0.05 * qps)
            gaps[i] = rng.exponential(1.0 / rate)
            t += gaps[i]
    elif pattern == "bursty":
        burst = int(max(8, n // 8))
        gaps = np.empty(n)
        for i in range(n):
            on = (i // burst) % 2 == 0
            gaps[i] = rng.exponential(1.0 / (qps * (4.0 if on else 0.25)))
    else:
        raise ValueError(f"unknown arrival pattern {pattern!r}")
    return np.cumsum(gaps)


def run_traffic_level(engine, requests, arrivals, deadline_s: float,
                      slo_ms: float) -> dict:
    """Drive one offered-load level through the engine: submit each
    request at its arrival offset, flush when the coalescing window
    fills (``max_batch`` rows, admission-widened) or the oldest pending
    ticket hits the batching deadline, measure per-request latency
    (completion − scheduled arrival: a backlogged engine pays its queue
    honestly). Returns the level's latency/QPS/shed/degraded stats."""
    from large_scale_recommendation_tpu.serving import (
        AdmissionRejectedError,
    )

    n = len(requests)
    lat = np.full(n, np.nan)
    shed = np.zeros(n, bool)
    degraded = np.zeros(n, bool)
    pending: list[tuple[int, float]] = []  # (request idx, arrival)
    pending_rows = 0
    t0 = time.perf_counter()
    i = 0

    def flush_pending():
        nonlocal pending, pending_rows
        results = engine.flush()
        done = time.perf_counter() - t0
        for (idx, arr), res in zip(pending, results):
            lat[idx] = done - arr
            degraded[idx] = getattr(res, "degraded", False)
        pending = []
        pending_rows = 0

    while i < n or pending:
        now = time.perf_counter() - t0
        while i < n and arrivals[i] <= now:
            try:
                engine.submit(requests[i])
                pending.append((i, arrivals[i]))
                pending_rows += len(requests[i])
            except AdmissionRejectedError:
                shed[i] = True
            i += 1
        widen = 1.0
        if engine.admission is not None:
            widen = engine.admission.widen_factor
        limit = int(engine.max_batch * widen)
        oldest = pending[0][1] if pending else None
        if pending and (pending_rows >= limit
                        or now - oldest >= deadline_s * widen
                        or i >= n):
            flush_pending()
            continue
        # idle until the next edge: an arrival or the deadline
        next_t = arrivals[i] if i < n else np.inf
        if oldest is not None:
            next_t = min(next_t, oldest + deadline_s * widen)
        sleep = min(max(next_t - (time.perf_counter() - t0), 0.0), 0.01)
        if sleep > 0:
            time.sleep(sleep)

    wall = time.perf_counter() - t0
    served = lat[~np.isnan(lat)]
    out = {
        "offered_qps": round(float(len(requests) / arrivals[-1]), 2),
        "achieved_qps": round(float(len(served) / wall), 2),
        "served": int(len(served)),
        "shed": int(shed.sum()),
        "shed_frac": round(float(shed.mean()), 4),
        "degraded_frac": round(float(degraded.mean()), 4),
        "p50_ms": (round(float(np.percentile(served, 50) * 1e3), 2)
                   if len(served) else None),
        "p99_ms": (round(float(np.percentile(served, 99) * 1e3), 2)
                   if len(served) else None),
        "met_slo": (bool(np.percentile(served, 99) * 1e3 <= slo_ms)
                    if len(served) else False),
    }
    return out


def run_traffic(num_users=20_000, num_items=262_144, rank=64,
                n_requests=400, req_max=32, k=10, max_batch=1024,
                n_centers=256, n_clusters=512, n_probe=16, overfetch=4,
                kmeans_sample=65536, pattern="poisson",
                levels=(0.02, 0.05, 0.1, 0.25, 0.5, 1.0), slo_ms=200.0,
                deadline_ms=25.0, recall_sample=256,
                overload_mult=3.0, seed=0) -> dict:
    import jax

    from large_scale_recommendation_tpu import obs
    from large_scale_recommendation_tpu.obs import health
    from large_scale_recommendation_tpu.serving import (
        AdmissionConfig,
        AdmissionController,
        RetrievalConfig,
        ServingEngine,
        recall_at_k,
    )

    # the rollout budget plane must exist BEFORE the engines are built
    # (each engine binds its handle at construction): every traffic
    # pass below is then attributed to the catalog version that served
    # it, and the canary pass at the end exercises the verdict engine
    budget = obs.enable_budget(
        slo_ms / 1e3, objective=0.9, fast_window=32, slow_window=256,
        min_samples=8, sample_budget=64)
    # the request plane rides the same lifecycle (ISSUE 20): engines
    # bind the handle at construction, so it too must exist first —
    # every flush below then carries a stage ledger and the sustained
    # pass's tail lands in the exemplar reservoir
    telemetry = obs.enable_requests(
        slo_ms / 1e3, objective=0.9, window=512, max_exemplars=64,
        slow_keep=16)

    model = build_structured_model(num_users, num_items, rank,
                                   n_centers=n_centers, seed=seed)
    rng = np.random.default_rng(seed + 1)
    requests = [rng.integers(0, num_users, int(sz)).astype(np.int64)
                for sz in rng.integers(1, req_max + 1, n_requests)]
    total_rows = sum(len(r) for r in requests)
    retrieval = RetrievalConfig(
        overfetch=overfetch,
        n_clusters=(n_clusters if n_clusters > 0 else None),
        n_probe=n_probe, kmeans_sample=kmeans_sample, seed=seed)
    t0 = time.perf_counter()
    fast = ServingEngine(model, k=k, retrieval=retrieval,
                         max_batch=max_batch)
    build_s = time.perf_counter() - t0
    exact = ServingEngine(model, k=k, max_batch=max_batch)
    extra = {
        "device": str(jax.devices()[0]), "catalog_rows": num_items,
        "num_users": num_users, "rank": rank, "k": k,
        "requests": n_requests, "request_rows": total_rows,
        "req_size_max": req_max, "max_batch": max_batch,
        "pattern": pattern, "slo_ms": slo_ms, "deadline_ms": deadline_ms,
        "catalog_build_s": round(build_s, 2),
        "index": dict(fast.retriever.catalog.stats),
    }

    # ---- saturation throughput, same bucket warmup both engines ------
    # best-of-reps per side: one descheduled slice on a shared 2-core
    # box can halve a single pass's rate (measured), and the ratio is
    # the acceptance bar — noise must not decide it
    warm = requests[:4]
    reps = int(os.environ.get("SERVE_SAT_REPS", 2))
    rates = {}
    for eng, name in ((fast, "fast"), (exact, "exact")):
        eng.serve(warm)
        best = 0.0
        for _ in range(reps):
            t0 = time.perf_counter()
            eng.serve(requests)
            best = max(best, total_rows / (time.perf_counter() - t0))
        rates[name] = best
        extra[f"{name}_users_per_s"] = round(best, 1)
    extra["fast_vs_exact"] = round(rates["fast"] / rates["exact"], 2)

    # ---- recall of the fast path against the exact answers -----------
    sample = rng.integers(0, num_users, recall_sample).astype(np.int64)
    ie, _ = exact.recommend(sample)
    ia, _ = fast.recommend(sample)
    extra["recall_at_10" if k == 10 else f"recall_at_{k}"] = round(
        recall_at_k(ia, ie), 4)

    # ---- warm the WHOLE bucket family, both stages -------------------
    # the curve flushes small deadline-bounded batches (buckets 8..256)
    # the saturation pass above never compiled, and the degrade level
    # additionally compiles stage-1-only variants: without this warmup
    # the low-QPS levels' p99 is XLA compile time, not serving latency
    import jax.numpy as jnp

    empty_excl = (np.zeros(8, np.int32), np.zeros(8, np.int32),
                  np.full(8, np.inf, np.float32))
    bucket = 8
    while bucket <= min(max_batch, fast.retriever.config.max_bucket):
        for stage1_only in (False, True):
            fast.retriever.topk(
                jnp.zeros((bucket, rank), jnp.float32), empty_excl,
                k=k, stage1_only=stage1_only)
        exact.recommend(np.zeros(bucket, np.int64))
        bucket <<= 1

    # ---- p99-vs-offered-QPS curve (admission armed) ------------------
    # capacity in requests/s: saturation users/s over mean request size.
    # NOTE the two operating modes: saturation throughput comes from
    # max_batch-deep coalescing, while the curve's deadline-bounded
    # flushes serve SMALL buckets whose per-row cost is far higher —
    # the latency knee sits well below multiplier 1.0, which is exactly
    # what the low rungs of the ladder exist to bracket.
    cap_qps = rates["fast"] / (total_rows / n_requests)
    slo = health.SLOTracker(target_s=slo_ms / 1e3, objective=0.9,
                            window=64)
    fast.attach_admission(AdmissionController(slo, AdmissionConfig()))
    curve = []
    for mult in levels:
        qps = cap_qps * mult
        # bound each level's wall: low rungs don't need the full
        # request stream to measure a stable p99
        n_lv = int(min(n_requests, max(60, qps * 20)))
        arr = make_arrivals(pattern, n_lv, qps, rng)
        level = run_traffic_level(fast, requests[:n_lv], arr,
                                  deadline_s=deadline_ms / 1e3,
                                  slo_ms=slo_ms)
        level["level"] = mult
        curve.append(level)
    extra["curve"] = curve
    met = [lv for lv in curve if lv["met_slo"]]
    extra["qps_at_slo"] = max((lv["achieved_qps"] for lv in met),
                              default=0.0)
    one_x = min(curve, key=lambda lv: abs(lv["level"] - 1.0))
    extra["p99_ms"] = one_x["p99_ms"]
    extra["p50_ms"] = one_x["p50_ms"]

    # ---- overload: admission sheds/degrades, p99 stays bounded -------
    qps = cap_qps * overload_mult
    arr = make_arrivals(pattern, n_requests, qps, rng)
    over = run_traffic_level(fast, requests, arr,
                             deadline_s=deadline_ms / 1e3, slo_ms=slo_ms)
    snap = fast.admission.snapshot()
    extra["overload_fast_p99_ms"] = over["p99_ms"]
    extra["overload_shed_frac"] = over["shed_frac"]
    extra["overload_degraded_frac"] = over["degraded_frac"]
    extra["admission_transitions"] = snap["transitions"]
    extra["admission_final_level"] = snap["level"]
    # the exact engine, admissionless, under the SAME offered load:
    # nothing sheds, the queue eats the backlog, p99 saturates
    over_exact = run_traffic_level(exact, requests, arr,
                                   deadline_s=deadline_ms / 1e3,
                                   slo_ms=slo_ms)
    extra["overload_exact_p99_ms"] = over_exact["p99_ms"]

    # ---- request-plane stamp: where the sustained pass's time went ---
    # per-stage medians/p99s over the plane's window (the curve +
    # overload passes fed it) plus the exemplar-reservoir census; the
    # full /slowz body optionally dumps for CI artifacts. Stamped keys
    # match the bench_regress DEFAULT_LOWER patterns ("request_stage",
    # "queue_wait") — watched via explicit --key only.
    req_snap = telemetry.snapshot()
    for stage, q in telemetry.stage_quantiles().items():
        # queue_wait stamps under its own name (its regress pattern)
        key = "queue_wait" if stage == "queue_wait" \
            else f"request_stage_{stage}"
        extra[f"{key}_s_p50"] = round(q["p50"], 6)
        extra[f"{key}_s_p99"] = round(q["p99"], 6)
    extra["request_dominant_stage"] = req_snap["dominant_stage"]
    extra["request_exemplars_kept"] = req_snap["kept"]
    extra["request_noted"] = req_snap["count"]
    extra["request_shed_noted"] = req_snap["shed"]
    slowz_out = os.environ.get("SERVING_SLOWZ_OUT")
    if slowz_out:
        with open(slowz_out, "w") as f:
            json.dump(req_snap, f, indent=1)

    # ---- rollout canary: poisoned catalog version, verdict latency ---
    # The canary serves a deliberately poisoned catalog (item factors
    # row-shuffled: identical latency, garbage answers) against the
    # healthy exact incumbent. Shadow recall of the canary against the
    # incumbent's answers feeds the budget plane as the shared eval
    # key, the verdict engine attributes the regression to the
    # canary's catalog version, and the verdict latency is the number
    # of canary batches until ROLLBACK.
    from large_scale_recommendation_tpu.models.mf import MFModel

    traffic_snap = budget.snapshot()
    extra["rollout_traffic_cohorts"] = traffic_snap["cohorts"]
    # service-level multi-window burn pair from the traffic phase (the
    # overload pass is what moves it); the canary pass below resets
    extra["slo_burn_rate_fast"] = round(
        traffic_snap["burn_rates"].get("fast", 0.0), 4)
    extra["slo_burn_rate_slow"] = round(
        traffic_snap["burn_rates"].get("slow", 0.0), 4)
    budget.reset()
    poisoned = MFModel(U=model.U,
                       V=model.V[rng.permutation(num_items)],
                       users=model.users, items=model.items)
    canary = ServingEngine(poisoned, k=k, max_batch=max_batch)
    inc_ver, can_ver = exact.version, canary.version
    verdict_batches = None
    last = None
    for b in range(1, 17):
        reqs = [rng.integers(0, num_users, 8).astype(np.int64)
                for _ in range(4)]
        inc_res = exact.serve(reqs)
        can_res = canary.serve(reqs)
        shadow = float(np.mean([recall_at_k(c[0], i[0])
                                for c, i in zip(can_res, inc_res)]))
        budget.note_eval(inc_ver, {"shadow_recall": 1.0})
        budget.note_eval(can_ver, {"shadow_recall": shadow})
        last = budget.verdicts.evaluate(can_ver, inc_ver)
        if last["verdict"] == "ROLLBACK":
            verdict_batches = b
            break
    if verdict_batches is not None:
        budget.verdicts.mark_rolled_back(can_ver)
    snap = budget.snapshot()
    extra["verdict_latency_batches"] = verdict_batches
    extra["rollout"] = {
        "incumbent_version": inc_ver,
        "canary_version": can_ver,
        "burn_rates": snap["burn_rates"],
        "cohorts": snap["cohorts"],
        "verdict": None if last is None else last["verdict"],
        "verdict_reason": None if last is None else last["reason"],
        "verdict_latency_batches": verdict_batches,
    }

    return {
        "metric": (f"two-stage quantized serving users/s vs exact "
                   f"full-catalog ({num_users}x{num_items} rank={rank}, "
                   f"{pattern} traffic, "
                   f"{'clustered' if n_clusters > 0 else 'flat'} "
                   f"stage 1)"),
        "value": extra["fast_users_per_s"],
        "unit": "users/s",
        "vs_baseline": extra["fast_vs_exact"],
        "extra": extra,
    }


def main() -> None:
    from large_scale_recommendation_tpu.utils.platform import (
        enable_compilation_cache,
        force_cpu,
        stamp_device,
    )

    if os.environ.get("SERVE_FORCE_CPU") == "1":
        force_cpu(n_devices=int(os.environ.get("SERVE_DEVICES", 8)))
    enable_compilation_cache()
    env = os.environ.get
    if env("SERVE_MODE", "micro") == "traffic":
        result = run_traffic(
            num_users=int(env("SERVE_USERS", 20_000)),
            num_items=int(env("SERVE_ITEMS", 262_144)),
            rank=int(env("SERVE_RANK", 64)),
            n_requests=int(env("SERVE_TRAFFIC_REQUESTS", 400)),
            req_max=int(env("SERVE_REQ_MAX", 32)),
            k=int(env("SERVE_K", 10)),
            max_batch=int(env("SERVE_MAX_BATCH", 1024)),
            n_centers=int(env("SERVE_CENTERS", 256)),
            n_clusters=int(env("SERVE_CLUSTERS", 512)),
            n_probe=int(env("SERVE_PROBE", 16)),
            overfetch=int(env("SERVE_OVERFETCH", 4)),
            kmeans_sample=int(env("SERVE_KMEANS_SAMPLE", 65536)),
            pattern=env("SERVE_PATTERN", "poisson"),
            levels=tuple(float(x) for x in
                         env("SERVE_LEVELS", "0.02,0.05,0.1,0.25,0.5,1").split(",")),
            slo_ms=float(env("SERVE_SLO_MS", 200)),
            deadline_ms=float(env("SERVE_DEADLINE_MS", 25)),
            recall_sample=int(env("SERVE_RECALL_SAMPLE", 256)),
            overload_mult=float(env("SERVE_OVERLOAD_MULT", 3.0)),
        )
    else:
        result = run(
            num_users=int(env("SERVE_USERS", 20_000)),
            num_items=int(env("SERVE_ITEMS", 8_192)),
            rank=int(env("SERVE_RANK", 64)),
            n_requests=int(env("SERVE_REQUESTS", 400)),
            req_max=int(env("SERVE_REQ_MAX", 64)),
            k=int(env("SERVE_K", 10)),
            max_batch=int(env("SERVE_MAX_BATCH", 1024)),
        )
    # nothing here is a chip number unless this says ``tpu``
    print(f"# ran on {stamp_device(result['extra'])}", file=sys.stderr)
    _emit_final(result)


if __name__ == "__main__":
    sys.exit(main())
