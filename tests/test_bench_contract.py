"""The benchmark deliverable's contract: one JSON line with the required
fields, produced end-to-end by the real child on a reduced config.

The driver runs ``python bench.py`` at round end and parses the last
stdout line — a regression here silently costs the round its perf
evidence, so the contract is pinned in the suite (slow-marked).
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_bench_child_emits_contract_json():
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "BENCH_FORCE_CPU": "1",
        "BENCH_NNZ": "200000",
        "BENCH_RANK": "16",
        "BENCH_ITERS": "1",
        "BENCH_MB": "4096",
        "BENCH_BLOCKS": "2",
        "BENCH_SKIP_EXTRAS": "1",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--child"],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    d = json.loads(lines[-1])
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in d, f"missing {key}"
    assert d["value"] > 0
    assert d["unit"] == "ratings/s"
    e = d["extra"]
    for key in ("h2d_mbps", "pipeline", "rmse_curve", "dsgd_train_wall_s",
                "effective_hbm_gbs", "numpy_seq_baseline_ratings_per_s"):
        assert key in e, f"missing extra.{key}"
    assert e["pipeline"] == "device"
    # a CPU rehearsal says so, and prints no share of a v5e's peaks
    assert (e["platform"], e["device_kind"]) == ("cpu", "cpu")
    assert e["pct_of_hbm_peak"] is None and e["pct_of_fp32_peak"] is None
    assert e["kernel_route"] == "xla"


def _run_merged(code: str) -> list[str]:
    """Run a snippet with stderr MERGED into stdout (the 2>&1 shape the
    round driver's wrapper captures) and return its non-empty lines."""
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, text=True,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout[-2000:]
    return [ln for ln in proc.stdout.splitlines() if ln.strip()]


def test_emit_final_is_last_merged_line_on_success():
    """The machine-readable emit contract, success path: even with
    stderr merged into stdout and a stderr comment written right before,
    the LAST line is the parseable JSON summary (round-5 driver wrapper
    recorded `parsed: null` when an unflushed stderr write landed after
    it)."""
    lines = _run_merged(
        "import sys; sys.path.insert(0, '.'); import bench\n"
        "print('# extras echo that must not land last', file=sys.stderr)\n"
        "bench._emit_final({'metric': 'm', 'value': 1.5,\n"
        "                   'unit': 'ratings/s', 'vs_baseline': 2.0,\n"
        "                   'extra': {}})\n")
    d = json.loads(lines[-1])
    assert d["value"] == 1.5
    for key in ("metric", "unit", "vs_baseline"):
        assert key in d


def test_parent_exits_nonzero_when_its_child_fails():
    """``python bench.py`` runs the child ONCE and exits with its code.
    Here the child fails because the device is not a TPU and
    ``BENCH_FORCE_CPU=1`` was not given: the parent must exit non-zero,
    name the platform, and print no result line — the removed ladder
    answered this with a CPU run (or ``value: 0.0``) and exit 0, which
    is how CPU numbers were filed under a chip metric."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("BENCH_")}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=300, cwd=REPO,
    )
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "not a TPU" in proc.stderr
    assert '"value"' not in proc.stdout


def test_fallback_ladder_is_gone():
    """No probe, retry or fallback survives in the parent (no jax
    import: ``bench`` at module scope is the parent half)."""
    sys.path.insert(0, REPO)
    import bench

    for name in ("_cpu_fallback", "CPU_FALLBACK_ENV", "ON_CHIP_ARTIFACT",
                 "_device_preprobe", "_looks_transient",
                 "_failure_result", "_attempt", "HBM_PEAK_GBS"):
        assert not hasattr(bench, name), name
    assert "jax" not in vars(bench)


def test_serving_bench_emits_contract_json():
    """The sustained-serving line's contract: scripts/serving_bench.py
    emits one JSON line with the standard fields, users/s unit, the
    engine-vs-per-call speedup as vs_baseline, and the engine evidence
    keys (rates, bf16 rate, executable-variant count) in extra — the
    same keys bench.py's serving_engine_* extras are built from."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "SERVE_FORCE_CPU": "1",  # SERVE_DEVICES virtual CPU devices
        "SERVE_USERS": "2000",
        "SERVE_ITEMS": "1024",
        "SERVE_RANK": "16",
        "SERVE_REQUESTS": "40",
        "SERVE_DEVICES": "4",
        "SERVE_MAX_BATCH": "256",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "serving_bench.py")],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    d = json.loads(lines[-1])
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in d, f"missing {key}"
    assert d["unit"] == "users/s"
    assert d["value"] > 0
    e = d["extra"]
    for key in ("engine_users_per_s", "percall_users_per_s",
                "engine_bf16_users_per_s", "engine_executable_variants",
                "engine_microbatches", "engine_bucket_histogram",
                "mesh_devices", "request_rows",
                # the obs_overhead_* contract: bench.py's instrumentation-
                # overhead extras are built from these keys — enabled-run
                # rate plus the enabled-vs-disabled delta. Structural
                # only (key presence + a sane range), NOT a wall-clock
                # gate: on a loaded shared runner a 3% threshold would be
                # an intermittent red; the ≤3% evidence lives in the
                # bench rounds' obs_overhead_pct extra
                "engine_obs_users_per_s", "obs_overhead_pct",
                "obs_metric_names"):
        assert key in e, f"missing extra.{key}"
    assert e["engine_obs_users_per_s"] > 0
    assert e["obs_metric_names"] > 0
    # the result names the device it ran on, and so does stderr
    assert e["platform"] == "cpu" and e["device_count"] >= 4
    assert "ran on {'platform': 'cpu'" in proc.stderr
    # the compile-count contract: the executable family is the pow2
    # bucket family (here ≤ {8..256} = 6 shapes), not the request count
    assert 0 < e["engine_executable_variants"] <= 6
    assert e["engine_microbatches"] < int(env["SERVE_REQUESTS"])


def test_serving_traffic_bench_contract_on_merged_stream():
    """The traffic-simulator contract (SERVE_MODE=traffic), captured
    with stderr MERGED into stdout — the 2>&1 shape the round driver's
    wrapper records. The LAST merged line must be the parseable JSON
    summary (the stderr-flush-before-final-line hardening
    bench.py/pallas_probe/pod_dryrun already carry), with the fast-path
    vs exact rates, recall, the p99-vs-QPS curve, and the
    overload/admission evidence keys the SERVING_r*.json regress family
    gates on."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "SERVE_FORCE_CPU": "1",  # SERVE_DEVICES virtual CPU devices
        "SERVE_MODE": "traffic",
        "SERVE_USERS": "500",
        "SERVE_ITEMS": "2048",
        "SERVE_RANK": "16",
        "SERVE_TRAFFIC_REQUESTS": "60",
        "SERVE_REQ_MAX": "16",
        "SERVE_DEVICES": "2",
        "SERVE_MAX_BATCH": "256",
        "SERVE_CENTERS": "32",
        "SERVE_CLUSTERS": "16",
        "SERVE_PROBE": "8",
        "SERVE_LEVELS": "0.5,1",
        "SERVE_RECALL_SAMPLE": "32",
        "SERVE_KMEANS_SAMPLE": "2048",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "serving_bench.py")],
        env=env, text=True, timeout=600, cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,  # 2>&1 merge
    )
    assert proc.returncode == 0, proc.stdout[-2000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    d = json.loads(lines[-1])  # the merged-stream emit contract
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in d, f"missing {key}"
    assert d["unit"] == "users/s"
    assert d["value"] > 0
    e = d["extra"]
    for key in ("fast_users_per_s", "exact_users_per_s", "fast_vs_exact",
                "recall_at_10", "qps_at_slo", "p99_ms", "p50_ms",
                "overload_fast_p99_ms", "overload_exact_p99_ms",
                "overload_shed_frac", "overload_degraded_frac",
                "admission_transitions", "admission_final_level",
                "catalog_build_s", "index", "curve"):
        assert key in e, f"missing extra.{key}"
    assert e["index"]["mode"] == "clustered"
    assert 0.0 <= e["recall_at_10"] <= 1.0
    assert len(e["curve"]) == 2
    for level in e["curve"]:
        for key in ("offered_qps", "achieved_qps", "p99_ms",
                    "shed_frac", "degraded_frac", "met_slo"):
            assert key in level, f"missing curve.{key}"


def test_streams_bench_emits_contract_json():
    """The durable-ingest line's contract: scripts/streams_bench.py
    emits one JSON line with the standard fields, ratings/s unit, the
    durable/bare throughput-retention ratio as vs_baseline, and the
    ingest evidence keys (rates, zero end-of-run lag, checkpoint count)
    in extra — the same keys bench.py's streams_ingest_* extras are
    built from."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "STREAMS_USERS": "1000",
        "STREAMS_ITEMS": "400",
        "STREAMS_RANK": "8",
        "STREAMS_BATCHES": "5",
        "STREAMS_BATCH": "4000",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "streams_bench.py")],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    d = json.loads(lines[-1])
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in d, f"missing {key}"
    assert d["unit"] == "ratings/s"
    assert d["value"] > 0
    e = d["extra"]
    for key in ("ingest_ratings_per_s", "bare_ratings_per_s",
                "log_append_ratings_per_s", "ingest_lag_records",
                "checkpoints_written", "queue_depth_high_water"):
        assert key in e, f"missing extra.{key}"
    # the driver drained the whole log (zero end-of-run lag) and wrote
    # its per-batch recovery checkpoints
    assert e["ingest_lag_records"] == 0
    assert e["checkpoints_written"] == int(env["STREAMS_BATCHES"])
    assert e["platform"] == "cpu" and e["device_kind"] == "cpu"
    # structural only — no wall-clock-ratio gate here: this test rides
    # tier-1 (and the new CI workflow), where a loaded shared runner
    # would turn a perf threshold into an intermittent red; the
    # throughput-retention evidence lives in the bench rounds'
    # streams_ingest_vs_bare extras instead
    assert d["vs_baseline"] > 0


def test_streams_bench_parallel_contract_on_merged_stream():
    """The N_CONSUMERS mode's contract (ISSUE 13): with
    STREAMS_CONSUMERS set, streams_bench emits the parallel-ingest
    round as ONE final JSON line on a 2>&1-MERGED stream (the
    stderr-flush-before-final-JSON hardening — progress lines go to
    stderr mid-run), carrying the scaling-curve, recovery and
    freshness-SLO evidence keys the ``--family ingest`` gate watches."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "STREAMS_CONSUMERS": "1,2",
        "STREAMS_USERS": "800",
        "STREAMS_ITEMS": "300",
        "STREAMS_RANK": "8",
        "STREAMS_BATCHES": "4",
        "STREAMS_BATCH": "3000",
        "STREAMS_CHECKPOINT_EVERY": "2",
        "STREAMS_FRESHNESS_S": "1",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "streams_bench.py")],
        env=env, text=True, timeout=600, cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,  # 2>&1 merge
    )
    assert proc.returncode == 0, proc.stdout[-2000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    d = json.loads(lines[-1])  # the merged-stream emit contract
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in d, f"missing {key}"
    assert d["unit"] == "ratings/s"
    assert d["value"] > 0
    e = d["extra"]
    for key in ("cpu_count", "curve",
                "ingest_n1_ratings_per_s", "ingest_n2_ratings_per_s",
                "scaling_eff_n2", "checkpoints_n1", "checkpoints_n2",
                "recovery_s", "recovery_replayed_records",
                "duplicate_window_batches_max", "duplicate_window_bound",
                "freshness_slo_held", "critical_path_partitions",
                "critical_path_samples"):
        assert key in e, f"missing extra.{key}"
    assert e["curve"] == [1, 2]
    # the recovery pass accounted a bounded per-partition replay and
    # the sustained pass held the freshness SLO with samples resolving
    # for BOTH partitions
    assert e["duplicate_window_batches_max"] <= e["duplicate_window_bound"]
    assert e["freshness_slo_held"] == 1
    assert e["critical_path_partitions"] == 2
    # cores < N must surface the honest caveat; enough cores must not
    if e["cpu_count"] < 2:
        assert "error" in d and "core" in d["error"]
    else:
        assert "error" not in d


def test_streams_bench_tiered_contract():
    """The TIERED mode's contract (ISSUE 17): with STREAMS_TIER_SLOTS
    set, streams_bench drives the SAME bounded-Zipf WAL stream all-HBM
    and through a TieredFactorStore and emits one JSON line carrying
    the tier's report-card keys (the ``--family tier`` watch set), the
    bit-exactness evidence, and the ALWAYS-stamped simulated-budget
    caveat. Structural + correctness only — no throughput-ratio gate
    in tier-1 (the shared-runner lesson above); retention evidence
    lives in the committed TIERED_r* rounds."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "STREAMS_TIER_SLOTS": "2048",
        "STREAMS_USERS": "100000",
        "STREAMS_ITEMS": "500",
        "STREAMS_RANK": "8",
        "STREAMS_BATCHES": "8",
        "STREAMS_BATCH": "4000",
        "STREAMS_CHECKPOINT_EVERY": "4",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "streams_bench.py")],
        env=env, text=True, timeout=600, cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,  # 2>&1 merge
    )
    assert proc.returncode == 0, proc.stdout[-2000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    d = json.loads(lines[-1])
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in d, f"missing {key}"
    assert d["unit"] == "ratings/s"
    assert d["value"] > 0
    e = d["extra"]
    for key in ("hbm_ratings_per_s", "tiered_ratings_per_s",
                "tiered_vs_hbm_frac", "user_rows", "device_budget_x",
                "tier_hit_rate", "tier_prefetch_wait_s",
                "tier_evictions", "tier_writebacks", "tier_host_bytes",
                "tier_prefetched_rows", "bit_exact", "serve_bit_exact",
                "tier_serve_hits", "tier_serve_misses"):
        assert key in e, f"missing extra.{key}"
    # the pinned invariant on the real pipeline: values AND answers
    assert e["bit_exact"] is True
    assert e["serve_bit_exact"] is True
    # the table genuinely outgrew the pool and the pool cycled
    assert e["device_budget_x"] >= 2.0
    assert e["tier_evictions"] > 0
    assert 0.0 <= e["tier_hit_rate"] <= 1.0
    # the honest caveat is stamped on EVERY tiered round, not just
    # degraded ones — a CPU slot-pool cap is not HBM pressure
    assert "simulated device budget" in d.get("error", "")


@pytest.mark.slow
def test_bench_kernel_knob_routes_pallas():
    """BENCH_KERNEL=pallas drives the headline through the model layer's
    kernel routing (interpreted: BENCH_FORCE_CPU=1 is the explicit CPU
    rehearsal) and records the choice and the route in the JSON — the driver-form twin of scripts/pallas_northstar.py."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "BENCH_FORCE_CPU": "1",
        "BENCH_NNZ": "60000",
        "BENCH_USERS": "600",
        "BENCH_ITEMS": "300",
        "BENCH_RANK": "16",
        "BENCH_ITERS": "1",
        "BENCH_MB": "512",
        "BENCH_BLOCKS": "4",
        "BENCH_SKIP_EXTRAS": "1",
        "BENCH_KERNEL": "pallas",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--child"],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    d = json.loads(lines[-1])
    assert d["extra"]["kernel"] == "pallas"
    assert d["extra"]["kernel_route"] == "pallas/stratum_pipeline"
    assert d["value"] > 0
    # training actually descended (the Pallas path really trained)
    curve = d["extra"]["rmse_curve"]
    assert curve[-1] < curve[0], curve
