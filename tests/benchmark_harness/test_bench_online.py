"""The online stream's cell (``syn10m1m-r512-online.ingest-replay``)
rehearsed on the CPU through ``run.py`` at the configuration's own toy
size, held to its real limits: counts and ``correct`` only, never a time.
And the unit cases of the files it brought: the runner kind's helpers, the
control and the fault against ``compare.judge``, the cell's lists by
membership, and the per-layer readers on hand-made events put through the
real reduction (a CPU capture holds no device plane to record)."""

import json
import os

import numpy as np
import pytest

import bench_testlib
from bench_testlib import run_toy
from benchmark import compare, harness, run as bench_run
from benchmark import trace_reduce as tr
from benchmark.layer_metrics import online_update_hbm_roofline as roof
from benchmark.peaks import load_peaks
from benchmark.runners import ingest

ROOT = bench_testlib.ROOT
CELL = "syn10m1m-r512-online.ingest-replay"
NEW_METRICS = ("online_update_device_ms", "online_update_hbm_roofline",
               "online_prepare_ms_p50", "online_source_wait_ms_p50")
COMPARED = {"loss_gap", "first_update_gap", "update_gap", "table_diff",
            "ratings_missing", "offset_behind_head"}


@pytest.fixture(scope="module")
def toy():
    return run_toy(CELL)


@pytest.fixture(scope="module")
def traced():
    return run_toy(CELL, trace=True)


def test_rehearsal_is_correct_under_the_real_limits_and_counts(toy):
    line, out = toy
    real = harness.resolve_cell(CELL).config
    assert set(out) == bench_run.RESULT_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_ratings_per_s", "setup_s"}
    assert set(line["compared"]) == COMPARED
    assert {k: c["limit"] for k, c in line["compared"].items()} == (
        real["limits"])
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())
    assert out["compiles_in_window"] == 0
    notes = out["notes"]
    cfg = bench_testlib.toy_cell(CELL).config
    mbr, warm = cfg["micro_batch_records"], 2
    # the guarantees, to the unit: every rating written was applied, once,
    # and the stamped offset is the log's head
    assert notes["batches"] == cfg["batches"] == 64
    assert notes["ratings_applied"] == 64 * mbr
    assert notes["log_head"] == notes["consumed_offset"] == (64 + warm) * mbr
    assert line["compared"]["ratings_missing"]["value"] == 0
    assert line["compared"]["offset_behind_head"]["value"] == 0
    # it learns: the holdout RMSE falls along the stream
    rmse = notes["stamp_rmse"]
    assert sorted(rmse) == [1, 8]
    assert rmse[8] < rmse[1]
    assert notes["end_rmse"] < cfg["target_rmse"]
    assert notes["reference"] == "online_ref" == ingest.REFERENCE
    counters = out["ctx"]["counters"]
    assert counters == {"sweeps_done": 1, "batches": 64,
                        "ratings_applied": 64 * mbr}
    assert out["ctx"]["sweep_flops"] == 6 * cfg["num_factors"] * 64 * mbr
    # tile-rounded or pow2 rows of float32, nothing else of size
    assert list(notes["table_bytes"]) == [8192 * 16 * 4, 2048 * 16 * 4]


def test_a_traced_rehearsal_reads_the_host_seams(traced):
    line, out = traced
    assert line["correct"] is True
    series = out["ctx"]["series"]
    # one span a micro-batch of the window; the source is asked once more
    assert len(series["online_prepare_s"]) == 64
    assert len(series["online_source_wait_s"]) == 65
    assert {"online_prepare_ms_p50", "online_source_wait_ms_p50"} <= set(
        line["metrics"])
    assert line["metrics"]["online_prepare_ms_p50"]["value"] > 0
    # off the chip: no device plane, so no device time and no share of a peak
    assert not {"online_update_device_ms", "online_update_hbm_roofline",
                "train_step_mfu"} & set(line["metrics"])


def test_control_bf16_keeps_every_key_and_is_not_correct(toy):
    line, _ = toy
    control, _ = run_toy(CELL, control="bf16")
    assert set(control) == set(line)
    assert set(control["compared"]) == set(line["compared"])
    assert set(control["notes"]) == set(line["notes"])
    assert control["correct"] is False
    assert (control["compared"]["table_diff"]["value"]
            > 100 * line["compared"]["table_diff"]["value"])
    # the guarantees hold under the control: it is wrong, not lossy
    assert control["compared"]["ratings_missing"]["value"] == 0
    with pytest.raises(SystemExit, match="no control"):
        run_toy(CELL, control="nope")


def test_the_fault_half_of_every_micro_batch_is_not_correct():
    from benchmark.tools import limits_ingest

    cell = bench_testlib.toy_cell(CELL)
    numbers, correct = limits_ingest.fault_numbers(cell, 5, "half_batch")
    assert not correct
    assert numbers["table_diff"] > 1000 * cell.config["limits"]["table_diff"]
    ok, _ = compare.judge(numbers, cell.config["limits"])
    assert not ok


def test_a_target_out_of_reach_is_a_failed_run(capsys):
    with pytest.raises(SystemExit) as e:
        run_toy(CELL, target_rmse=1e-3)
    assert e.value.code not in (0, None)
    assert "target not reached" in capsys.readouterr().err


def test_a_program_without_tables_read_in_place_ends_before_any_input(
        monkeypatch):
    """The parent of PR 35 under this PR's benchmark files: the runner
    cannot read its tables in place, and the run ends non-zero at once."""
    from large_scale_recommendation_tpu.data.tables import (
        GrowableFactorTable,
    )

    monkeypatch.delattr(GrowableFactorTable, "borrowed")
    monkeypatch.setattr(ingest, "make_stream", lambda *a, **k: 1 / 0)
    with pytest.raises(SystemExit, match="borrowed"):
        run_toy(CELL)


# -- the runner kind's helpers -------------------------------------------------


@pytest.mark.parametrize("seconds,trace", [
    (51.0, False), (48.0, False), (24.0, False), (1.0, False), (51.0, True)])
def test_batches_for(seconds, trace):
    """A fixed amount of work from ``full_at_seconds`` up, the share below
    in whole 64s, a short traced window."""
    cell = harness.resolve_cell(CELL)
    full, traced = cell.traffic["batches"], cell.traffic["traced_batches"]
    assert full % 64 == 0 and cell.traffic["full_at_seconds"] == 48
    want = {(51.0, False): full, (48.0, False): full,
            (24.0, False): max(64, full // 2 // 64 * 64), (1.0, False): 64,
            (51.0, True): min(full, traced)}[seconds, trace]
    assert ingest.batches_for(cell, seconds, trace) == (want, want < full)


@pytest.mark.parametrize("train,share", [(65536 * 1474, 0.05), (1000, 0.05),
                                         (66 * 1024, 0.05), (7, 0.5)])
def test_nnz_for_leaves_at_least_the_stream(train, share):
    nnz = ingest.nnz_for(train, share)
    assert nnz - int(round(nnz * share)) >= train
    assert (nnz - 1) - int(round((nnz - 1) * share)) < train + 1


def test_check_ids_are_touched_drawn_from_the_seed_and_a_quarter_early():
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 5000, 4000)
    a = ingest.check_ids(3, ids, 500, 256)
    assert a.size == 256 and np.unique(a).size == 256
    assert np.isin(a, ids).all()
    assert np.isin(a, ids[:500]).sum() >= 64
    assert np.array_equal(a, ingest.check_ids(3, ids, 500, 256))
    assert not np.array_equal(a, ingest.check_ids(2**31 + 7, ids, 500, 256))
    # fewer touched ids than asked for: all of them
    assert ingest.check_ids(1, np.array([4, 4, 9]), 1, 256).tolist() == [4, 9]


def test_the_stream_is_whole_micro_batches_from_the_seed():
    cell = bench_testlib.toy_cell(CELL)
    (u, i, r), hold = ingest.make_stream(cell, 2**31 + 11, 5)
    assert u.shape == i.shape == r.shape == (5 * 1024,)
    assert u.dtype == np.int32 and r.dtype == np.float32
    assert 0 <= u.min() and u.max() < cell.config["num_users"]
    assert 0 <= i.min() and i.max() < cell.config["num_items"]
    assert hold[0].shape[0] >= 0.05 * 5 * 1024
    (u2, _, _), _ = ingest.make_stream(cell, 2**31 + 11, 5)
    assert np.array_equal(u, u2)
    U, V = ingest.starting_tables(cell, 2**31 + 11)
    assert U.shape == (6000, 16) and V.shape == (2048, 16)
    assert U.dtype == np.float32 and abs(U.std() - 0.25) < 0.01


def test_the_control_rounds_the_rows_and_nothing_else():
    import jax.numpy as jnp

    from large_scale_recommendation_tpu.core.updaters import SGDUpdater

    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(8, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(8, 16)).astype(np.float32))
    r = jnp.asarray(rng.normal(size=8).astype(np.float32))
    w = jnp.ones(8, jnp.float32)
    ctrl = ingest.control_updater("bf16", {"learning_rate": 0.03})
    plain = SGDUpdater(learning_rate=0.03)
    du, dv = ctrl.delta(r, u, v, weights=w)
    pu, pv = plain.delta(r, u, v, weights=w)
    rel = float(jnp.linalg.norm(du - pu) / jnp.linalg.norm(pu))
    assert 1e-4 < rel < 3e-2      # bfloat16's 8 bits, not float32's 24
    # on rows that bfloat16 holds exactly it IS the plain step
    ub, vb = (x.astype(jnp.bfloat16).astype(jnp.float32) for x in (u, v))
    du, dv = ctrl.delta(r, ub, vb, weights=w)
    pu, pv = plain.delta(r, ub, vb, weights=w)
    np.testing.assert_allclose(np.asarray(du), np.asarray(pu), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(pv), rtol=1e-6)
    assert ingest.control_updater(None, {}) is None
    hash(ctrl)  # a static argument of the jitted update


# -- the files ----------------------------------------------------------------


def test_the_configuration_states_the_deployment():
    cfg = harness.resolve_cell(CELL).config
    assert (cfg["num_users"], cfg["num_users_published"], cfg["num_items"],
            cfg["num_factors"]) == (2500000, 10000000, 1048576, 512)
    assert cfg["reduced"] == ["num_users"] and cfg["chips"] == 1
    assert cfg["runner_kinds"] == ["ingest"]
    assert cfg["reference"] == "online_ref" and cfg["architecture"] is None
    assert (cfg["minibatch_size"], cfg["collision_mode"]) == (256, "mean")
    assert cfg["learning_rate"] in (0.01, 0.03, 0.1)
    assert "float32" in cfg["precision"]
    assert set(cfg["guarantees"]) >= {"applied_once_in_log_order",
                                      "delivery", "checkpoints"}
    assert set(cfg["assumed"]) >= {
        "micro_batch_records", "minibatch_size", "collision_mode",
        "learning_rate", "data", "starting_tables", "ids", "checkpoints",
        "target_rmse"}
    assert set(cfg["limits"]) == COMPARED
    assert cfg["limits"]["ratings_missing"] == 0
    assert cfg["limits"]["offset_behind_head"] == 0
    assert len(cfg["source"]) <= 200 and "OnlineSpark" in cfg["source"]
    # the tables a chip must hold: 7.27 GB of float32 rows
    assert (2500000 + 1048576) * 512 * 4 == 7267483648
    # the toy shrinks sizes, never the limits
    assert not set(cfg["toy"]) & {"limits", "learning_rate",
                                  "minibatch_size", "collision_mode"}


def test_the_traffic_file_carries_the_table_of_the_issue():
    traffic = harness.resolve_cell(CELL).traffic
    assert traffic["runner"] == "ingest"
    assert traffic["micro_batch_records"] == 65536
    assert isinstance(traffic["batches"], int)
    assert traffic["batches"] % 64 == 0 and traffic["batches"] >= 64
    assert (traffic["queue_capacity"], traffic["queue_policy"]) == (
        16, "block")
    assert (traffic["reference_batches"], traffic["check_rows"]) == (
        32, 4096)
    assert traffic["holdout_share"] == 0.05
    assert traffic["full_at_seconds"] == 48
    assert traffic["traced_batches"] >= traffic["reference_batches"]


def test_the_cell_stands_in_the_lists_it_needs():
    """By membership: whatever else lists the cell later is an entry
    there."""
    manifest = harness.load_manifest()
    mine = {m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]
            if CELL in m.get("workloads", [])}
    assert mine >= {"train_ratings_per_s", "train_step_mfu", *NEW_METRICS}
    assert "time_to_target_s" not in mine
    (entry,) = (w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "ingest-replay"
    assert entry["config"] == "syn10m1m-r512-online"
    (config,) = (c for c in manifest["configs"]
                 if c["name"] == entry["config"])
    assert config["reduced"] == ["num_users"]
    assert config["file"] == "benchmark/configs/syn10m1m-r512-online.json"
    assert config["source"] == harness.resolve_cell(CELL).config["source"]
    cell = harness.resolve_cell(CELL)
    assert {m["name"] for m in cell.end_to_end} == {"train_ratings_per_s",
                                                    "setup_s"}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_lists_this_cell(name):
    by_name = {m["name"]: m for m in harness.load_manifest()["per_layer"]}
    entry = by_name[name]
    assert CELL in entry["workloads"]
    assert entry["moves"] == "train_ratings_per_s"
    spec = harness.load_json(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".json"))
    assert "programs" not in spec["reader"]
    assert {k: v for k, v in spec.items() if k != "reader"} == {
        k: v for k, v in entry.items() if k != "workloads"}


# -- the readers, on hand-made events -----------------------------------------


def ms(x):
    return int(round(x * 1e6))  # milliseconds -> the trace's nanoseconds


def span(name, a, b):
    return (name, ms(a), ms(b) - ms(a))


# three micro-batches: the host prepares for 4 ms and dispatches; the
# device takes 10 ms a batch
HOST = [("bench/window", 0, ms(100)), span("fit/ingest", 1, 90)]
MODULES = []
for k, t0 in enumerate((5, 20, 35)):
    HOST += [span("fit/online/source", t0, t0 + 1),
             span("fit/online/prepare", t0 + 1, t0 + 5),
             span("fit/online/update", t0 + 5, t0 + 6),
             span("fit/online/stamp", t0 + 6, t0 + 6.5)]
    MODULES.append((f"jit_online_train({k})", ms(t0 + 6), ms(10)))
DEVICE = {"modules": MODULES,
          "ops": [("%fusion = f32[8] fusion()", s, d)
                  for _, s, d in MODULES]}
SIZES = {"rank": 512, "micro_batch_records": 65536, "num_users": 2500000,
         "num_items": 1048576}


def ctx_of(host, device, peaks=True, series=None):
    reduced = tr.reduce_trace({"devices": {0: device}, "host": host})
    return {"trace": reduced, "sizes": SIZES, "chips": 1, "window_s": 0.1,
            "series": series if series is not None else {
                "online_prepare_s": [0.004, 0.004, 0.005],
                "online_source_wait_s": [0.001, 0.001, 0.001, 0.0]},
            "counters": {"sweeps_done": 1, "batches": 3,
                         "ratings_applied": 3 * 65536},
            "peaks": load_peaks("TPU v5 lite") if peaks else None,
            "sweep_flops": 6 * 512 * 3 * 65536}


def values(ctx):
    return {k: v["value"] for k, v in harness.layer_metrics(
        harness.resolve_cell(CELL), ctx).items()}


def test_the_readers_on_hand_made_events():
    got = values(ctx_of(HOST, DEVICE))
    assert got["online_update_device_ms"] == pytest.approx(10.0)
    # 65,536 ratings x 4 rows x 2 KB = 537 MB, 0.6555 ms at 819 GB/s
    assert roof.update_min_bytes(65536, 512) == 536870912
    assert got["online_update_hbm_roofline"] == pytest.approx(
        100.0 * (536870912 / 819e9) / 0.010)
    assert 6.5 < got["online_update_hbm_roofline"] < 6.6
    assert got["online_prepare_ms_p50"] == pytest.approx(4.0)
    assert got["online_source_wait_ms_p50"] == pytest.approx(1.0)
    assert got["train_step_mfu"] == pytest.approx(
        100.0 * 6 * 512 * 3 * 65536 / (0.1 * 197e12))
    assert set(got) == {"train_step_mfu", *NEW_METRICS}


def test_a_program_without_the_update_or_the_seams_reports_none():
    """The parent under this PR's benchmark files, a run off the chip or
    without ``--trace 1``: nothing is read and nothing raises."""
    other = dict(DEVICE, modules=[("jit_something_else(1)", ms(5), ms(10))])
    got = values(ctx_of(HOST, other, series={}))
    assert not set(NEW_METRICS) & set(got)
    ctx = ctx_of(HOST, DEVICE, series={})
    ctx["trace"] = None
    assert set(values(ctx)) == {"train_step_mfu"}
    got = values(ctx_of(HOST, DEVICE, peaks=False))
    assert "online_update_hbm_roofline" not in got
    assert "train_step_mfu" not in got
    assert got["online_update_device_ms"] == pytest.approx(10.0)


def test_the_update_is_one_jitted_function_under_one_name():
    from large_scale_recommendation_tpu.ops import sgd

    (name,) = roof.PROGRAMS
    for fn in (sgd.online_train, sgd.online_train_inplace):
        assert fn.__name__ == name and hasattr(fn, "lower")
        assert tr.program_name(f"jit_{fn.__name__}(123)") == name


def test_the_seams_the_runner_reads_are_the_programs():
    from large_scale_recommendation_tpu.obs.trace import SEAMS

    assert set(ingest.SEAM_SERIES) <= SEAMS
    series = set(ingest.SEAM_SERIES.values())
    for name in ("online_prepare_ms_p50", "online_source_wait_ms_p50"):
        spec = harness.load_json(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".json"))
        assert spec["reader"]["series"] in series


# -- the tool -----------------------------------------------------------------


def test_limits_ingest_reads_program_control_and_fault_off_the_chip(capsys):
    from benchmark.tools import limits_ingest

    assert limits_ingest.main([
        "--workload", CELL, "--seeds", "7", "--off-chip",
        "--what", "program,control,fault"]) == 0
    lines = dict(line.split(" ", 1)
                 for line in capsys.readouterr().out.splitlines()
                 if line.split(" ", 1)[0] in (
                     "program", "control_bf16", "fault_half_batch"))
    got = {k: json.loads(v) for k, v in lines.items()}
    assert got["program"]["correct"] is True
    assert got["control_bf16"]["correct"] is False
    assert got["fault_half_batch"]["correct"] is False
    assert got["program"]["batches"] == 64
    assert (got["fault_half_batch"]["table_diff"]
            > 100 * got["control_bf16"]["compared"]["table_diff"]["value"])
