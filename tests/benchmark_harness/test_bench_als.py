"""The ALS cell (``netflix100m-als-r128.fit``) rehearsed on the CPU at the
configuration's own toy size, held to its real limits: counts and
``correct`` only, never a time. And the unit cases of the files it brought:
its operation and byte counts, and its three per-layer readers on hand-made
device events put through the real reduction (a CPU capture holds no device
plane to record)."""

import importlib
import os

import pytest

import bench_testlib
from bench_testlib import run_toy
from benchmark import harness
from benchmark import trace_reduce as tr
from benchmark.layer_metrics import als_sweep_roofline as roof
from benchmark.peaks import load_peaks
from benchmark.runners.solvers import als as als_solver

ROOT = bench_testlib.ROOT
CELL = "netflix100m-als-r128.fit"
NEW_METRICS = ("als_plan_s", "als_sweep_device_ms", "als_sweep_roofline")
# the cell's sizes: 95% of 100,480,507 ratings train
SIZES = {"nnz_train": 95456482, "num_users": 480189, "num_items": 17770,
         "rank": 128}


@pytest.fixture(scope="module")
def toy():
    return run_toy(CELL)


def test_rehearsal_is_correct_under_the_real_limits_and_counts(toy):
    line, out = toy
    real = harness.resolve_cell(CELL).config
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"time_to_target_s",
                                    "train_ratings_per_s", "setup_s"}
    assert {k: c["limit"] for k, c in line["compared"].items()} == (
        real["limits"])
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())
    assert out["notes"]["sweeps"] == 4 and out["compiles_in_window"] == 0
    # the holdout RMSE falls at every sweep, as the target's choice assumes
    rmse = out["notes"]["holdout_rmse"]
    assert all(b < a for a, b in zip(rmse, rmse[1:]))
    assert out["ctx"]["counters"] == {"sweeps_to_target": 1,
                                      "sweeps_done": 4}
    assert out["ctx"]["sweep_flops"] == als_solver.sweep_flops(
        out["ctx"]["sizes"])


def test_the_reference_is_als_ref_and_says_so_on_the_line(toy):
    line, _ = toy
    cell = harness.resolve_cell(CELL)
    assert harness.reference_for(cell, "dsgd_ref").__file__ == os.path.join(
        ROOT, "benchmark", "reference", "als_ref.py")
    assert line["notes"]["reference"] == "als_ref"


def test_control_bf16_keeps_every_key_of_the_line(toy):
    line, _ = toy
    control, _ = run_toy(CELL, control="bf16")
    assert set(control) == set(line)
    assert set(control["compared"]) == set(line["compared"])
    assert set(control["metrics"]) == set(line["metrics"])
    assert set(control["notes"]) == set(line["notes"])
    # and the bf16 Gram inputs read far above the program's own gaps
    assert (control["compared"]["table_diff"]["value"]
            > 100 * line["compared"]["table_diff"]["value"])


def test_a_target_out_of_reach_is_a_failed_run(capsys):
    with pytest.raises(SystemExit) as e:
        run_toy(CELL, target_rmse=1e-6)
    assert e.value.code not in (0, None)
    assert "target not reached" in capsys.readouterr().err


def test_the_fault_half_of_every_row_is_not_correct():
    from benchmark import compare, datagen
    from benchmark.reference import als_ref
    from benchmark.reference.dsgd_ref import holdout_rmse

    cfg = bench_testlib.toy_cell(CELL).config
    (u, i, r), hold = datagen.planted_ratings(
        5, num_users=cfg["num_users"], num_items=cfg["num_items"],
        nnz=cfg["nnz"], rank=cfg["planted_rank"], noise=cfg["noise"],
        skew_lam=cfg["skew_lam"])

    def scored(fit):
        return [float(holdout_rmse(U, V, *fit["seen"], *hold))
                for U, V in fit["sweeps"]]

    ref = als_ref.fit(u, i, r, cfg, 2)
    fault = als_ref.fit(u, i, r, cfg, 2, fault="half_batch")
    numbers = compare.fit_numbers(fault["sweeps"], scored(fault), ref,
                                  scored(ref))
    correct, _ = compare.judge(numbers, cfg["limits"])
    assert not correct
    assert numbers["table_diff"] > 10 * cfg["limits"]["table_diff"]
    with pytest.raises(ValueError, match="no fault"):
        als_ref.fit(u, i, r, cfg, 1, fault="nope")


# -- the counts ---------------------------------------------------------------


def test_als_sweep_flops_at_the_cells_size_and_by_hand():
    # 3 ratings, 2 + 1 rows, rank 2: per side 3 x (2*4 + 2*2) = 36; per row
    # 8 // 3 + 2*4 = 10
    assert roof.als_sweep_flops(3, 2, 1, 2) == 2 * 36 + 3 * 10
    args = [SIZES[k] for k in ("nnz_train", "num_users", "num_items",
                               "rank")]
    flops = roof.als_sweep_flops(*args)
    assert flops == als_solver.sweep_flops(SIZES)
    gram = 2 * SIZES["nnz_train"] * (2 * 128 * 128 + 2 * 128)
    assert flops - gram == (480189 + 17770) * (128 ** 3 // 3 + 2 * 128 ** 2)
    assert 6.6e12 < flops < 6.7e12
    assert als_solver.sizes({}) == {}
    assert als_solver.CONTROLS == {"bf16": {"gram_dtype": "bf16"}}


def test_als_sweep_min_bytes_and_which_bound_binds():
    assert roof.als_sweep_min_bytes(3, 2, 1, 2) == 2 * 36 + 2 * 3 * 2 * 4
    args = [SIZES[k] for k in ("nnz_train", "num_users", "num_items",
                               "rank")]
    peaks = load_peaks("TPU v5 lite")
    by_flops = roof.als_sweep_flops(*args) / peaks["bf16_flops_per_s"]
    by_bytes = roof.als_sweep_min_bytes(*args) / peaks["hbm_bytes_per_s"]
    assert roof.floor_s(SIZES, peaks) == by_flops  # the arithmetic binds
    assert 0.033 < by_flops < 0.035 and 0.003 < by_bytes < 0.004


# -- the readers, on hand-made events -----------------------------------------


def ms(x):
    return int(round(x * 1e6))  # milliseconds -> the trace's nanoseconds


def span(name, a, b):
    return (name, ms(a), ms(b) - ms(a))


# a fit of 2 one-sweep segments: the plan's programs, then per sweep two
# solve programs of 100 ms and 60 ms; the chip is idle 20 ms after each sweep
ALS_HOST = [
    ("bench/window", 0, ms(1000)),
    span("fit/fit_device", 10, 900),
    span("fit/als/plan", 11, 400),
    span("fit/als/init", 400, 410),
    span("fit/als/segment", 410, 412),
    span("fit/als/after_segment", 412, 600),
    span("fit/als/segment", 600, 602),
    span("fit/als/after_segment", 602, 790),
]
ALS_MODULES = [("jit__device_plan_keys(1)", ms(20), ms(300)),
               ("jit__device_bucket(2)", ms(330), ms(60)),
               ("jit__solve_bucket(3)", ms(420), ms(100)),
               ("jit__solve_bucket(4)", ms(520), ms(60)),
               ("jit__solve_bucket(3)", ms(610), ms(100)),
               ("jit__solve_bucket(4)", ms(710), ms(60))]
ALS_DEVICE = {"modules": ALS_MODULES,
              "ops": [("%fusion = f32[8] fusion()", s, d)
                      for _, s, d in ALS_MODULES]}


def ctx_of(host, device, peaks=True):
    reduced = tr.reduce_trace({"devices": {0: device}, "host": host})
    return {"trace": reduced, "series": {}, "sizes": SIZES, "chips": 1,
            "counters": {"sweeps_done": 2, "sweeps_to_target": 2},
            "peaks": load_peaks("TPU v5 lite") if peaks else None,
            "window_s": 1.0, "sweep_flops": als_solver.sweep_flops(SIZES)}


def values(ctx):
    return {k: v["value"] for k, v in harness.layer_metrics(
        harness.resolve_cell(CELL), ctx).items()}


def test_the_three_readers_on_hand_made_events():
    got = values(ctx_of(ALS_HOST, ALS_DEVICE))
    # the call of fit_device (10 ms) to the first solve program (420 ms)
    assert got["als_plan_s"] == pytest.approx(0.410)
    assert got["als_sweep_device_ms"] == pytest.approx(160.0)
    floor = roof.floor_s(SIZES, load_peaks("TPU v5 lite"))
    assert got["als_sweep_roofline"] == pytest.approx(
        100.0 * 2 * floor / 0.320)
    assert 0 < got["als_sweep_roofline"] < 100
    assert got["sweeps_to_target"] == 2
    assert got["train_step_mfu"] == pytest.approx(
        100.0 * 2 * als_solver.sweep_flops(SIZES) / 197e12)
    # DSGD's metrics read DSGD's programs: the cell does not report them
    assert not {"blocking_s", "sweep_device_ms", "sweep_hbm_roofline",
                "blocking_bucket_s", "blocking_layout_s"} & set(got)
    # the seams name the idle time: the host inside after_segment with the
    # chip still busy is not idle, the gap after each sweep is
    gaps = dict(ctx_of(ALS_HOST, ALS_DEVICE)["trace"]["idle_gaps"])
    assert gaps["fit/als/after_segment"] == pytest.approx(0.056)
    assert gaps["fit/als/plan"] == pytest.approx(0.029)


def test_a_program_without_the_solve_program_or_the_seam_reports_none():
    """The parent commit under this PR's benchmark files, and a run off the
    chip or without ``--trace 1``: nothing is read and nothing raises."""
    other = dict(ALS_DEVICE, modules=[
        (n.replace("_solve_bucket", "dsgd_train"), s, d)
        for n, s, d in ALS_MODULES])
    got = values(ctx_of(ALS_HOST, other))
    assert not set(NEW_METRICS) & set(got)
    no_seam = [h for h in ALS_HOST if h[0] != "fit/als/segment"]
    got = values(ctx_of(no_seam, ALS_DEVICE))
    assert "als_sweep_device_ms" not in got and "als_plan_s" in got
    got = values(ctx_of(ALS_HOST, ALS_DEVICE, peaks=False))
    assert "als_sweep_roofline" not in got and "train_step_mfu" not in got
    ctx = ctx_of(ALS_HOST, ALS_DEVICE)
    ctx["trace"] = None
    assert not set(NEW_METRICS) & set(values(ctx))


@pytest.mark.parametrize("name", roof.PROGRAMS)
def test_program_named_by_the_readers_is_a_jitted_function(name):
    from large_scale_recommendation_tpu.ops import als as als_ops

    fn = getattr(als_ops, name)
    assert fn.__name__ == name  # what the trace calls it, less "jit_"
    assert hasattr(fn, "lower"), f"{name} is not jitted"
    assert tr.program_name(f"jit_{fn.__name__}(123)") == name


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_is_reported_by_the_als_cell_alone(name):
    by_name = {m["name"]: m for m in harness.load_manifest()["per_layer"]}
    assert by_name[name]["workloads"] == [CELL]
    module = importlib.import_module("benchmark.layer_metrics." + name)
    assert callable(module.read)
    spec = harness.load_json(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".json"))
    assert spec["reader"] == {"kind": "python", "file": name + ".py"}


def test_the_cell_joins_the_lists_the_issue_names_and_no_other():
    manifest = harness.load_manifest()
    mine = {m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]
            if CELL in m.get("workloads", [])}
    assert mine == {"time_to_target_s", "train_ratings_per_s",
                    "sweeps_to_target", "train_step_mfu", *NEW_METRICS}
    cfg = harness.resolve_cell(CELL).config
    dsgd = harness.resolve_cell("netflix100m-r128.fit").config
    assert cfg["reduced"] == [] and cfg["gram_dtype"] is None
    for key in ("num_users", "num_items", "nnz", "num_factors",
                "planted_rank", "noise", "skew_lam"):
        assert cfg[key] == dsgd[key], key
