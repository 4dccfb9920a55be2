"""The implicit-feedback cell (``msd34m-ials-r128.fit-rank``) rehearsed on
the CPU at the configuration's own toy size, held to its real limits:
counts and ``correct`` only, never a time. And the unit cases of the files
it brought: the data maker, the reference's rank, the solver file's counts
and its two per-layer readers, on hand-made device events put through the
real reduction (a CPU capture holds no device plane to record)."""

import importlib
import json
import os

import numpy as np
import pytest

import bench_testlib
from bench_testlib import run_toy
from benchmark import harness
from benchmark import trace_reduce as tr
from benchmark.layer_metrics import als_sweep_roofline as roof
from benchmark.peaks import load_peaks
from benchmark.reference import ials_ref
from benchmark.runners import fit_rank
from benchmark.runners.solvers import ials as ials_solver

ROOT = bench_testlib.ROOT
CELL = "msd34m-ials-r128.fit-rank"
ALS_CELL = "netflix100m-als-r128.fit"
NEW_METRICS = ("als_shared_gram_ms", "als_plan_pad_ratio")
# the cell's sizes: 95% of 33,600,000 interactions train
SIZES = {"nnz_train": 31920000, "num_users": 571355, "num_items": 41140,
         "rank": 128}


@pytest.fixture(scope="module")
def toy():
    return run_toy(CELL)


def test_rehearsal_is_correct_under_the_real_limits_and_counts(toy):
    line, out = toy
    real = harness.resolve_cell(CELL).config
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) >= {"time_to_target_s",
                                    "train_ratings_per_s", "setup_s"}
    assert {k: c["limit"] for k, c in line["compared"].items()} == (
        real["limits"])
    assert set(line["compared"]) == {"loss_gap", "first_update_gap",
                                     "update_gap", "table_diff"}
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())
    assert out["notes"]["sweeps"] == 4 and out["compiles_in_window"] == 0
    # it ranks: far under chance at every sweep, and better after two
    rank = out["notes"]["expected_percentile_rank"]
    assert len(rank) == 4 and max(rank) < 0.35 and rank[1] < rank[0]
    counters = out["ctx"]["counters"]
    assert counters["sweeps_done"] == 4 and counters["sweeps_to_target"] == 1
    # the program's own count of its plan: every entry has a slot, and the
    # power-of-two classes at most double them (min_pad 8 adds no more here)
    assert 1.0 <= counters["als_plan_pad_ratio"] < 2.5
    assert out["ctx"]["sweep_flops"] == ials_solver.sweep_flops(
        out["ctx"]["sizes"])


def test_the_reference_is_ials_ref_and_says_so_on_the_line(toy):
    line, _ = toy
    cell = harness.resolve_cell(CELL)
    assert harness.reference_for(cell, None).__file__ == os.path.join(
        ROOT, "benchmark", "reference", "ials_ref.py")
    assert line["notes"]["reference"] == "ials_ref"
    assert fit_rank.REFERENCE == "ials_ref"


def test_the_programs_evaluator_ranks_the_target_sweep_as_the_reference(
        toy, monkeypatch):
    """``obs.PercentileRankEvaluator`` on the tables of the sweep that met
    the target reads what ``ials_ref.expected_percentile_rank`` reads (both
    rank the whole catalog at ``highest``, a tie half a place); a program
    without the evaluator leaves the two keys empty and nothing raises."""
    _, out = toy
    notes = out["notes"]
    hit = out["ctx"]["counters"]["sweeps_to_target"]
    assert notes["program_rank"] == pytest.approx(
        notes["expected_percentile_rank"][hit - 1], abs=1e-6)
    assert 0 <= notes["program_rank_gap"] < 1e-6
    from large_scale_recommendation_tpu import obs

    monkeypatch.delattr(obs, "PercentileRankEvaluator")
    assert fit_rank.program_rank(None, None, None, None) is None


def test_control_bf16_keeps_every_key_of_the_line(toy):
    line, _ = toy
    control, _ = run_toy(CELL, control="bf16")
    assert set(control) == set(line)
    assert set(control["compared"]) == set(line["compared"])
    assert set(control["metrics"]) == set(line["metrics"])
    assert set(control["notes"]) == set(line["notes"])
    # and the bf16 Gram inputs are not correct under the real limits: they
    # read far above the program's own gaps
    assert control["correct"] is False
    assert (control["compared"]["table_diff"]["value"]
            > 100 * line["compared"]["table_diff"]["value"])


def test_a_target_out_of_reach_is_a_failed_run(capsys):
    with pytest.raises(SystemExit) as e:
        run_toy(CELL, target_rank=1e-6)
    assert e.value.code not in (0, None)
    assert "target not reached" in capsys.readouterr().err


def test_the_fault_half_of_every_row_is_not_correct():
    from benchmark import compare

    cfg = bench_testlib.toy_cell(CELL).config
    (u, i, r), hold = fit_rank.planted_interactions(5, cfg)

    def ranked(fit):
        return [ials_ref.expected_percentile_rank(U, V, *fit["seen"], *hold)
                for U, V in fit["sweeps"]]

    ref = ials_ref.fit(u, i, r, cfg, 2)
    fault = ials_ref.fit(u, i, r, cfg, 2, fault="half_batch")
    numbers = compare.fit_numbers(fault["sweeps"], ranked(fault), ref,
                                  ranked(ref))
    correct, _ = compare.judge(numbers, cfg["limits"])
    assert not correct
    assert numbers["table_diff"] > 10 * cfg["limits"]["table_diff"]
    with pytest.raises(ValueError, match="no fault"):
        ials_ref.fit(u, i, r, cfg, 1, fault="nope")


def test_too_few_unique_pairs_ends_the_run_naming_the_key():
    cfg = dict(bench_testlib.toy_cell(CELL).config, oversample=1.0)
    with pytest.raises(SystemExit, match="oversample"):
        fit_rank.planted_interactions(5, cfg)


# -- the data -----------------------------------------------------------------


@pytest.fixture(scope="module")
def data():
    cfg = bench_testlib.toy_cell(CELL).config
    return cfg, fit_rank.planted_interactions(2**31 + 12345, cfg)


def test_interactions_are_unique_pairs_split_95_to_5(data):
    cfg, ((u, i, r), (hu, hi, hr)) = data
    assert u.shape[0] == 95000 and hu.shape[0] == 5000
    au, ai = np.concatenate([u, hu]), np.concatenate([i, hi])
    assert au.min() >= 0 and au.max() < cfg["num_users"]
    assert ai.min() >= 0 and ai.max() < cfg["num_items"]
    pairs = au.astype(np.int64) * cfg["num_items"] + ai
    assert np.unique(pairs).size == pairs.size
    assert all(a.dtype == np.int32 for a in (u, i, hu, hi))
    assert r.dtype == np.float32 and hr.dtype == np.float32


def test_rows_are_power_law_and_counts_mostly_one(data):
    cfg, ((u, i, r), _) = data
    per_user = np.bincount(np.asarray(u), minlength=cfg["num_users"])
    per_item = np.bincount(np.asarray(i), minlength=cfg["num_items"])
    # every user keeps most of its floor of draws; a few hold many times
    # the median, and so do a few items (low ids are the popular ones)
    assert per_user.min() >= cfg["user_floor"] // 2
    assert per_user.max() > 4 * np.median(per_user)
    assert per_item.max() > 4 * np.median(per_item)
    assert per_item[:40].mean() > 3 * per_item[-40:].mean()
    r = np.asarray(r)
    assert r.min() == 1.0 and r.max() <= cfg["count_cap"]
    assert 0.4 < (r == 1.0).mean() < 0.8 and r.mean() > 1.5
    assert (r == np.floor(r)).all()


def test_the_same_seed_gives_the_same_data_and_another_other(data):
    cfg, ((u, i, r), _) = data
    (u2, i2, r2), _ = fit_rank.planted_interactions(2**31 + 12345, cfg)
    for a, b in ((u, u2), (i, i2), (r, r2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    (u3, _, _), _ = fit_rank.planted_interactions(12345, cfg)
    assert not np.array_equal(np.asarray(u), np.asarray(u3))


def test_a_user_plays_mostly_its_own_two_genres(data):
    cfg, ((u, i, _), _) = data
    u, genre = np.asarray(u), np.asarray(i) % cfg["genres"]
    top_two = []
    for user in range(200):
        mine = np.bincount(genre[u == user], minlength=cfg["genres"])
        top_two.append(np.sort(mine)[-2:].sum() / max(mine.sum(), 1))
    # 4 genres at the toy size: two of them at random would hold a half
    assert np.mean(top_two) > 0.8


# -- the rank -----------------------------------------------------------------


def test_expected_percentile_rank_by_hand():
    import jax.numpy as jnp

    # one user, scores 3 > 2 > 1 > 0 over four items
    U = jnp.asarray([[1.0], [0.0]])
    V = jnp.asarray([[3.0], [2.0], [1.0], [0.0]])
    all_u, all_i = jnp.ones(2, bool), jnp.ones(4, bool)

    def rank(items, counts, users=None):
        users = [0] * len(items) if users is None else users
        return ials_ref.expected_percentile_rank(
            U, V, all_u, all_i, jnp.asarray(users, jnp.int32),
            jnp.asarray(items, jnp.int32), jnp.asarray(counts, jnp.float32))

    assert rank([0], [1.0]) == 0.0 and rank([3], [1.0]) == 1.0
    assert rank([1], [7.0]) == pytest.approx(1 / 3)
    # weighted by the held-out count: (1 x 0 + 3 x 1) / 4
    assert rank([0, 3], [1.0, 3.0]) == pytest.approx(0.75)
    # a user whose scores are all equal reads chance, not the top
    assert rank([2], [1.0], users=[1]) == pytest.approx(0.5)
    # a pair whose user or item was never seen is no prediction
    seen_u = jnp.asarray([True, False])
    got = ials_ref.expected_percentile_rank(
        U, V, seen_u, all_i, jnp.asarray([0, 1], jnp.int32),
        jnp.asarray([1, 2], jnp.int32), jnp.asarray([1.0, 1.0]))
    assert got == pytest.approx(1 / 3)


def test_a_random_model_ranks_at_chance():
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    U = jnp.asarray(rng.normal(size=(300, 8)).astype(np.float32))
    V = jnp.asarray(rng.normal(size=(5000, 8)).astype(np.float32))
    n = 3 * ials_ref._PAIRS + 17  # several blocks and a ragged one
    got = ials_ref.expected_percentile_rank(
        U, V, jnp.ones(300, bool), jnp.ones(5000, bool),
        jnp.asarray(rng.integers(0, 300, n), jnp.int32),
        jnp.asarray(rng.integers(0, 5000, n), jnp.int32),
        jnp.asarray(rng.integers(1, 9, n), jnp.float32))
    assert got == pytest.approx(0.5, abs=0.01)


# -- the counts ---------------------------------------------------------------


def test_sweep_flops_is_the_als_count_plus_the_shared_gram():
    als = roof.als_sweep_flops(*(SIZES[k] for k in (
        "nnz_train", "num_users", "num_items", "rank")))
    shared = 2 * (571355 + 41140) * 128 ** 2
    assert ials_solver.sweep_flops(SIZES) == als + shared
    assert shared < 0.01 * als and 2.5e12 < als < 2.6e12
    assert ials_solver.sizes({}) == {}
    assert ials_solver.CONTROLS == {"bf16": {"gram_dtype": "bf16"}}


def test_counters_reads_the_plans_gauge_and_nothing_else():
    snapshot = [
        {"name": "als_plan_pad_ratio", "labels": {"side": "user"},
         "value": 1.5},
        {"name": "als_plan_pad_ratio", "labels": {"side": "item"},
         "value": 1.3},
        {"name": "als_plan_padded_slots", "labels": {"side": "user"},
         "value": 9.0}]
    assert ials_solver.counters(snapshot) == {
        "als_plan_pad_ratio": pytest.approx(1.4)}
    # the parent of the PR that added the gauge publishes none
    assert ials_solver.counters(snapshot[2:]) == {}
    assert ials_solver.counters([]) == {}


def test_the_program_publishes_what_the_solver_file_reads(toy):
    """The gauge by the name ``counters`` looks for, and the implicit
    sweeps' counter, from a fit through the solver file with the registry
    live (what the runner's warm-up does)."""
    from benchmark.spans import Spans
    from benchmark.runners.fit import SegmentStamps
    from large_scale_recommendation_tpu import obs

    cfg = bench_testlib.toy_cell(CELL).config
    (u, i, r), _ = fit_rank.planted_interactions(5, cfg)
    registry, _ = obs.enable()
    try:
        ials_solver.make_fit(cfg, 2, SegmentStamps(Spans()), 1)(u, i, r)
        got = registry.snapshot()["metrics"]
    finally:
        obs.disable()
    ratio = {m["labels"]["side"]: m["value"] for m in got
             if m["name"] == "als_plan_pad_ratio"}
    assert set(ratio) == {"user", "item"}
    _, out = toy
    assert out["notes"]["program_counters"] == {
        "als_plan_pad_ratio": pytest.approx(sum(ratio.values()) / 2)}
    sweeps = [m["value"] for m in got
              if m["name"] == "als_implicit_sweeps_total"]
    assert sweeps == [2.0]


# -- the readers, on hand-made events -----------------------------------------


def ms(x):
    return int(round(x * 1e6))  # milliseconds -> the trace's nanoseconds


def span(name, a, b):
    return (name, ms(a), ms(b) - ms(a))


# a fit of 2 one-sweep segments: per half-step the shared Gram (2 ms) and
# two solve programs
HOST = [
    ("bench/window", 0, ms(1000)),
    span("fit/fit_device", 10, 900),
    span("fit/als/plan", 11, 400),
    span("fit/als/init", 400, 410),
    span("fit/als/segment", 410, 412),
    span("fit/als/after_segment", 412, 600),
    span("fit/als/segment", 600, 602),
    span("fit/als/after_segment", 602, 790),
]
MODULES = [("jit__device_plan_keys(1)", ms(20), ms(300))]
for t0 in (420, 610):
    MODULES += [("jit__full_gram(5)", ms(t0), ms(2)),
                ("jit__solve_bucket(3)", ms(t0 + 2), ms(50)),
                ("jit__solve_bucket(4)", ms(t0 + 52), ms(30)),
                ("jit__full_gram(6)", ms(t0 + 82), ms(3)),
                ("jit__solve_bucket(7)", ms(t0 + 85), ms(60))]
DEVICE = {"modules": MODULES,
          "ops": [("%fusion = f32[8] fusion()", s, d)
                  for _, s, d in MODULES]}


def ctx_of(host, device, counters=None, peaks=True):
    reduced = tr.reduce_trace({"devices": {0: device}, "host": host})
    return {"trace": reduced, "series": {}, "sizes": SIZES, "chips": 1,
            "counters": {"sweeps_done": 2, "sweeps_to_target": 1,
                         **(counters or {})},
            "peaks": load_peaks("TPU v5 lite") if peaks else None,
            "window_s": 1.0,
            "sweep_flops": ials_solver.sweep_flops(SIZES)}


def values(ctx):
    return {k: v["value"] for k, v in harness.layer_metrics(
        harness.resolve_cell(CELL), ctx).items()}


def test_the_two_readers_on_hand_made_events():
    got = values(ctx_of(HOST, DEVICE, {"als_plan_pad_ratio": 1.41}))
    # two shared Gram matrices a sweep: 2 + 3 ms
    assert got["als_shared_gram_ms"] == pytest.approx(5.0)
    assert got["als_plan_pad_ratio"] == pytest.approx(1.41)
    assert got["sweeps_to_target"] == 1
    assert got["train_step_mfu"] == pytest.approx(
        100.0 * 2 * ials_solver.sweep_flops(SIZES) / 197e12)


def test_a_program_without_the_gram_program_or_the_gauge_reports_none():
    """The explicit fit (no shared Gram), the parent commit under this PR's
    benchmark files (no gauge), a run off the chip or without ``--trace
    1``: nothing is read and nothing raises."""
    explicit = dict(DEVICE, modules=[m for m in MODULES
                                     if "_full_gram" not in m[0]])
    got = values(ctx_of(HOST, explicit))
    assert not set(NEW_METRICS) & set(got)
    no_seam = [h for h in HOST if h[0] != "fit/als/segment"]
    got = values(ctx_of(no_seam, DEVICE))
    assert "als_shared_gram_ms" not in got
    ctx = ctx_of(HOST, DEVICE, {"als_plan_pad_ratio": 1.41})
    ctx["trace"] = None
    got = values(ctx)
    assert "als_shared_gram_ms" not in got  # a device time needs the trace
    assert got["als_plan_pad_ratio"] == pytest.approx(1.41)
    assert got["sweeps_to_target"] == 1
    assert got["train_step_mfu"] == pytest.approx(
        100.0 * 2 * ials_solver.sweep_flops(SIZES) / 197e12)


def test_the_shared_gram_is_a_jitted_function_under_its_scope():
    import jax
    import jax.numpy as jnp

    from large_scale_recommendation_tpu.ops import als as als_ops

    module = importlib.import_module(
        "benchmark.layer_metrics.als_shared_gram_ms")
    (name,) = module.SPEC["programs"]
    fn = getattr(als_ops, name)
    assert fn.__name__ == name and hasattr(fn, "lower")
    assert tr.program_name(f"jit_{fn.__name__}(123)") == name
    assert name in roof.PROGRAMS  # als_sweep_device_ms keeps its time
    text = fn.lower(jnp.ones((6, 4))).as_text(debug_info=True)
    assert "als/shared_gram" in text


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_lists_this_cell(name):
    """Membership only: a later cell (``als_plan_pad_ratio`` is published
    by the explicit fit too) joins the list as an entry, not as an edit
    here."""
    by_name = {m["name"]: m for m in harness.load_manifest()["per_layer"]}
    assert CELL in by_name[name]["workloads"]
    spec = harness.load_json(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".json"))
    assert "programs" not in spec["reader"]


def test_the_cell_stands_in_the_lists_it_needs():
    """Four lists of the accepted benchmark and its own two metrics, by
    membership: whatever else lists the cell later is an entry there. (The
    three ``als_*`` metrics of PR 29 would read this cell's trace as they
    stand, but ``test_bench_als.py`` pins their ``workloads`` to the
    explicit cell alone, and no file there may be edited: a ``benchmark``
    PR has to lift that pin first; ``PERF.md``, Open questions.)"""
    manifest = harness.load_manifest()
    mine = {m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]
            if CELL in m.get("workloads", [])}
    assert mine >= {"time_to_target_s", "train_ratings_per_s",
                    "sweeps_to_target", "train_step_mfu", *NEW_METRICS}
    (entry,) = (w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["config"] == "msd34m-ials-r128"
    cfg = harness.resolve_cell(CELL).config
    assert cfg["reduced"] == [] and cfg["gram_dtype"] is None
    assert (cfg["num_users"], cfg["num_items"], cfg["nnz"],
            cfg["num_factors"]) == (571355, 41140, 33600000, 128)
    assert cfg["reg_mode"] == "direct" and cfg["alpha"] > 0
    assert 0 < cfg["target_rank"] < 0.25  # far below chance
    assert set(cfg["assumed"]) >= {"nnz", "data", "filter_floors", "alpha",
                                   "lambda", "init_scale", "target_rank",
                                   "limits"}
    als = harness.resolve_cell(ALS_CELL)
    assert {m["name"] for m in als.per_layer} >= {
        "als_plan_s", "als_sweep_device_ms", "als_sweep_roofline"}


# -- the tool -----------------------------------------------------------------


def test_readings_rank_reads_program_control_and_fault_off_the_chip(capsys):
    from benchmark.tools import readings_rank

    assert readings_rank.main([
        "--workload", CELL, "--seeds", "7", "--off-chip", "--sweeps", "2",
        "--what", "shape,program,control,fault"]) == 0
    lines = dict(line.split(" ", 1)
                 for line in capsys.readouterr().out.splitlines()
                 if line.split(" ", 1)[0] in (
                     "shape", "program", "control_bf16", "fault_half_batch"))
    got = {k: json.loads(v) for k, v in lines.items()}
    assert got["shape"]["train"] == 95000
    assert got["program"]["correct"] is True
    assert got["fault_half_batch"]["correct"] is False
    assert got["control_bf16"]["correct"] is False
    assert (got["control_bf16"]["compared"]["table_diff"]["value"]
            > 100 * got["program"]["compared"]["table_diff"]["value"])
