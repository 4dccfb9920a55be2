"""The BPR cell (``mpd66m-bpr-r128.fit-rank``): its configuration at the
Million Playlist shape, its rehearsal at the toy size on the CPU under the
real limits (counts and ``correct`` only, never a time), the bf16 control
and the fault it is read against, its reference's rank, and the new
per-layer metric on hand-made events."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import bench_testlib
import test_seam_metrics as seams
from benchmark import compare, harness
from benchmark.reference import bpr_ref
from benchmark.runners import fit_rank
from benchmark.runners.solvers import bpr as bpr_solver

ROOT = bench_testlib.ROOT
CELL = "mpd66m-bpr-r128.fit-rank"
FIT = "netflix100m-r128.fit"
LISTS = ("time_to_target_s", "train_ratings_per_s", "sweeps_to_target",
         "blocking_s", "sweep_device_ms", "sweep_hbm_roofline",
         "sweep_gather_ms", "sweep_scatter_ms", "train_step_mfu")


@pytest.fixture(scope="module")
def toy():
    return bench_testlib.run_toy(CELL)


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
    assert len(out["notes"]["expected_percentile_rank"]) == 4
    counters = out["ctx"]["counters"]
    assert counters["sweeps_done"] == 4
    # the warm-up fit is one sweep: a negative a training entry
    assert counters["dsgd_negatives_total"] == out["ctx"]["sizes"][
        "nnz_train"]
    assert out["ctx"]["sweep_flops"] == bpr_solver.sweep_flops(
        out["ctx"]["sizes"])
    assert line["notes"]["reference"] == "bpr_ref"
    cell = harness.resolve_cell(CELL)
    assert harness.reference_for(cell, None).__file__ == os.path.join(
        ROOT, "benchmark", "reference", "bpr_ref.py")


def test_control_bf16_is_not_correct_and_keeps_every_key(toy):
    line, _ = toy
    control, _ = bench_testlib.run_toy(CELL, control="bf16")
    assert set(control) == set(line)
    assert set(control["compared"]) == set(line["compared"])
    assert control["correct"] is False
    assert (control["compared"]["table_diff"]["value"]
            > 100 * line["compared"]["table_diff"]["value"])


def test_the_fault_without_the_negatives_push_fails_by_all_four():
    cfg = bench_testlib.toy_cell(CELL).config
    (u, i, r), hold = fit_rank.planted_interactions(5, cfg)

    def ranked(fit):
        return [bpr_ref.expected_percentile_rank(U, V, *fit["seen"], *hold)
                for U, V in fit["sweeps"]]

    ref = bpr_ref.fit(u, i, r, cfg, 2)
    fault = bpr_ref.fit(u, i, r, cfg, 2, fault="no_negative_step")
    numbers = compare.fit_numbers(fault["sweeps"], ranked(fault), ref,
                                  ranked(ref))
    correct, compared = compare.judge(numbers, cfg["limits"])
    assert not correct
    assert all(c["value"] > c["limit"] for c in compared.values())
    with pytest.raises(ValueError, match="no fault"):
        bpr_ref.fit(u, i, r, cfg, 1, fault="half_batch")


def test_a_program_without_the_loss_ends_when_the_solver_loads(monkeypatch):
    """The parent of the PR that brought BPR has no ``DSGDConfig.loss``:
    the run ends as the runner loads the solver file, before any data."""
    from large_scale_recommendation_tpu.models.dsgd import DSGDConfig

    fields = {k: v for k, v in DSGDConfig.__dataclass_fields__.items()
              if k != "loss"}
    monkeypatch.setattr(DSGDConfig, "__dataclass_fields__", fields)
    with pytest.raises(SystemExit, match="loss"):
        bpr_solver._require_the_loss()


def test_the_solver_files_counts():
    sizes = {"nnz_train": 63029107, "rank": 128, "num_blocks": 16}
    assert bpr_solver.sweep_flops(sizes) == 63029107 * 10 * 128
    assert bpr_solver.sizes({"num_blocks": 16}) == {"num_blocks": 16}
    assert bpr_solver.CONTROLS == {"bf16": {"factor_dtype": "bfloat16"}}
    snapshot = [{"name": "dsgd_negatives_total", "labels": {},
                 "value": 95.0},
                {"name": "train_segment_s", "labels": {}, "value": 1.0}]
    assert bpr_solver.counters(snapshot) == {"dsgd_negatives_total": 95.0}
    assert bpr_solver.counters(snapshot[1:]) == {}


# -- the configuration and the manifest ---------------------------------------


def test_the_configuration_is_the_published_shape():
    cfg = harness.resolve_cell(CELL).config
    assert (cfg["num_users"], cfg["num_items"], cfg["nnz"],
            cfg["num_factors"]) == (1000000, 2262292, 66346428, 128)
    assert cfg["reduced"] == [] and cfg["solver"] == "bpr"
    assert cfg["reference"] == "bpr_ref" and cfg["chips"] == 1
    assert cfg["runner_kinds"] == ["fit_rank"]
    assert cfg["count_cap"] == 1 and cfg["user_floor"] == 5
    assert cfg["factor_dtype"] == "float32"
    assert "rank_subset" in cfg["assumed"]
    assert 0 < cfg["target_rank"] < 0.25
    # the planted playlists' length is a departure, stated beside the source
    assert "250" in cfg["departs_from_source"]
    assert "departs_from_source" in cfg["assumed"]["data"]


def test_the_cell_and_its_metric_stand_in_the_manifest():
    manifest = harness.load_manifest()
    (entry,) = (w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "fit-rank"
    assert entry["config"] == "mpd66m-bpr-r128"
    lists = {m["name"]: m.get("workloads", [])
             for m in manifest["end_to_end"] + manifest["per_layer"]}
    for name in LISTS:
        assert CELL in lists[name], name
    last = manifest["per_layer"][-1]
    assert last["name"] == "sweep_negatives_ms"
    assert last["workloads"] == [CELL]
    assert last["moves"] == "train_ratings_per_s"
    spec = harness.load_json(os.path.join(
        ROOT, "benchmark", "layer_metrics", "sweep_negatives_ms.json"))
    assert spec["reader"] == {"kind": "python",
                              "file": "sweep_negatives_ms.py"}
    from benchmark.layer_metrics import sweep_negatives_ms

    assert sweep_negatives_ms.SPEC["scopes"] == ["sgd/negatives"]
    assert sweep_negatives_ms.SPEC["programs"] == ["dsgd_train"]
    assert sweep_negatives_ms.read({"trace": None}) is None


# -- the new metric, on hand-made events --------------------------------------


def test_sweep_negatives_ms_reads_its_scope():
    """Two runs of ``dsgd_train``, each with 3 + 1 ms under the scope."""
    ctx = seams.scoped("dsgd_train", [], [
        ("rng.1", 0, 3, "sgd/negatives"),
        ("scatter.2", 3, 4, "sgd/negatives"),
        ("gather.3", 4, 10, "sgd/gather"),
        ("scatter.4", 10, 30, "sgd/scatter_v")])
    got = seams.values(CELL, ctx)
    assert got["sweep_negatives_ms"] == pytest.approx(4.0)
    assert got["sweep_gather_ms"] == pytest.approx(6.0)
    # the squared loss's trace has no such scope: the metric is left out
    got = seams.values(CELL, seams.scoped("dsgd_train", [], seams.SGD_OPS))
    assert "sweep_negatives_ms" not in got
    assert got["sweep_scatter_ms"] == pytest.approx(33.0)


# -- the reference's rank -----------------------------------------------------


def test_the_rank_counts_the_ranked_users_and_the_seen_items():
    # users 0 and 100 are ranked, 1 is not; item 3 was never seen
    U = jnp.zeros((101, 1)).at[0, 0].set(1.0).at[100, 0].set(1.0)
    V = jnp.asarray([[3.0], [2.0], [1.0], [9.0], [0.0]])
    seen_u = jnp.ones(101, bool)
    seen_i = jnp.asarray([True, True, True, False, True])

    def rank(users, items, counts):
        return bpr_ref.expected_percentile_rank(
            U, V, seen_u, seen_i, jnp.asarray(users, jnp.int32),
            jnp.asarray(items, jnp.int32), jnp.asarray(counts, jnp.float32))

    # the unseen item 3 scores highest and counts for nothing
    assert rank([0], [0], [1.0]) == 0.0
    assert rank([0], [4], [1.0]) == 1.0
    assert rank([100], [1], [1.0]) == pytest.approx(1 / 3)
    # user 1 is not ranked; a pair whose item is unseen is no prediction
    assert rank([0, 1, 0], [0, 4, 3], [1.0, 1.0, 1.0]) == 0.0
    assert np.isnan(rank([1], [0], [1.0]))


def test_a_random_model_ranks_at_chance():
    rng = np.random.default_rng(0)
    U = jnp.asarray(rng.normal(size=(30000, 8)).astype(np.float32))
    V = jnp.asarray(rng.normal(size=(2000, 8)).astype(np.float32))
    n = 40000
    got = bpr_ref.expected_percentile_rank(
        U, V, jnp.ones(30000, bool), jnp.ones(2000, bool),
        jnp.asarray(rng.integers(0, 30000, n), jnp.int32),
        jnp.asarray(rng.integers(0, 2000, n), jnp.int32),
        jnp.ones(n, jnp.float32))
    assert got == pytest.approx(0.5, abs=0.02)


# -- the tool -----------------------------------------------------------------


def test_readings_bpr_reads_program_control_and_fault_off_the_chip(capsys):
    from benchmark.tools import readings_bpr

    assert readings_bpr.main([
        "--workload", CELL, "--seeds", "7", "--off-chip", "--sweeps", "2",
        "--what", "shape,program,control,fault,choose", "--lr", "0.1"]) == 0
    got = {}
    for line in capsys.readouterr().out.splitlines():
        kind, _, rest = line.partition(" ")
        if kind in ("shape", "program", "control_bf16",
                    "fault_no_negative_step", "choose"):
            got[kind] = json.loads(rest)
    assert got["shape"]["train"] == 95000
    assert got["program"]["correct"] is True
    assert got["control_bf16"]["correct"] is False
    assert got["fault_no_negative_step"]["correct"] is False
    assert got["choose"]["lr"] == 0.1 and len(got["choose"]["rank"]) == 2
