"""The Yahoo! Music ring cell (``yahoo-r1-r100-ring4.fit``, PR 40): its
configuration at the published shape, its reference copy
(``reference/dsgd_big_ref.py``) against ``dsgd_ref``, its rehearsal at the
toy size on four virtual devices, and the two exchange metrics on
hand-made events."""

import numpy as np
import pytest

import jax.numpy as jnp

import bench_testlib
import test_seam_metrics as seams
from benchmark import harness, readers
from benchmark.reference import dsgd_big_ref, dsgd_ref

CELL = "yahoo-r1-r100-ring4.fit"
RING = "netflix100m-r128-ring4.fit"
EXCHANGE = ("blocking_exchange_s", "blocking_exchange_ici_roofline")


def test_configuration_is_the_published_shape():
    cfg = harness.resolve_cell(CELL).config
    assert (cfg["num_users"], cfg["num_items"], cfg["nnz"],
            cfg["num_factors"]) == (1_000_990, 624_961, 262_810_175, 100)
    assert cfg["reduced"] == [] and cfg["chips"] == cfg["num_blocks"] == 4
    assert (cfg["solver"], cfg["reference"]) == ("mesh_dsgd",
                                                 "dsgd_big_ref")
    assert "Dror" in cfg["source"] and "NOMAD" in cfg["source"]
    assert cfg["factor_dtype"] == "float32" and cfg["deployment"]
    assert {"planted_rank", "holdout", "learning_rate", "lambda",
            "target_rmse"} <= set(cfg["assumed"])


@pytest.mark.parametrize("n,nu,ni,k,mb,seed,sort_side", [
    (3001, 120, 90, 2, 32, 1, "item"),
    (5000, 57, 33, 4, 64, 2, None),
    (20000, 300, 200, 4, 128, 3, "user"),
    (7, 5, 4, 4, 8, 0, "item"),
])
def test_reference_copy_lays_out_what_dsgd_ref_lays_out(n, nu, ni, k, mb,
                                                        seed, sort_side):
    rng = np.random.default_rng(seed)

    def ids(m):
        return jnp.asarray(np.minimum(
            (rng.exponential(0.3, n) * m).astype(np.int32), m - 1))

    u, i = ids(nu), ids(ni)
    r = jnp.asarray(rng.normal(0, 1, n).astype(np.float32))
    kw = dict(num_users=nu, num_items=ni, k=k, minibatch=mb,
              solver_seed=seed, sort_side=sort_side)
    want = dsgd_ref.block_layout(u, i, r, **kw)
    got = dsgd_big_ref.block_layout([u, i, r], **kw)
    assert set(got) == set(want)
    for name, a in want.items():
        assert np.asarray(got[name]).tobytes() == np.asarray(a).tobytes(), \
            name


def test_rehearsal_is_correct_and_counts():
    line, out = bench_testlib.run_toy(CELL)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"time_to_target_s",
                                    "train_ratings_per_s", "setup_s"}
    assert out["compiles_in_window"] == 0 and out["notes"]["sweeps"] == 4
    assert line["device"]["count"] >= 4
    # the reference's tables are where the program's are, for the
    # comparison: bit for bit the one-chip order, so the gaps are rounding
    assert all(c["value"] < c["limit"] for c in line["compared"].values())


def test_the_cell_is_listed_where_the_issue_names_it():
    manifest = harness.load_manifest()
    (entry,) = (w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 4 and entry["traffic"] == "fit"
    lists = {m["name"]: m.get("workloads", [])
             for m in manifest["end_to_end"] + manifest["per_layer"]}
    for name in ("time_to_target_s", "train_ratings_per_s",
                 "sweeps_to_target", "blocking_s", "sweep_device_ms",
                 "sweep_hbm_roofline", "train_step_mfu", "sweep_gather_ms",
                 "sweep_scatter_ms"):
        assert CELL in lists[name], name
    for name in EXCHANGE:
        assert lists[name] == [RING, CELL]


def _exchange_ctx(per_chip_ms, program="_bucket_entries",
                  scope="bucket/exchange"):
    """One run of ``program`` on each chip, its exchange taking the given
    milliseconds there."""
    reduced = seams.tr.reduce_trace({
        "devices": {chip: {
            "modules": [(f"jit_{program}(3)", seams.ms(10), seams.ms(100))],
            "ops": [seams.op("sort.1", 10, 20, "bucket/permutation"),
                    seams.op("all-to-all.2", 20, 20 + t, scope)]}
            for chip, t in enumerate(per_chip_ms)},
        "host": [("bench/window", 0, seams.ms(1000))]})
    return {"trace": reduced, "series": {}, "counters": {},
            "sizes": {"nnz_train": 240_000_000, "num_blocks": 4},
            "peaks": {"hbm_bytes_per_s": 819e9}, "chips": 4,
            "window_s": 1.0}


def _read(name, ctx):
    spec = harness.load_json(
        f"{seams.ROOT}/benchmark/layer_metrics/{name}.json")
    return readers.read(spec, ctx)


def test_exchange_time_is_the_busiest_chips():
    ctx = _exchange_ctx([4, 9, 6, 5])
    assert _read("blocking_exchange_s", ctx) == pytest.approx(0.009)
    # 240M / 4 * 3/4 entries leave each chip, 12 B each, at 200 GB/s:
    # 2.7 ms of 9
    assert _read("blocking_exchange_ici_roofline", ctx) == pytest.approx(
        100 * 0.0027 / 0.009)


@pytest.mark.parametrize("name", EXCHANGE)
def test_exchange_metrics_read_nothing_where_there_is_no_exchange(name):
    """No trace; the one-chip blocking, or the ring's before PR 40 (the
    parent commit under this PR's benchmark files): no such scope."""
    assert _read(name, dict(_exchange_ctx([4, 4]), trace=None)) is None
    assert _read(name, _exchange_ctx([4, 4], scope="bucket/sort")) is None
    assert _read(name, _exchange_ctx([4, 4], program="run")) is None
