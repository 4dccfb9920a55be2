"""The eight per-layer metrics that read the program's seams
(``obs.trace.SEAMS``), on hand-made events put through the real reduction
and the real ``harness.layer_metrics`` (so the manifest's wiring is under
test too); and a pin that every program a metric file names is a jitted
function of the program, so that a rename cannot null a metric unnoticed."""

import glob
import json
import os

import pytest

import bench_testlib
from benchmark import harness
from benchmark import trace_reduce as tr

ROOT = bench_testlib.ROOT
BULK = "syn10m1m-r512.serve-bulk"
FIT = "netflix100m-r128.fit"
RING = "netflix100m-r128-ring4.fit"
FLUSH_METRICS = ("flush_form_idle_ms", "flush_prepare_idle_ms",
                 "flush_dispatch_idle_ms", "flush_drain_idle_ms",
                 "flush_results_idle_ms", "flush_unspanned_idle_ms")


def ms(x):
    return int(round(x * 1e6))  # milliseconds -> the trace's nanoseconds


def span(name, a, b):
    return (name, ms(a), ms(b) - ms(a))


# two flushes; the chip is busy 40-90 in the first and 220-250 in the second
SERVING_HOST = [
    ("bench/window", 0, ms(1000)),
    span("serving/flush", 0, 100),
    span("serving/engine/form", 0, 10),            # idle 10
    span("serving/engine/excl", 10, 15),           # 5
    span("serving/engine/gather", 15, 20),         # 5
    span("serving/retrieval/stage1", 20, 30),      # 10
    span("serving/retrieval/stage2", 30, 35),      # 5
    span("serving/pipeline/drain", 35, 92),        # 57 - 50 busy = 7
    span("serving/engine/results", 92, 99),        # 7; 1 ms unspanned
    span("serving/flush", 200, 260),
    span("serving/engine/form", 200, 205),         # 5
    span("serving/engine/excl", 205, 207),         # 2
    span("serving/engine/gather", 207, 210),       # 3
    span("serving/retrieval/stage1", 210, 216),    # 6
    span("serving/retrieval/stage2", 216, 219),    # 3
    span("serving/pipeline/drain", 219, 252),      # 33 - 30 busy = 3
    span("serving/engine/results", 252, 258),      # 6; 2 ms unspanned
]
SERVING_DEVICE = {
    "ops": [("%fusion = f32[8] fusion()", ms(40), ms(50)),
            ("%fusion = f32[8] fusion()", ms(220), ms(30))],
    "modules": [("jit__stage1_flat(1)", ms(40), ms(50)),
                ("jit__stage1_flat(1)", ms(220), ms(30))]}
EXPECTED_MS = {"flush_form_idle_ms": 7.5, "flush_prepare_idle_ms": 7.5,
               "flush_dispatch_idle_ms": 12.0, "flush_drain_idle_ms": 5.0,
               "flush_results_idle_ms": 6.5, "flush_unspanned_idle_ms": 1.5}


def ctx_of(host, device):
    reduced = tr.reduce_trace({"devices": {0: device}, "host": host})
    return {"trace": reduced, "series": {}, "counters": {}, "sizes": {},
            "peaks": None, "chips": 1, "window_s": 1.0}


def values(cell, ctx):
    return {k: v["value"] for k, v in harness.layer_metrics(
        harness.resolve_cell(cell), ctx).items()}


@pytest.mark.parametrize("metric", FLUSH_METRICS)
def test_flush_idle_metric_on_hand_made_events(metric):
    got = values(BULK, ctx_of(SERVING_HOST, SERVING_DEVICE))
    assert got[metric] == pytest.approx(EXPECTED_MS[metric])


def test_the_six_add_up_to_the_mean_host_share_of_a_flush():
    ctx = ctx_of(SERVING_HOST, SERVING_DEVICE)
    got = values(BULK, ctx)
    host = ctx["trace"]["span_host_s"]["serving/flush"]
    assert host == [pytest.approx(0.050), pytest.approx(0.030)]
    assert sum(got[m] for m in FLUSH_METRICS) == pytest.approx(
        sum(host) / len(host) * 1e3)
    # its median stays the accepted metric's, untouched by the seams: the
    # count of serving/flush spans is the benchmark's own
    assert got["flush_host_ms_p50"] == pytest.approx(40.0)
    assert ctx["trace"]["span_runs"]["serving/flush"] == 2


@pytest.mark.parametrize("missing", ["serving/engine/excl",
                                     "serving/pipeline/drain"])
def test_a_missing_seam_leaves_its_metric_and_the_remainder_out(missing):
    host = [h for h in SERVING_HOST if h[0] != missing]
    got = values(BULK, ctx_of(host, SERVING_DEVICE))
    gone = {"serving/engine/excl": "flush_prepare_idle_ms",
            "serving/pipeline/drain": "flush_drain_idle_ms"}[missing]
    assert gone not in got and "flush_unspanned_idle_ms" not in got
    assert got["flush_form_idle_ms"] == pytest.approx(7.5)


def test_a_program_without_seams_reports_none_of_them_and_does_not_raise():
    """The parent commit under this PR's benchmark files."""
    host = [h for h in SERVING_HOST
            if h[0] in ("bench/window", "serving/flush")]
    got = values(BULK, ctx_of(host, SERVING_DEVICE))
    assert not set(FLUSH_METRICS) & set(got)
    assert got["flush_host_ms_p50"] == pytest.approx(40.0)
    ctx = ctx_of(host, SERVING_DEVICE)
    ctx["trace"] = None  # and a run without --trace 1
    assert values(BULK, ctx) == {}


def test_a_measured_zero_stays_zero():
    host = [h if h[0] != "serving/engine/form" else
            ("serving/engine/form", h[1] + ms(45), ms(1))
            for h in SERVING_HOST if h[1] < ms(200)]
    got = values(BULK, ctx_of(host, SERVING_DEVICE))
    assert got["flush_form_idle_ms"] == 0.0


FIT_HOST = [
    ("bench/window", 0, ms(1000)),
    span("fit/fit_device", 0, 1000),
    span("fit/blocking/bucket", 5, 300),
    span("fit/blocking/layout", 300.5, 320),
    span("fit/dsgd/init", 320, 321),
    span("fit/dsgd/segment", 699, 700),
]
FIT_DEVICE = {
    "ops": [("%sort = s32[8] sort()", ms(10), ms(289)),
            ("%scatter = s32[8] scatter()", ms(301), ms(390)),
            ("%fusion = f32[8] fusion()", ms(700), ms(290))],
    "modules": [("jit__bucket_entries(1)", ms(10), ms(289)),
                ("jit__layout(2)", ms(301), ms(390)),
                ("jit_dsgd_train(3)", ms(700), ms(290))]}


@pytest.mark.parametrize("cell,program", [(FIT, "jit_dsgd_train(3)"),
                                          (RING, "jit_run(3)")])
def test_blocking_splits_at_the_read_back(cell, program):
    device = dict(FIT_DEVICE, modules=FIT_DEVICE["modules"][:2]
                  + [(program,) + FIT_DEVICE["modules"][2][1:]])
    got = values(cell, ctx_of(FIT_HOST, device))
    assert got["blocking_bucket_s"] == pytest.approx(0.296)
    assert got["blocking_layout_s"] == pytest.approx(0.3995)
    assert got["blocking_s"] == pytest.approx(0.700)
    # the two add up to blocking_s less what fit_device does before
    # blocking starts (5 ms here), plus the _layout dispatch counted twice
    assert got["blocking_bucket_s"] + got["blocking_layout_s"] == (
        pytest.approx(got["blocking_s"] - 0.005 + 0.0005))


def test_blocking_split_is_left_out_without_the_seams():
    host = [h for h in FIT_HOST if not h[0].startswith("fit/blocking/")]
    got = values(FIT, ctx_of(host, FIT_DEVICE))
    assert "blocking_bucket_s" not in got
    assert "blocking_layout_s" not in got
    assert got["blocking_s"] == pytest.approx(0.700)


def test_new_metrics_are_reported_by_the_cells_the_issue_names():
    by_name = {m["name"]: m for m in harness.load_manifest()["per_layer"]}
    for m in FLUSH_METRICS:
        assert by_name[m]["workloads"] == [
            BULK, "syn10m1m-r512.serve-online"]
        assert by_name[m]["source"] == "program_span"
    for m in ("blocking_bucket_s", "blocking_layout_s"):
        assert by_name[m]["workloads"] == [FIT, RING]
        assert "reader" not in by_name[m]


def _programs_named_by_metric_files():
    out = set()
    for path in glob.glob(os.path.join(ROOT, "benchmark", "layer_metrics",
                                       "*.json")):
        with open(path) as f:
            out.update(json.load(f)["reader"].get("programs", ()))
    return sorted(out)


def _jitted(name):
    """The jitted function of the program that a device trace would name
    ``jit_<name>``."""
    from large_scale_recommendation_tpu.core.updaters import (
        RegularizedSGDUpdater,
    )
    from large_scale_recommendation_tpu.data import device_blocking
    from large_scale_recommendation_tpu.ops import sgd
    from large_scale_recommendation_tpu.parallel import make_block_mesh
    from large_scale_recommendation_tpu.parallel.dsgd_mesh import (
        build_mesh_dsgd_step,
    )
    from large_scale_recommendation_tpu.serving import retrieval

    if name == "run":  # the mesh step, built per mesh
        return build_mesh_dsgd_step(make_block_mesh(4),
                                    RegularizedSGDUpdater(), 256, 4, 1)
    for module in (sgd, device_blocking, retrieval):
        if hasattr(module, name):
            return getattr(module, name)
    raise AssertionError(f"no module of the program has {name!r}")


def test_metric_files_name_the_programs_expected():
    assert _programs_named_by_metric_files() == [
        "_layout", "_stage1_flat", "_stage2", "dsgd_train", "run"]


@pytest.mark.parametrize("name", ["_layout", "_stage1_flat", "_stage2",
                                  "dsgd_train", "run"])
def test_program_named_by_a_metric_is_a_jitted_function(name):
    fn = _jitted(name)
    assert fn.__name__ == name  # what the trace calls it, less "jit_"
    assert hasattr(fn, "lower"), f"{name} is not jitted"
    assert tr.program_name(f"jit_{fn.__name__}(123)") == name
