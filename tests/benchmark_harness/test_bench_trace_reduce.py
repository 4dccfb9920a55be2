"""The trace reduction, on a small recorded TPU trace (three flushes of a
toy engine on one v5e chip, PR 24) and on hand-made events."""

import os

import numpy as np
import pytest

import bench_testlib  # noqa: F401  (puts the repo root on sys.path)
from benchmark import trace_reduce as tr

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "tiny_serving.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce_trace(tr.read_xplane(TRACE))


def test_recorded_trace_has_one_chip_and_the_benchmark_spans():
    ev = tr.read_xplane(TRACE)
    assert sorted(ev["devices"]) == [0]
    assert len(ev["devices"][0]["modules"]) == 12
    assert len(ev["devices"][0]["ops"]) == 324
    assert [h[0] for h in ev["host"]].count("bench/flush") == 3


def test_recorded_busy_and_idle_share(reduced):
    assert reduced["window_s"] == pytest.approx(0.016984349, rel=1e-9)
    assert reduced["busy_s"] == pytest.approx(0.000287111, rel=1e-6)
    idle = 1.0 - reduced["busy_s"] / reduced["window_s"]
    assert idle == pytest.approx(0.98310, abs=1e-4)


@pytest.mark.parametrize("program,runs,seconds", [
    ("_stage1_flat", 3, 150.490e-6), ("_stage2", 3, 125.032e-6),
    ("_take", 3, 10.695e-6), ("_quantize_rows", 3, 3.654e-6)])
def test_recorded_device_time_by_program_name(reduced, program, runs,
                                              seconds):
    assert reduced["program_runs"][program] == runs
    assert reduced["program_s"][program] == pytest.approx(seconds, rel=1e-6)


def test_recorded_gaps_are_named_after_host_spans(reduced):
    gaps = dict(reduced["idle_gaps"])
    assert gaps["bench/flush"] == pytest.approx(0.011550407, rel=1e-6)
    assert gaps["bench/between_flushes"] == pytest.approx(0.005124021,
                                                          rel=1e-6)
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-9)


def test_recorded_top_operation_is_topk_by_stable_name(reduced):
    name, seconds = reduced["device_ops"][0]
    assert name == "_stage1_flat/custom-call:TopK f32[16,40]"
    assert seconds == pytest.approx(142.207e-6, rel=1e-6)
    assert len(reduced["device_ops"]) <= 10
    assert not any("/while" in n for n, _ in reduced["device_ops"])


def _events(ops, modules, host, chip=0):
    return {"devices": {chip: {"ops": ops, "modules": modules}},
            "host": host}


def test_overlapping_operations_count_once():
    ev = _events([("%a = f32[1] x", 0, 100), ("%b = f32[1] y", 50, 100)],
                 [("jit_f(1)", 0, 150)], [("bench/window", 0, 300)])
    r = tr.reduce_trace(ev)
    assert r["busy_s"] == pytest.approx(150e-9)
    assert r["window_s"] == pytest.approx(300e-9)
    assert dict(r["idle_gaps"]) == {"unattributed": pytest.approx(150e-9)}


def test_window_span_clips_events_outside_it():
    ev = _events([("%a = f32[1] x", 0, 100), ("%b = f32[1] y", 900, 200)],
                 [("jit_f(1)", 0, 100), ("jit_g(2)", 900, 200)],
                 [("bench/window", 50, 950)])
    r = tr.reduce_trace(ev)
    assert r["busy_s"] == pytest.approx((50 + 100) * 1e-9)
    assert r["program_s"]["f"] == pytest.approx(50e-9)
    assert r["program_s"]["g"] == pytest.approx(100e-9)


def test_busy_is_averaged_over_chips_and_program_time_summed():
    ev = {"devices": {
        0: {"ops": [("%a = f32[1] x", 0, 100)],
            "modules": [("jit_step(1)", 0, 100)]},
        1: {"ops": [("%a = f32[1] x", 0, 300)],
            "modules": [("jit_step(1)", 0, 300)]}},
        "host": [("bench/window", 0, 400)]}
    r = tr.reduce_trace(ev)
    assert r["busy_s"] == pytest.approx(200e-9)
    assert r["program_s"]["step"] == pytest.approx(400e-9)
    assert r["program_per_chip_s"]["step"] == pytest.approx(300e-9)
    assert r["program_runs"]["step"] == 2


def test_host_share_of_a_span_is_its_wall_minus_device_time_inside():
    ev = _events([("%a = f32[1] x", 100, 50)], [("jit_f(1)", 100, 50)],
                 [("bench/window", 0, 400), ("serving/flush", 80, 100)])
    r = tr.reduce_trace(ev)
    assert r["span_host_s"]["serving/flush"] == [pytest.approx(50e-9)]
    assert r["span_runs"] == {"serving/flush": 1}
    assert r["program_first_start_s"]["f"] == pytest.approx(100e-9)
    assert r["span_first_start_s"]["serving/flush"] == pytest.approx(80e-9)


def test_a_trace_without_a_device_plane_is_refused():
    with pytest.raises(ValueError, match="no operation ran on the device"):
        tr.reduce_trace({"devices": {}, "host": []})


@pytest.mark.parametrize("text,name", [
    ('%fusion.2 = f32[128,1048576]{1,0:T(8,128)} fusion(s8[128,512] %a)',
     "fusion.2 f32[128,1048576]"),
    ('%custom-call = (f32[16,40]{1,0}, s32[16,40]{1,0}) custom-call(f32[16,'
     '8192] %f), custom_call_target="TopK"', "custom-call:TopK f32[16,40]"),
    ("no-equals-sign", "no-equals-sign")])
def test_operation_names_are_short_and_stable(text, name):
    assert tr.op_name(text) == name


def test_program_name_strips_jit_prefix_and_fingerprint():
    assert tr.program_name("jit__stage1_flat(5993341012)") == "_stage1_flat"
    assert tr.program_name("jit_dsgd_train(1)") == "dsgd_train"


def test_union_of_intervals():
    s, e = tr._union(np.array([10, 0, 5, 40]), np.array([20, 6, 12, 50]))
    assert list(s) == [0, 40] and list(e) == [20, 50]
