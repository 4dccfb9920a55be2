"""Each runner rehearsed end to end on the CPU at a toy size: counts and
``correct`` only, never a time. The control (the nearest precision below
the configuration's) and each planted fault must come out not correct,
held to the limits of the real configuration files."""

import json

import numpy as np
import pytest

import bench_testlib
from bench_testlib import run_toy

FIT = "netflix100m-r128.fit"
RING = "netflix100m-r128-ring4.fit"
BULK = "syn10m1m-r512.serve-bulk"
ONLINE = "syn10m1m-r512.serve-online"


def test_the_timing_path_refuses_a_machine_without_a_tpu(capsys):
    from benchmark import run

    with pytest.raises(SystemExit) as e:
        run.main(["--workload", FIT, "--seed", "1", "--seconds", "1",
                  "--trace", "0"])
    assert e.value.code not in (0, None)
    out = capsys.readouterr()
    assert out.out == "" and "no timing is taken off the chip" in out.err


def test_fit_rehearsal_is_correct_and_counts():
    line, out = run_toy(FIT)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 1
    assert set(line["metrics"]) == {"time_to_target_s",
                                    "train_ratings_per_s", "setup_s"}
    assert list(line)[-1] == "compared"
    assert set(line["compared"]) == set(
        bench_testlib.toy_cell(FIT).config["limits"])
    assert out["notes"]["sweeps"] == 4 and len(out["notes"][
        "holdout_rmse"]) == 4
    assert out["compiles_in_window"] == 0
    assert out["ctx"]["counters"]["sweeps_to_target"] == 1
    assert line["device"]["platform"] == "cpu"


def test_fit_traced_rehearsal_reports_only_what_it_can_read():
    line, out = run_toy(FIT, trace=True)
    assert line["correct"] is True
    # no device plane and no peaks off the chip: no share of a peak, no
    # device time; the count is there
    assert set(line["metrics"]) == {"sweeps_to_target"}


def test_fit_control_bf16_is_not_correct():
    line, _ = run_toy(FIT, control="bf16")
    assert line["correct"] is False
    over = [k for k, c in line["compared"].items()
            if c["value"] > c["limit"]]
    assert over


def test_fit_fault_state_unchanged_is_not_correct(monkeypatch):
    from large_scale_recommendation_tpu.ops import sgd as sgd_ops

    monkeypatch.setattr(sgd_ops, "dsgd_train",
                        lambda U, V, *a, **k: (U, V))
    line, _ = run_toy(FIT)
    assert line["correct"] is False
    assert line["compared"]["first_update_gap"]["value"] == pytest.approx(1)


def test_fit_fault_half_batch_is_not_correct(monkeypatch):
    from large_scale_recommendation_tpu.ops import sgd as sgd_ops

    real = sgd_ops.dsgd_train

    def half(U, V, su, si, sv, sw, ou, ov, icu=None, icv=None, **kw):
        mb = kw["minibatch"]
        keep = (np.arange(sw.shape[-1]) % mb) >= mb // 2
        # the mean over the rest: counts taken at run time over what stays
        return real(U, V, su, si, sv, sw * keep.astype(np.float32), ou, ov,
                    None, None, **kw)

    monkeypatch.setattr(sgd_ops, "dsgd_train", half)
    line, _ = run_toy(FIT)
    assert line["correct"] is False


def test_fit_unreached_target_is_a_failed_run():
    with pytest.raises(SystemExit) as e:
        run_toy(FIT, target_rmse=0.01)
    assert e.value.code == 1


def test_fit_short_window_cuts_sweeps_down_to_one():
    from benchmark.runners.fit import sweeps_for

    t = bench_testlib.toy_cell(FIT).traffic
    full = t["full_at_seconds"]
    assert sweeps_for(t, full) == sweeps_for(t, 10 * full) == t["sweeps"]
    assert sweeps_for(t, full / 2) == t["sweeps"] // 2
    assert sweeps_for(t, 1) == 1


def test_mesh_dsgd_solver_on_four_virtual_devices():
    line, out = run_toy(RING)
    assert line["correct"] is True and line["device"]["count"] >= 4
    assert out["notes"]["sweeps"] == 4
    assert len(out["notes"]["per_device_peak_bytes"]) >= 4


def test_mesh_dsgd_refuses_a_block_count_that_is_not_the_chip_count():
    with pytest.raises(ValueError, match="one block per chip"):
        run_toy(RING, num_blocks=2)


def test_bulk_rehearsal_is_correct_and_counts():
    line, out = run_toy(BULK)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_users_per_s", "setup_s"}
    users = out["ctx"]["counters"]["users_answered"]
    per_request = bench_testlib.toy_cell(BULK).traffic["request_users"]["fixed"]
    assert users == line["attempted"] * per_request
    assert set(out["ctx"]["series"]["bucket_rows"]) == {256}
    assert out["compiles_in_window"] == 0


def test_online_rehearsal_is_correct_and_counts():
    line, out = run_toy(ONLINE, seconds=3.0)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_users_per_s", "setup_s"}
    assert line["notes"]["end_to_end"]["request_p95_ms"] > 0
    series = out["ctx"]["series"]
    assert len(series["request_latency_ms"]) == line["attempted"]
    assert np.all(np.isfinite(series["request_latency_ms"]))
    assert sum(series["flush_rows"]) == out["ctx"]["counters"][
        "users_answered"]
    assert out["compiles_in_window"] == 0


def test_online_traced_rehearsal_reads_its_span_metrics():
    line, _ = run_toy(ONLINE, seconds=2.0, trace=True)
    assert {"request_p95_ms", "batch_rows_p50", "queue_wait_ms_p50",
            "loadgen_late_ms_p99"} <= set(line["metrics"])
    assert not any("roofline" in k or "mfu" in k for k in line["metrics"])


@pytest.mark.parametrize("cell", [BULK, ONLINE])
def test_serving_control_int8_is_not_correct(cell):
    line, _ = run_toy(cell, control="int8")
    assert line["correct"] is False


def test_serving_fault_altered_answer_is_not_correct(monkeypatch):
    from large_scale_recommendation_tpu.serving import retrieval

    real = retrieval.TwoStageRetriever.topk

    def altered(self, U_chunk, excl, k, **kw):
        v, rows = real(self, U_chunk, excl, k, **kw)
        return v, rows.at[:, 0].set((rows[:, 0] + 1) % self.n_rows)

    monkeypatch.setattr(retrieval.TwoStageRetriever, "topk", altered)
    line, _ = run_toy(ONLINE, seconds=2.0)
    assert line["correct"] is False


def test_a_failed_request_is_counted_and_not_correct(monkeypatch):
    from large_scale_recommendation_tpu.serving.engine import ServingEngine

    real = ServingEngine.flush
    calls = {"n": 0}

    def flaky(self, *a, **k):
        calls["n"] += 1
        if calls["n"] > 40 and calls["n"] % 7 == 0:  # past the warm-up
            self._pending, self._pending_t = [], []
            raise RuntimeError("planted")
        return real(self, *a, **k)

    monkeypatch.setattr(ServingEngine, "flush", flaky)
    line, _ = run_toy(ONLINE, seconds=2.0)
    assert line["failed"] > 0 and line["correct"] is False


def test_a_compile_inside_the_window_makes_the_run_not_correct(monkeypatch):
    import jax
    import jax.numpy as jnp

    from benchmark import loadgen

    real = loadgen.run_closed_loop

    def compiles(engine, requests, seconds, spans):
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(17)).block_until_ready()
        return real(engine, requests, seconds, spans)

    monkeypatch.setattr(loadgen, "run_closed_loop", compiles)
    line, out = run_toy(BULK)
    assert out["compiles_in_window"] >= 1 and line["correct"] is False


def test_result_line_is_one_json_object_with_the_contracts_keys():
    line, _ = run_toy(BULK)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    json.dumps(line)
