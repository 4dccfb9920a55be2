"""The terminal-side observability tooling: the bench regression gate
(``scripts/bench_regress.py`` — wrapper/raw/salvage loading, threshold
verdicts, exit codes) and the live-watch delta math in
``scripts/obs_report.py``.
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from scripts.bench_regress import (  # noqa: E402
    compare,
    flatten_result,
    load_result,
    main as regress_main,
)
from scripts.obs_report import snapshot_deltas  # noqa: E402


def _bench_doc(value=1000.0, extra=None):
    return {"metric": "ratings/s test", "value": value, "unit": "ratings/s",
            "vs_baseline": 1.0, "extra": extra or {}}


def _wrapper(parsed=None, tail=""):
    return {"n": 1, "cmd": "python harness.py", "rc": 0, "tail": tail,
            "parsed": parsed}


class TestLoading:
    def test_raw_bench_line(self, tmp_path):
        p = tmp_path / "raw.json"
        p.write_text(json.dumps(_bench_doc(
            2000.0, {"serving_users_per_s": 42.5, "pipeline": "device"})))
        flat, caveat = load_result(str(p))
        assert flat == {"value": 2000.0, "serving_users_per_s": 42.5}
        assert caveat is None

    def test_wrapper_with_parsed(self, tmp_path):
        p = tmp_path / "w.json"
        p.write_text(json.dumps(_wrapper(parsed=_bench_doc(
            3000.0, {"online_ratings_per_s": 7.0}))))
        flat, _ = load_result(str(p))
        assert flat["value"] == 3000.0
        assert flat["online_ratings_per_s"] == 7.0

    def test_truncated_tail_salvage(self, tmp_path):
        """A front-truncated tail (the real r05 shape) still yields its
        numeric pairs — array elements (no preceding key) don't match."""
        tail = ('_per_s\": 123.4, \"rmse_curve\": [0.27, 0.26], '
                '\"serving_users_per_s\": 25837.8}}')
        p = tmp_path / "t.json"
        p.write_text(json.dumps(_wrapper(parsed=None, tail=tail)))
        flat, _ = load_result(str(p))
        assert flat["serving_users_per_s"] == 25837.8
        assert 0.26 not in flat.values()  # curve entries not salvaged

    def test_error_field_is_caveat(self, tmp_path):
        doc = _bench_doc(1.0)
        doc["error"] = "CPU fallback run"
        p = tmp_path / "e.json"
        p.write_text(json.dumps(_wrapper(parsed=doc)))
        _, caveat = load_result(str(p))
        assert "CPU fallback" in caveat

    def test_flat_baseline_dict(self):
        flat = flatten_result({"serving_users_per_s": 10.0, "note": "x"})
        assert flat == {"serving_users_per_s": 10.0}


class TestCompare:
    def test_verdicts(self):
        base = {"a": 100.0, "b": 100.0, "c": 100.0}
        cur = {"a": 95.0, "b": 60.0}
        rows = compare(base, cur, {"a": 10.0, "b": 10.0, "c": 10.0})
        by_key = {r["key"]: r for r in rows}
        assert by_key["a"]["verdict"] == "ok"  # -5% within 10%
        assert by_key["b"]["verdict"] == "REGRESSION"  # -40%
        assert by_key["c"]["verdict"] == "missing"

    def test_improvement_is_ok(self):
        rows = compare({"a": 100.0}, {"a": 300.0}, {"a": 10.0})
        assert rows[0]["verdict"] == "ok"

    def test_lower_is_better_keys(self):
        # *_wall_s is auto-flagged lower-better: growth is the regression
        rows = compare({"dsgd_train_wall_s": 2.0},
                       {"dsgd_train_wall_s": 3.0},
                       {"dsgd_train_wall_s": 10.0})
        assert rows[0]["verdict"] == "REGRESSION"
        rows = compare({"dsgd_train_wall_s": 2.0},
                       {"dsgd_train_wall_s": 1.0},
                       {"dsgd_train_wall_s": 10.0})
        assert rows[0]["verdict"] == "ok"

    def test_higher_is_better_keys_explicit(self):
        """Throughputs and achieved bandwidth (the ISSUE-6 gate keys) are
        EXPLICITLY higher-is-better: a drop regresses, growth never does —
        even for keys that also contain a lower-better substring."""
        from scripts.bench_regress import is_lower_better

        for key in ("effective_hbm_gbs", "pct_of_hbm_peak",
                    "online_ratings_per_s", "als_rank32_rows_per_s",
                    "serving_users_per_s", "train_hbm_gbs",
                    "kernel_pallas_loop_effective_hbm_gbs"):
            assert not is_lower_better(key, set()), key
            rows = compare({key: 100.0}, {key: 60.0}, {key: 10.0})
            assert rows[0]["verdict"] == "REGRESSION", key
            rows = compare({key: 100.0}, {key: 300.0}, {key: 10.0})
            assert rows[0]["verdict"] == "ok", key
        # the explicit rule wins over an accidental DEFAULT_LOWER
        # substring collision ("time_to_" is lower-better, but a rate
        # named around it must stay higher-better)
        assert not is_lower_better("time_to_target_ratings_per_s", set())
        # an explicit --lower flag still wins over everything
        assert is_lower_better("effective_hbm_gbs",
                               {"effective_hbm_gbs"})

    def test_hbm_gate_keys_in_default_watch_set(self):
        """The ISSUE-6 bandwidth keys are gated by DEFAULT (no flags)."""
        from scripts.bench_regress import DEFAULT_KEYS

        assert "effective_hbm_gbs" in DEFAULT_KEYS
        assert "pct_of_hbm_peak" in DEFAULT_KEYS

    def test_compile_gate_keys_in_default_watch_set(self):
        """The ISSUE-9 compile-time keys are gated by DEFAULT: a
        compile-count explosion or a compile-wall blowup trips the gate
        with no flags."""
        from scripts.bench_regress import DEFAULT_KEYS

        for key in ("compile_wall_s", "xla_compile_wall_s",
                    "compile_count"):
            assert key in DEFAULT_KEYS, key

    def test_compile_keys_lower_is_better(self):
        """Compile time/count regress when they GROW — lower-is-better
        (compile_wall_s via the _wall_s pattern, compile_count via its
        own DEFAULT_LOWER entry)."""
        from scripts.bench_regress import is_lower_better

        for key in ("compile_wall_s", "xla_compile_wall_s",
                    "compile_count"):
            assert is_lower_better(key, set()), key
            rows = compare({key: 10.0}, {key: 20.0}, {key: 15.0})
            assert rows[0]["verdict"] == "REGRESSION", key
            rows = compare({key: 10.0}, {key: 5.0}, {key: 15.0})
            assert rows[0]["verdict"] == "ok", key


class TestGateEndToEnd:
    def _write(self, tmp_path, name, value, extra=None):
        p = tmp_path / name
        p.write_text(json.dumps(_wrapper(parsed=_bench_doc(value, extra))))
        return str(p)

    def test_ok_exit_zero(self, tmp_path, capsys):
        b = self._write(tmp_path, "b.json", 1000.0,
                        {"serving_users_per_s": 50.0})
        c = self._write(tmp_path, "c.json", 980.0,
                        {"serving_users_per_s": 51.0})
        rc = regress_main(["--baseline", b, "--current", c])
        assert rc == 0
        out = capsys.readouterr().out
        assert "no regressions" in out

    def test_regression_exit_one_and_table(self, tmp_path, capsys):
        b = self._write(tmp_path, "b.json", 1000.0)
        c = self._write(tmp_path, "c.json", 500.0)
        rc = regress_main(["--baseline", b, "--current", c,
                           "--key", "value=20"])
        assert rc == 1
        out = capsys.readouterr().out
        assert "REGRESSION" in out and "value" in out

    def test_report_file_written(self, tmp_path):
        b = self._write(tmp_path, "b.json", 1000.0)
        c = self._write(tmp_path, "c.json", 990.0)
        report = tmp_path / "report.txt"
        rc = regress_main(["--baseline", b, "--current", c,
                           "--report", str(report)])
        assert rc == 0
        assert "baseline" in report.read_text()

    def test_missing_key_fails_only_strict(self, tmp_path):
        b = self._write(tmp_path, "b.json", 1000.0,
                        {"serving_users_per_s": 50.0})
        c = self._write(tmp_path, "c.json", 1000.0)  # extra key gone
        args = ["--baseline", b, "--current", c,
                "--key", "value=30", "--key", "serving_users_per_s=30"]
        assert regress_main(args) == 0
        assert regress_main(args + ["--strict"]) == 1

    def test_multichip_family_gates_pad_and_throughput(self, tmp_path,
                                                       capsys):
        """--family multichip (ISSUE 7): MULTICHIP_r*.json rounds gate
        through the same loader with pad ratio LOWER-is-better and
        sharded throughput HIGHER-is-better."""
        base = {"n_devices": 16, "max_pad_ratio": 1.10, "layout_mb": 600.0,
                "train_ratings_per_s": 500_000.0, "als_rows_per_s": 9000.0}
        # a pad-ratio blowup alone must trip the gate
        cur = dict(base, max_pad_ratio=1.60)
        b, c = tmp_path / "MULTICHIP_r01.json", tmp_path / "MULTICHIP_r02.json"
        b.write_text(json.dumps(base))
        c.write_text(json.dumps(cur))
        rc = regress_main(["--family", "multichip",
                           "--baseline", str(b), "--current", str(c)])
        assert rc == 1
        assert "max_pad_ratio" in capsys.readouterr().out
        # a throughput collapse must trip it too
        c.write_text(json.dumps(dict(base, train_ratings_per_s=100_000.0)))
        assert regress_main(["--family", "multichip",
                             "--baseline", str(b),
                             "--current", str(c)]) == 1
        # better pad ratio AND faster training is never a regression
        c.write_text(json.dumps(dict(base, max_pad_ratio=1.02,
                                     train_ratings_per_s=900_000.0)))
        assert regress_main(["--family", "multichip",
                             "--baseline", str(b),
                             "--current", str(c)]) == 0

    def test_multichip_direction_rules(self):
        """Pad/layout keys are lower-is-better; the sharded throughput
        keys stay higher-is-better; all are in the default watch set."""
        from scripts.bench_regress import (
            MULTICHIP_KEYS,
            is_lower_better,
        )

        for key in ("max_pad_ratio", "layout_mb", "layout_bytes"):
            assert is_lower_better(key, set()), key
        for key in ("train_ratings_per_s", "als_rows_per_s"):
            assert not is_lower_better(key, set()), key
        for key in ("train_ratings_per_s", "als_rows_per_s",
                    "max_pad_ratio", "layout_mb"):
            assert key in MULTICHIP_KEYS

    def test_multichip_find_rounds_and_legacy_wrappers(self, tmp_path):
        """find_rounds(prefix=) orders MULTICHIP rounds; the committed
        legacy wrapper shape ({n_devices, rc, ok, tail}) still loads
        (empty metrics -> 'missing' verdicts, never a crash)."""
        from scripts.bench_regress import find_rounds

        for n in (2, 1, 10):
            (tmp_path / f"MULTICHIP_r{n:02d}.json").write_text("{}")
        (tmp_path / "BENCH_r01.json").write_text("{}")
        rounds = find_rounds(str(tmp_path), prefix="MULTICHIP")
        assert [os.path.basename(p) for p in rounds] == [
            "MULTICHIP_r01.json", "MULTICHIP_r02.json",
            "MULTICHIP_r10.json"]
        legacy = tmp_path / "MULTICHIP_r00.json"
        legacy.write_text(json.dumps(
            {"n_devices": 8, "rc": 0, "ok": True, "skipped": False,
             "tail": ""}))
        flat, caveat = load_result(str(legacy))
        assert flat == {} and caveat is None
        rows = compare(flat, {"max_pad_ratio": 1.2}, {"max_pad_ratio": 10.0})
        assert rows[0]["verdict"] == "missing"

    def test_multichip_wrapper_tail_salvage(self, tmp_path):
        """A future driver wrapper whose tail holds the pod_dryrun JSON
        line salvages the numeric fields through the shared loader."""
        tail = ('{"n_devices": 16, "max_pad_ratio": 1.104, '
                '"train_ratings_per_s": 421337, "two_process": '
                '{"ok": true, "wall_s": 38.2}}')
        p = tmp_path / "MULTICHIP_r03.json"
        p.write_text(json.dumps({"n": 16, "rc": 0, "tail": tail,
                                 "parsed": None}))
        flat, _ = load_result(str(p))
        assert flat["max_pad_ratio"] == 1.104
        assert flat["train_ratings_per_s"] == 421337

    def test_real_rounds_parse(self):
        """Every committed *successful* BENCH_r*.json loads into a
        non-empty flat metric dict — the gate can always read the
        repo's own rounds (a crashed round, rc != 0 with a traceback
        tail, legitimately yields nothing and must not blow up)."""
        from scripts.bench_regress import find_rounds

        rounds = find_rounds()
        # BENCH_r02 is the one committed round left: PR 21 deleted the
        # rounds that were CPU fallbacks filed under the chip metric
        assert len(rounds) >= 1
        parsed_any = 0
        for path in rounds:
            with open(path) as f:
                rc = json.load(f).get("rc")
            flat, _ = load_result(path)  # must never raise
            if rc == 0:
                assert flat, f"no numeric keys salvaged from {path}"
                parsed_any += 1
        assert parsed_any >= 1  # a healthy round the gate can read


class TestQualityLineageRenderers:
    def test_render_lineage_snapshot(self):
        from scripts.obs_report import render_lineage

        doc = {"time": 100.0, "swaps": 3, "evicted": 0,
               "records": [
                   {"catalog_version": 1, "wall_time": 90.0,
                    "wal_offset_watermark": 500, "train_step": 4,
                    "retrain_id": None, "source": "stream_refresh",
                    "seq": 1}],
               "freshness": {"servable_watermark": 500,
                             "servable_swap_age_s": 10.0,
                             "latest_ingest_offset": 700,
                             "ingest_ahead": True,
                             "unservable_age_s": 6.0}}
        out = render_lineage(doc)
        assert "stream_refresh" in out
        assert "500" in out
        assert "INGEST AHEAD" in out

    def test_render_lineage_accepts_bundle_file_shape(self):
        from scripts.obs_report import render_lineage

        bundle_doc = {"lineage": {"records": [], "swaps": 0,
                                  "freshness": {}},
                      "quality": [], "data_quality": []}
        assert "no provenance records" in render_lineage(bundle_doc)

    def test_render_quality_series_and_bundle_shapes(self):
        from scripts.obs_report import render_quality

        series_doc = {"series": {
            'eval_rmse{source="online"}': {
                "points": [[1, 0.5], [2, 0.4]], "n": 2},
            "online_batch_s:p50": {"points": [[1, 0.1]], "n": 1}}}
        out = render_quality(series_doc)
        assert "eval_rmse" in out
        assert "online_batch_s" not in out  # non-quality series filtered
        bundle_doc = {"lineage": {"records": []},
                      "quality": [{"name": "eval_rmse",
                                   "labels": {"source": "online"},
                                   "type": "gauge", "value": 0.42}],
                      "data_quality": []}
        out = render_quality(bundle_doc)
        assert "0.42" in out

    def test_cli_modes(self, tmp_path, capsys):
        import json as _json

        from scripts.obs_report import main as report_main

        p = tmp_path / "lineage.json"
        p.write_text(_json.dumps({"records": [], "swaps": 0,
                                  "freshness": {}}))
        assert report_main(["--lineage", str(p)]) == 0
        assert "catalog lineage" in capsys.readouterr().out
        q = tmp_path / "series.json"
        q.write_text(_json.dumps({"series": {}}))
        assert report_main(["--quality", str(q)]) == 0
        assert "model-quality" in capsys.readouterr().out
        b = tmp_path / "budget.json"
        b.write_text(_json.dumps({"note": "rollout budget not enabled",
                                  "cohorts": {}}))
        assert report_main(["--budget", str(b)]) == 0
        assert "rollout error budget" in capsys.readouterr().out

    def test_render_budget_snapshot_and_fleet_shapes(self):
        from scripts.obs_report import render_budget

        # the local /budgetz shape: cohorts keyed by version string
        doc = {"target_s": 0.1, "objective": 0.9,
               "burn_rates": {"primary": 0.5, "fast": 4.0, "slow": 0.5},
               "cohorts": {"7": {"served": 40, "shed": 0,
                                 "shed_frac": 0.0, "attainment": 1.0,
                                 "burn_rate_fast": 0.0, "p99_ms": 10.0,
                                 "error_budget_remaining": 1.0},
                           "9": {"served": 40, "shed": 3,
                                 "shed_frac": 0.07, "attainment": 0.0,
                                 "burn_rate_fast": 10.0, "p99_ms": 200.0,
                                 "error_budget_remaining": 0.0}},
               "verdicts": {
                   "pending_rollbacks": {"9": {"reason": "burn cliff",
                                               "time": 100.0}},
                   "history": [{"time": 100.0, "canary_version": 9,
                                "incumbent_version": 7,
                                "verdict": "ROLLBACK",
                                "reason": "burn cliff"}]}}
        out = render_budget(doc)
        assert "PENDING ROLLBACK v9" in out
        assert "burn cliff" in out
        assert "fast=4" in out
        # the fleet pod-aggregate shape: a merged, sorted row list
        fleet = {"objective": 0.9,
                 "cohorts": [{"version": 9, "served": 80, "shed": 6,
                              "shed_frac": 0.07, "attainment": 0.0,
                              "burn_rate_fast_max": 10.0,
                              "p99_ms_max": 200.0,
                              "error_budget_remaining_min": 0.0,
                              "hosts": 2}],
                 "pending_rollbacks": {"9": [{"host": "a:1",
                                              "reason": "burn cliff"}]},
                 "targets": [{"host": "a:1", "evaluations": 3,
                              "pending_rollbacks": ["9"],
                              "note": None}]}
        out = render_budget(fleet)
        assert "a:1" in out and "PENDING ROLLBACK v9" in out
        # the absent-plane note renders, never crashes
        assert "enable_budget" in render_budget(
            {"note": "rollout budget not enabled (obs.enable_budget)",
             "cohorts": {}})


class TestWatchDeltas:
    def _snap(self, t, counter=0.0, gauge=0.0, hist_count=0):
        return {"time": t, "metrics": [
            {"name": "c_total", "type": "counter", "labels": {},
             "value": counter},
            {"name": "g", "type": "gauge", "labels": {"x": "1"},
             "value": gauge},
            {"name": "h_s", "type": "histogram", "labels": {},
             "count": hist_count, "sum": 1.0, "mean": 0.1, "min": 0.1,
             "max": 0.1, "p50": 0.1, "p90": 0.1, "p99": 0.1},
        ]}

    def test_counter_and_histogram_rates(self):
        rows = snapshot_deltas(self._snap(0, counter=10, hist_count=4),
                               self._snap(2, counter=30, gauge=7.0,
                                          hist_count=10), dt=2.0)
        by = {r["name"]: r for r in rows}
        assert by["c_total"]["delta"] == 20
        assert by["c_total"]["rate"] == 10.0
        assert by["h_s"]["delta"] == 6
        assert by["h_s"]["rate"] == 3.0
        assert by["h_s"]["p99"] == 0.1
        # gauges: value + change (no rate) — the delta is what keeps a
        # moving lag/SLO gauge visible in --watch's active-only view
        assert by["g"]["value"] == 7.0
        assert by["g"]["delta"] == 7.0
        assert "rate" not in by["g"]

    def test_watch_active_view_keeps_moving_gauges(self):
        from scripts.obs_report import render_deltas

        prev = self._snap(0, gauge=3.0)
        cur = self._snap(1, gauge=9.0)
        table = render_deltas(prev, cur, dt=1.0, active_only=True)
        assert "g" in table.splitlines()[2]  # the gauge row survived
        stale = render_deltas(cur, cur, dt=1.0, active_only=True)
        assert "(no activity)" in stale  # unchanged gauge drops out

    def test_new_instrument_counts_from_zero(self):
        prev = {"time": 0, "metrics": []}
        rows = snapshot_deltas(prev, self._snap(1, counter=5), dt=1.0)
        by = {r["name"]: r for r in rows}
        assert by["c_total"]["delta"] == 5


class TestServingFamily:
    """``--family serving`` (ISSUE 8): SERVING_r*.json traffic-sim
    rounds gate with p99 latencies LOWER-is-better and throughput /
    QPS-at-SLO / recall higher-is-better — the unit twins of the
    multichip family's tests above."""

    BASE = {"fast_users_per_s": 900.0, "exact_users_per_s": 300.0,
            "fast_vs_exact": 3.0, "qps_at_slo": 60.0,
            "recall_at_10": 0.97, "p99_ms": 120.0,
            "overload_fast_p99_ms": 250.0}

    def _round(self, tmp_path, name, **over):
        extra = dict(self.BASE, **over)
        value = extra.pop("value", extra["fast_users_per_s"])
        p = tmp_path / name
        p.write_text(json.dumps(  # the SERVING rounds' line shape
            {"metric": "two-stage serving users/s", "value": value,
             "unit": "users/s", "vs_baseline": extra["fast_vs_exact"],
             "extra": extra}))
        return str(p)

    def test_p99_blowup_alone_trips(self, tmp_path, capsys):
        b = self._round(tmp_path, "SERVING_r01.json")
        c = self._round(tmp_path, "SERVING_r02.json", p99_ms=400.0)
        rc = regress_main(["--family", "serving",
                           "--baseline", b, "--current", c])
        assert rc == 1
        assert "p99_ms" in capsys.readouterr().out

    def test_recall_drop_trips_tight(self, tmp_path):
        """Recall is deterministic (same code + seed ⇒ same index):
        its threshold is tight — a 7% drop is a retrieval-math change."""
        b = self._round(tmp_path, "SERVING_r01.json")
        c = self._round(tmp_path, "SERVING_r02.json", recall_at_10=0.90)
        assert regress_main(["--family", "serving",
                             "--baseline", b, "--current", c]) == 1

    def test_throughput_collapse_trips(self, tmp_path):
        b = self._round(tmp_path, "SERVING_r01.json")
        c = self._round(tmp_path, "SERVING_r02.json",
                        fast_users_per_s=400.0, value=400.0)
        assert regress_main(["--family", "serving",
                             "--baseline", b, "--current", c]) == 1

    def test_across_the_board_improvement_never_trips(self, tmp_path):
        b = self._round(tmp_path, "SERVING_r01.json")
        c = self._round(tmp_path, "SERVING_r02.json",
                        fast_users_per_s=2000.0, value=2000.0,
                        p99_ms=40.0, overload_fast_p99_ms=90.0,
                        qps_at_slo=200.0, recall_at_10=0.999,
                        fast_vs_exact=6.0)
        assert regress_main(["--family", "serving",
                             "--baseline", b, "--current", c]) == 0

    def test_serving_direction_rules(self):
        from scripts.bench_regress import SERVING_KEYS, is_lower_better

        for key in ("p99_ms", "p50_ms", "overload_fast_p99_ms",
                    "overload_exact_p99_ms"):
            assert is_lower_better(key, set()), key
        for key in ("fast_users_per_s", "exact_users_per_s",
                    "fast_vs_exact", "qps_at_slo", "recall_at_10"):
            assert not is_lower_better(key, set()), key
        for key in ("fast_users_per_s", "qps_at_slo", "recall_at_10",
                    "p99_ms", "overload_fast_p99_ms"):
            assert key in SERVING_KEYS

    def test_serving_find_rounds(self, tmp_path):
        from scripts.bench_regress import find_rounds

        for n in (3, 1):
            (tmp_path / f"SERVING_r{n:02d}.json").write_text("{}")
        (tmp_path / "BENCH_r01.json").write_text("{}")
        rounds = find_rounds(str(tmp_path), prefix="SERVING")
        assert [os.path.basename(p) for p in rounds] == [
            "SERVING_r01.json", "SERVING_r03.json"]


class TestQualityFamily:
    """``--family quality`` (ISSUE 10): the model-quality keys ride
    inside the BENCH rounds — implicit ranking/coverage and the eval_*
    family gate higher-is-better, eval_rmse lower — following the
    PR 7/8 family pattern (direction + watch-set unit twins)."""

    BASE = {"als_implicit_ndcg": 0.45, "als_implicit_hr10": 0.62,
            "als_implicit_coverage": 0.30, "rmse_final": 0.85}

    def _round(self, tmp_path, name, **over):
        extra = dict(self.BASE, **over)
        p = tmp_path / name
        p.write_text(json.dumps(  # the real bench line shape
            {"metric": "ratings/s", "value": 1000.0,
             "unit": "ratings/s", "extra": extra}))
        return str(p)

    def test_ndcg_collapse_alone_trips(self, tmp_path, capsys):
        """The ndcg=0.003 scenario the family exists for: a ranking
        collapse trips the gate even with throughput untouched."""
        b = self._round(tmp_path, "BENCH_r01.json")
        c = self._round(tmp_path, "BENCH_r02.json",
                        als_implicit_ndcg=0.003, als_implicit_hr10=0.007)
        rc = regress_main(["--family", "quality",
                           "--baseline", b, "--current", c])
        assert rc == 1
        assert "als_implicit_ndcg" in capsys.readouterr().out

    def test_rmse_blowup_trips_lower_is_better(self, tmp_path):
        b = self._round(tmp_path, "BENCH_r01.json")
        c = self._round(tmp_path, "BENCH_r02.json", rmse_final=2.0)
        assert regress_main(["--family", "quality",
                             "--baseline", b, "--current", c]) == 1

    def test_coverage_collapse_trips(self, tmp_path):
        b = self._round(tmp_path, "BENCH_r01.json")
        c = self._round(tmp_path, "BENCH_r02.json",
                        als_implicit_coverage=0.05)
        assert regress_main(["--family", "quality",
                             "--baseline", b, "--current", c]) == 1

    def test_across_the_board_improvement_never_trips(self, tmp_path):
        b = self._round(tmp_path, "BENCH_r01.json")
        c = self._round(tmp_path, "BENCH_r02.json",
                        als_implicit_ndcg=0.9, als_implicit_hr10=0.95,
                        als_implicit_coverage=0.6, rmse_final=0.4)
        assert regress_main(["--family", "quality",
                             "--baseline", b, "--current", c]) == 0

    def test_quality_direction_rules(self):
        """Direction rules cover BOTH the bench-borne keys and the
        evaluator's eval_* family (watchable via --key on
        quality-bearing rounds)."""
        from scripts.bench_regress import QUALITY_KEYS, is_lower_better

        for key in ("als_implicit_ndcg", "als_implicit_hr10",
                    "als_implicit_coverage", "eval_ndcg_at_k",
                    "eval_hr_at_k", "eval_coverage"):
            assert not is_lower_better(key, set()), key
        for key in ("eval_rmse", "rmse_final", "lineage_staleness_s"):
            assert is_lower_better(key, set()), key
        for key in self.BASE:
            assert key in QUALITY_KEYS, key

    def test_quality_family_reads_bench_rounds(self):
        """The family maps onto the BENCH prefix and watches ONLY keys
        a bench round can actually carry — a default watch key no
        round contains would be permanent 'missing' noise and an
        unconditional --strict failure."""
        from scripts.bench_regress import QUALITY_KEYS, FAMILIES

        prefix, keys = FAMILIES["quality"]
        assert prefix == "BENCH"
        assert keys is not FAMILIES["bench"][1]
        assert not any(k.startswith("eval_") for k in QUALITY_KEYS)


class TestCriticalPathDirection:
    """ISSUE 12: the ingest→servable critical-path keys gate
    LOWER-is-better — the PR 7/8-pattern direction/watch-set unit
    twins for ``critical_path_total_s`` and the per-stage keys."""

    def test_critical_path_keys_lower_is_better(self):
        from scripts.bench_regress import is_lower_better

        for key in ("critical_path_total_s", "critical_path_s",
                    "critical_path_swap_lag_s"):
            assert is_lower_better(key, set()), key
        rows = compare({"critical_path_total_s": 1.0},
                       {"critical_path_total_s": 2.0},
                       {"critical_path_total_s": 30.0})
        assert rows[0]["verdict"] == "REGRESSION"
        rows = compare({"critical_path_total_s": 1.0},
                       {"critical_path_total_s": 0.5},
                       {"critical_path_total_s": 30.0})
        assert rows[0]["verdict"] == "ok"

    def test_no_higher_pattern_collision(self):
        """A critical-path wall must never match a higher-is-better
        pattern (DEFAULT_HIGHER wins over DEFAULT_LOWER, so a
        collision would silently flip the gate's direction)."""
        from scripts.bench_regress import DEFAULT_HIGHER

        for key in ("critical_path_total_s", "critical_path_s"):
            assert not any(pat in key for pat in DEFAULT_HIGHER), key


class TestIngestFamily:
    """``--family ingest`` (ISSUE 13): INGEST_r*.json parallel-ingest
    rounds gate with rates and scaling efficiency higher-is-better and
    recovery wall / duplicate window LOWER-is-better — the PR 7/8
    pattern direction/no-collision unit twins."""

    BASE = {"ingest_n1_ratings_per_s": 1_000_000.0,
            "ingest_n4_ratings_per_s": 3_000_000.0,
            "scaling_eff_n4": 0.75,
            "recovery_s": 2.0,
            "duplicate_window_batches_max": 4.0}

    def _round(self, tmp_path, name, **over):
        extra = dict(self.BASE, **over)
        value = extra.pop("value", extra["ingest_n4_ratings_per_s"])
        p = tmp_path / name
        p.write_text(json.dumps(  # the round's line shape
            {"metric": "parallel ingest ratings/s", "value": value,
             "unit": "ratings/s", "vs_baseline": 3.0, "extra": extra}))
        return str(p)

    def test_scaling_efficiency_drop_trips(self, tmp_path, capsys):
        b = self._round(tmp_path, "INGEST_r01.json")
        c = self._round(tmp_path, "INGEST_r02.json", scaling_eff_n4=0.3)
        rc = regress_main(["--family", "ingest",
                           "--baseline", b, "--current", c])
        assert rc == 1
        assert "scaling_eff_n4" in capsys.readouterr().out

    def test_recovery_blowup_trips(self, tmp_path):
        b = self._round(tmp_path, "INGEST_r01.json")
        c = self._round(tmp_path, "INGEST_r02.json", recovery_s=10.0)
        assert regress_main(["--family", "ingest",
                             "--baseline", b, "--current", c]) == 1

    def test_duplicate_window_growth_trips_tight(self, tmp_path):
        """The duplicate window is bounded by the barrier cadence —
        near-deterministic, so its threshold is tight: +1 batch on a
        4-batch window is a 25% regression."""
        b = self._round(tmp_path, "INGEST_r01.json")
        c = self._round(tmp_path, "INGEST_r02.json",
                        duplicate_window_batches_max=5.0)
        assert regress_main(["--family", "ingest",
                             "--baseline", b, "--current", c]) == 1

    def test_throughput_collapse_trips(self, tmp_path):
        b = self._round(tmp_path, "INGEST_r01.json")
        c = self._round(tmp_path, "INGEST_r02.json",
                        ingest_n4_ratings_per_s=1_000_000.0,
                        value=1_000_000.0)
        assert regress_main(["--family", "ingest",
                             "--baseline", b, "--current", c]) == 1

    def test_across_the_board_improvement_never_trips(self, tmp_path):
        b = self._round(tmp_path, "INGEST_r01.json")
        c = self._round(tmp_path, "INGEST_r02.json",
                        ingest_n1_ratings_per_s=1_500_000.0,
                        ingest_n4_ratings_per_s=5_000_000.0,
                        value=5_000_000.0, scaling_eff_n4=0.85,
                        recovery_s=0.5,
                        duplicate_window_batches_max=1.0)
        assert regress_main(["--family", "ingest",
                             "--baseline", b, "--current", c]) == 0

    def test_ingest_direction_rules(self):
        from scripts.bench_regress import INGEST_KEYS, is_lower_better

        for key in ("recovery_s", "duplicate_window_batches_max"):
            assert is_lower_better(key, set()), key
        for key in ("ingest_n1_ratings_per_s", "ingest_n4_ratings_per_s",
                    "scaling_eff_n4", "scaling_eff_n2"):
            assert not is_lower_better(key, set()), key
        assert set(self.BASE) | {"value"} == set(INGEST_KEYS)

    def test_no_higher_pattern_collision(self):
        """The lower-is-better ingest keys must never match a
        higher-is-better pattern (DEFAULT_HIGHER wins, so a collision
        would silently flip the gate's direction) — and vice versa."""
        from scripts.bench_regress import DEFAULT_HIGHER, DEFAULT_LOWER

        for key in ("recovery_s", "duplicate_window_batches_max"):
            assert not any(pat in key for pat in DEFAULT_HIGHER), key
        for key in ("scaling_eff_n4", "ingest_n4_ratings_per_s"):
            assert not any(pat in key for pat in DEFAULT_LOWER), key

    def test_contention_direction_rules(self):
        """The ISSUE-14 concurrency keys: a rising Amdahl serial
        fraction or per-rung lock-wait total is a serialization
        regression — LOWER is better, at every N suffix the bench
        emits."""
        from scripts.bench_regress import is_lower_better

        for key in ("serial_fraction_n2", "serial_fraction_n8",
                    "lock_wait_s_total_n2", "lock_wait_s_total_n4"):
            assert is_lower_better(key, set()), key

    def test_contention_no_direction_collision(self):
        """serial_fraction/lock_wait must not match any
        higher-is-better pattern (which would win and flip the
        direction), and no existing higher-is-better ingest key may
        match the new lower patterns."""
        from scripts.bench_regress import DEFAULT_HIGHER, DEFAULT_LOWER

        for key in ("serial_fraction_n4", "lock_wait_s_total_n4"):
            assert not any(pat in key for pat in DEFAULT_HIGHER), key
        for key in ("ingest_n4_ratings_per_s", "scaling_eff_n4",
                    "qps_at_slo", "effective_hbm_gbs"):
            assert not any(pat in key
                           for pat in ("serial_fraction", "lock_wait")), key
        assert "serial_fraction" in DEFAULT_LOWER
        assert "lock_wait" in DEFAULT_LOWER

    def test_serial_fraction_rise_trips_via_key(self, tmp_path):
        """The watch-via---key contract the CI step uses on rounds that
        carry the contention extras (the committed pre-ISSUE-14 round
        doesn't, so the keys stay out of the family default set)."""
        b = self._round(tmp_path, "INGEST_r01.json",
                        serial_fraction_n4=0.10)
        c = self._round(tmp_path, "INGEST_r02.json",
                        serial_fraction_n4=0.40)
        assert regress_main(["--family", "ingest",
                             "--baseline", b, "--current", c,
                             "--key", "serial_fraction_n4=50"]) == 1
        # an IMPROVED (dropping) serial fraction never trips
        assert regress_main(["--family", "ingest",
                             "--baseline", c, "--current", b,
                             "--key", "serial_fraction_n4=50"]) == 0


class TestRankShardDirection:
    """ISSUE 16: the rank-sharded 2-D mesh keys pod_dryrun emits into
    the MULTICHIP rounds — throughput higher-is-better, per-device
    factor+catalog bytes (and the ratio vs model=1) LOWER-is-better.
    Watched via --key, NOT in MULTICHIP_KEYS: rounds before r07 lack
    the keys, and a default watch key the baseline can't contain is
    permanent "missing" noise (the PR 10/13 lesson)."""

    def test_rank_shard_direction_rules(self):
        from scripts.bench_regress import is_lower_better

        for key in ("rank_shard_bytes_per_device",
                    "rank_shard_bytes_per_device_m1",
                    "rank_shard_bytes_ratio_vs_m1"):
            assert is_lower_better(key, set()), key
        for key in ("rank_sharded_ratings_per_s",
                    "rank_sharded_8x2_ratings_per_s"):
            assert not is_lower_better(key, set()), key

    def test_rank_shard_no_direction_collision(self):
        """The bytes keys must not match any higher-is-better pattern
        (DEFAULT_HIGHER wins over DEFAULT_LOWER, so a collision would
        silently flip the gate's direction), and the throughput keys
        must not match the new lower pattern — 'rank_shard_bytes' is
        NOT a substring of 'rank_sharded_*'."""
        from scripts.bench_regress import DEFAULT_HIGHER, DEFAULT_LOWER

        for key in ("rank_shard_bytes_per_device",
                    "rank_shard_bytes_ratio_vs_m1"):
            assert not any(pat in key for pat in DEFAULT_HIGHER), key
        for key in ("rank_sharded_ratings_per_s",
                    "rank_sharded_8x2_ratings_per_s"):
            assert not any(pat in key for pat in DEFAULT_LOWER), key
        assert "rank_shard_bytes" in DEFAULT_LOWER
        assert "rank_sharded" in DEFAULT_HIGHER

    def test_rank_shard_keys_not_in_family_watch_set(self):
        """The PR 10/13 lesson: new keys gate via --key until every
        committed round in the diff window carries them."""
        from scripts.bench_regress import MULTICHIP_KEYS

        for key in MULTICHIP_KEYS:
            assert "rank_shard" not in key, key

    def _round(self, tmp_path, name, **over):
        base = {"n_devices": 16, "train_ratings_per_s": 450_000.0,
                "als_rows_per_s": 2_600.0, "max_pad_ratio": 1.104,
                "layout_mb": 144.0,
                "rank_sharded_ratings_per_s": 320_000.0,
                "rank_shard_bytes_per_device": 2_031_616.0,
                "rank_shard_bytes_ratio_vs_m1": 0.256}
        base.update(over)
        p = tmp_path / name
        p.write_text(json.dumps(base))
        return str(p)

    def test_footprint_growth_trips_via_key(self, tmp_path):
        b = self._round(tmp_path, "MULTICHIP_r07.json")
        c = self._round(tmp_path, "MULTICHIP_r08.json",
                        rank_shard_bytes_per_device=4_000_000.0)
        assert regress_main(["--family", "multichip",
                             "--baseline", b, "--current", c,
                             "--key", "rank_shard_bytes_per_device=20"
                             ]) == 1
        # SHRINKING per-device bytes is the improvement direction
        assert regress_main(["--family", "multichip",
                             "--baseline", c, "--current", b,
                             "--key", "rank_shard_bytes_per_device=20"
                             ]) == 0

    def test_rank_sharded_throughput_collapse_trips_via_key(self, tmp_path):
        b = self._round(tmp_path, "MULTICHIP_r07.json")
        c = self._round(tmp_path, "MULTICHIP_r08.json",
                        rank_sharded_ratings_per_s=100_000.0)
        assert regress_main(["--family", "multichip",
                             "--baseline", b, "--current", c,
                             "--key", "rank_sharded_ratings_per_s=30"
                             ]) == 1


class TestTierFamily:
    """``--family tier`` (ISSUE 17): TIERED_r*.json tiered-store
    rounds gate with the tiered ingest rate / hit rate / fraction-of-
    HBM higher-is-better and prefetch stall / eviction count
    LOWER-is-better — the direction/no-collision/not-in-family twins
    the ingest and rank-shard families carry."""

    BASE = {"tier_hit_rate": 0.93,
            "tiered_vs_hbm_frac": 0.78,
            "tier_prefetch_wait_s": 0.4,
            "tier_evictions": 900.0}

    def _round(self, tmp_path, name, **over):
        extra = dict(self.BASE, **over)
        value = extra.pop("value", 400_000.0)
        p = tmp_path / name
        p.write_text(json.dumps(  # the round's line shape
            {"metric": "tiered ingest ratings/s", "value": value,
             "unit": "ratings/s", "vs_baseline": 1.0, "extra": extra}))
        return str(p)

    def test_hit_rate_drop_trips_tight(self, tmp_path, capsys):
        """Same Zipfian trace + same slot budget → the hit rate is
        near-deterministic, so its threshold is tight (10%)."""
        b = self._round(tmp_path, "TIERED_r01.json")
        c = self._round(tmp_path, "TIERED_r02.json", tier_hit_rate=0.70)
        rc = regress_main(["--family", "tier",
                           "--baseline", b, "--current", c])
        assert rc == 1
        assert "tier_hit_rate" in capsys.readouterr().out

    def test_prefetch_stall_blowup_trips(self, tmp_path):
        b = self._round(tmp_path, "TIERED_r01.json")
        c = self._round(tmp_path, "TIERED_r02.json",
                        tier_prefetch_wait_s=2.5)
        assert regress_main(["--family", "tier",
                             "--baseline", b, "--current", c]) == 1

    def test_eviction_blowup_trips(self, tmp_path):
        b = self._round(tmp_path, "TIERED_r01.json")
        c = self._round(tmp_path, "TIERED_r02.json",
                        tier_evictions=2_000.0)
        assert regress_main(["--family", "tier",
                             "--baseline", b, "--current", c]) == 1

    def test_throughput_collapse_trips(self, tmp_path):
        b = self._round(tmp_path, "TIERED_r01.json")
        c = self._round(tmp_path, "TIERED_r02.json",
                        value=200_000.0, tiered_vs_hbm_frac=0.4)
        assert regress_main(["--family", "tier",
                             "--baseline", b, "--current", c]) == 1

    def test_across_the_board_improvement_never_trips(self, tmp_path):
        b = self._round(tmp_path, "TIERED_r01.json")
        c = self._round(tmp_path, "TIERED_r02.json",
                        value=600_000.0, tier_hit_rate=0.98,
                        tiered_vs_hbm_frac=0.95,
                        tier_prefetch_wait_s=0.05, tier_evictions=100.0)
        assert regress_main(["--family", "tier",
                             "--baseline", b, "--current", c]) == 0

    def test_tier_direction_rules(self):
        from scripts.bench_regress import TIER_KEYS, is_lower_better

        for key in ("tier_prefetch_wait_s", "tier_evictions",
                    "tier_evictions_total"):
            assert is_lower_better(key, set()), key
        for key in ("tier_hit_rate", "tiered_vs_hbm_frac",
                    "tiered_ratings_per_s"):
            assert not is_lower_better(key, set()), key
        assert set(self.BASE) | {"value"} == set(TIER_KEYS)

    def test_tier_no_direction_collision(self):
        """tier_prefetch_wait_s must not match the _per_s HIGHER
        pattern ("_pre" != "_per" — DEFAULT_HIGHER wins, so a
        collision would silently flip the gate's direction), and the
        higher-is-better tier keys must not match any lower pattern."""
        from scripts.bench_regress import DEFAULT_HIGHER, DEFAULT_LOWER

        for key in ("tier_prefetch_wait_s", "tier_evictions"):
            assert not any(pat in key for pat in DEFAULT_HIGHER), key
        for key in ("tier_hit_rate", "tiered_vs_hbm_frac",
                    "tiered_ratings_per_s"):
            assert not any(pat in key for pat in DEFAULT_LOWER), key
        assert "prefetch_wait" in DEFAULT_LOWER
        assert "tier_evictions" in DEFAULT_LOWER
        assert "tier_hit_rate" in DEFAULT_HIGHER

    def test_tier_keys_not_in_other_families(self):
        """The tier watch set is its own family — tier keys must not
        leak into the bench/ingest default sets (the PR 10/13 lesson:
        a default watch key a family's committed rounds can't contain
        is permanent "missing" noise)."""
        from scripts.bench_regress import (
            DEFAULT_KEYS,
            FAMILIES,
            INGEST_KEYS,
            TIER_KEYS,
        )

        for key in list(DEFAULT_KEYS) + list(INGEST_KEYS):
            assert "tier" not in key, key
        prefix, keys = FAMILIES["tier"]
        assert prefix == "TIERED"
        assert keys is TIER_KEYS


class TestTransferDirections:
    """Transfer-plane keys (ISSUE 18): ``retrace`` /
    ``implicit_transfers`` / ``transfer_wait`` joined DEFAULT_LOWER —
    the direction/no-collision/not-in-family twins the tier and ingest
    families carry. CI watches these via explicit ``--key`` only:
    committed rounds predating ISSUE 18 lack the keys, and a default
    watch key the baseline can't contain is permanent "missing" noise
    (the PR 10/13 lesson)."""

    TRANSFER_KEYS = ("retrace_total", "implicit_transfers_total",
                     "transfer_wait_s_total")

    def test_transfer_direction_rules(self):
        from scripts.bench_regress import is_lower_better

        for key in self.TRANSFER_KEYS + ("retraces_steady",
                                         "transfer_wait_s"):
            assert is_lower_better(key, set()), key

    def test_transfer_no_direction_collision(self):
        """None of the transfer keys may match a HIGHER pattern
        (DEFAULT_HIGHER wins, so a collision silently flips the gate's
        direction). In particular "transfer_wait" vs the _per_s HIGHER
        rule: "wait" != "_per_s", pinned here like tier's "_pre"."""
        from scripts.bench_regress import DEFAULT_HIGHER, DEFAULT_LOWER

        for key in self.TRANSFER_KEYS:
            assert not any(pat in key for pat in DEFAULT_HIGHER), key
        for pat in ("retrace", "implicit_transfers", "transfer_wait"):
            assert pat in DEFAULT_LOWER

    def test_transfer_keys_not_in_family_watch_sets(self):
        """Explicit --key only — no family default set may carry a
        transfer key."""
        from scripts.bench_regress import FAMILIES

        for fam, (_, keys) in FAMILIES.items():
            for key in keys:
                for pat in ("retrace", "implicit_transfer",
                            "transfer_wait"):
                    assert pat not in key, (fam, key)

    def test_retrace_blowup_trips_via_key(self, tmp_path):
        """A steady-state retrace regression on a round that carries
        the key trips through the LOWER direction rule."""
        for name, retraces in (("TIERED_r01.json", 1.0),
                               ("TIERED_r02.json", 8.0)):
            (tmp_path / name).write_text(json.dumps(
                {"metric": "tiered ingest ratings/s", "value": 400_000.0,
                 "unit": "ratings/s",
                 "extra": {"tier_hit_rate": 0.93,
                           "tiered_vs_hbm_frac": 0.78,
                           "tier_prefetch_wait_s": 0.4,
                           "tier_evictions": 900.0,
                           "retrace_total": retraces,
                           "implicit_transfers_total": 0.0}}))
        b = str(tmp_path / "TIERED_r01.json")
        c = str(tmp_path / "TIERED_r02.json")
        assert regress_main(["--family", "tier",
                             "--baseline", b, "--current", c,
                             "--key", "retrace_total=50"]) == 1
        # the improvement direction (fewer retraces) never trips
        assert regress_main(["--family", "tier",
                             "--baseline", c, "--current", b,
                             "--key", "retrace_total=50"]) == 0


class TestRolloutDirections:
    """Rollout-budget keys (ISSUE 19): ``burn_rate`` /
    ``verdict_latency`` joined DEFAULT_LOWER and
    ``error_budget_remaining`` DEFAULT_HIGHER — the direction /
    no-collision / not-in-family twins the transfer and rank-shard
    entries carry. CI watches these via explicit ``--key`` only:
    SERVING_r01 predates the plane, and a default watch key the
    baseline can't contain is permanent "missing" noise (the
    PR 10/13 lesson)."""

    LOWER_KEYS = ("slo_burn_rate_fast", "slo_burn_rate_slow",
                  "verdict_latency_batches")

    def test_rollout_direction_rules(self):
        from scripts.bench_regress import is_lower_better

        for key in self.LOWER_KEYS:
            assert is_lower_better(key, set()), key
        assert not is_lower_better("error_budget_remaining", set())

    def test_rollout_no_direction_collision(self):
        """The burn/verdict keys must not match a HIGHER pattern
        (DEFAULT_HIGHER wins, so a collision silently flips the gate's
        direction), and error_budget_remaining must not match a LOWER
        pattern — in particular "_rmse" does not occur in it."""
        from scripts.bench_regress import DEFAULT_HIGHER, DEFAULT_LOWER

        for key in self.LOWER_KEYS:
            assert not any(pat in key for pat in DEFAULT_HIGHER), key
        assert not any(pat in "error_budget_remaining"
                       for pat in DEFAULT_LOWER)
        for pat in ("burn_rate", "verdict_latency"):
            assert pat in DEFAULT_LOWER
        assert "error_budget_remaining" in DEFAULT_HIGHER

    def test_rollout_keys_not_in_family_watch_sets(self):
        """Explicit --key only — no family default set may carry a
        rollout key."""
        from scripts.bench_regress import FAMILIES

        for fam, (_, keys) in FAMILIES.items():
            for key in keys:
                for pat in ("burn_rate", "verdict_latency",
                            "error_budget"):
                    assert pat not in key, (fam, key)

    def test_burn_rate_blowup_trips_via_key(self, tmp_path):
        """A fast-burn regression on a round that carries the key
        trips through the LOWER direction rule; the remaining-budget
        key gates through the HIGHER rule."""
        for name, burn, remaining in (("SERVING_r01.json", 0.5, 0.95),
                                      ("SERVING_r02.json", 4.0, 0.20)):
            (tmp_path / name).write_text(json.dumps(
                {"metric": "serving users/s", "value": 300.0,
                 "unit": "users/s",
                 "extra": {"qps_at_slo": 12.0, "p99_ms": 80.0,
                           "recall_at_10": 0.99, "shed_frac": 0.0,
                           "slo_burn_rate_fast": burn,
                           "error_budget_remaining": remaining}}))
        b = str(tmp_path / "SERVING_r01.json")
        c = str(tmp_path / "SERVING_r02.json")
        assert regress_main(["--family", "serving",
                             "--baseline", b, "--current", c,
                             "--key", "slo_burn_rate_fast=50"]) == 1
        assert regress_main(["--family", "serving",
                             "--baseline", b, "--current", c,
                             "--key", "error_budget_remaining=50"]) == 1
        # the improvement direction (less burn, more budget) never
        # trips
        assert regress_main(["--family", "serving",
                             "--baseline", c, "--current", b,
                             "--key", "slo_burn_rate_fast=50",
                             "--key", "error_budget_remaining=50"]) == 0


class TestRequestStageDirections:
    """Request-plane keys (ISSUE 20): ``request_stage`` /
    ``queue_wait`` joined DEFAULT_LOWER — the direction /
    no-collision / not-in-family twins the rollout entries carry. CI
    watches these via explicit ``--key`` only: committed rounds
    predating the plane lack the keys, and a default watch key the
    baseline can't contain is permanent "missing" noise (the
    PR 10/13 lesson)."""

    LOWER_KEYS = ("request_stage_gather_s_p99",
                  "request_stage_score_stage1_s_p50",
                  "queue_wait_s_p99")

    def test_request_stage_direction_rules(self):
        from scripts.bench_regress import is_lower_better

        for key in self.LOWER_KEYS:
            assert is_lower_better(key, set()), key

    def test_request_stage_no_direction_collision(self):
        """A stage wall must not match a HIGHER pattern
        (DEFAULT_HIGHER wins, so a collision silently flips the
        gate's direction)."""
        from scripts.bench_regress import DEFAULT_HIGHER, DEFAULT_LOWER

        for key in self.LOWER_KEYS:
            assert not any(pat in key for pat in DEFAULT_HIGHER), key
        for pat in ("request_stage", "queue_wait"):
            assert pat in DEFAULT_LOWER

    def test_request_stage_keys_not_in_family_watch_sets(self):
        """Explicit --key only — no family default set may carry a
        request-plane key."""
        from scripts.bench_regress import FAMILIES

        for fam, (_, keys) in FAMILIES.items():
            for key in keys:
                for pat in ("request_stage", "queue_wait"):
                    assert pat not in key, (fam, key)

    def test_stage_p99_blowup_trips_via_key(self, tmp_path):
        """A gather-stage p99 regression on a round that carries the
        key trips through the LOWER direction rule."""
        for name, p99 in (("SERVING_r02.json", 0.002),
                          ("SERVING_r03.json", 0.080)):
            (tmp_path / name).write_text(json.dumps(
                {"metric": "serving users/s", "value": 300.0,
                 "unit": "users/s",
                 "extra": {"qps_at_slo": 12.0, "p99_ms": 80.0,
                           "recall_at_10": 0.99, "shed_frac": 0.0,
                           "request_stage_gather_s_p99": p99,
                           "queue_wait_s_p99": p99}}))
        b = str(tmp_path / "SERVING_r02.json")
        c = str(tmp_path / "SERVING_r03.json")
        assert regress_main(["--family", "serving",
                             "--baseline", b, "--current", c,
                             "--key", "request_stage_gather_s_p99=50"
                             ]) == 1
        assert regress_main(["--family", "serving",
                             "--baseline", b, "--current", c,
                             "--key", "queue_wait_s_p99=50"]) == 1
        # the improvement direction (faster stages) never trips
        assert regress_main(["--family", "serving",
                             "--baseline", c, "--current", b,
                             "--key", "request_stage_gather_s_p99=50",
                             "--key", "queue_wait_s_p99=50"]) == 0
