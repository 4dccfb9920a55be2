"""REQUEST plane (``obs/requests.py``, ISSUE 20): per-request stage
decomposition, tail-based exemplar sampling, ``/slowz``.

The acceptance pin everything here defends: a REAL ``ServingEngine``
traffic run (two-stage retrieval, admission armed, at least one shed
and one degraded request) serves ``/slowz`` over a REAL socket where
EVERY exemplar's stage sum reconciles exactly (``math.fsum`` equality,
not approx) against its measured wall, the slowest injected request is
present worst-first with its dominant stage correctly named, and the
plane's violation accounting agrees with the engine's ``SLOTracker``
over the same window (both priced the IDENTICAL ``end - ts`` floats).
Covered besides: ledger mark/finish math, the reservoir policy
(violating/shed/degraded always kept, slowest-N floor for healthy
windows), the zero-cost disabled path (no clock reads, no ledger
allocation), ``Tracer.complete`` span trees, the server route +
``/`` index, fleet worst-first merge, postmortem bundles (v8
write/load, archived v7 synthesized), ``RequestStageCheck`` +
``HealthMonitor.watch_requests``, and the ``--requests`` renderer.
"""

import json
import math
import time

import numpy as np
import pytest

from large_scale_recommendation_tpu import obs
from large_scale_recommendation_tpu.obs.health import HealthMonitor
from large_scale_recommendation_tpu.obs.requests import (
    STAGES,
    FlushLedger,
    RequestStageCheck,
    RequestTelemetry,
    _pow2_bucket,
    get_requests,
    request_scope,
    set_requests,
    slowz,
)
from large_scale_recommendation_tpu.obs.server import ObsServer, http_get
from large_scale_recommendation_tpu.obs.transfers import _NULL_CONTEXT

RANK = 8


@pytest.fixture(autouse=True)
def _reset_planes():
    """Tests install telemetries — never leak the plane into the next
    test."""
    prev = get_requests()
    yield
    set_requests(prev)


def _telemetry(**kw):
    kw.setdefault("objective", 0.9)
    kw.setdefault("window", 64)
    kw.setdefault("max_exemplars", 8)
    kw.setdefault("slow_keep", 4)
    return RequestTelemetry(0.1, **kw)


def _model(num_users=50, num_items=256, seed=20):
    import jax.numpy as jnp

    from large_scale_recommendation_tpu.data.blocking import flat_index
    from large_scale_recommendation_tpu.models.mf import MFModel

    rng = np.random.default_rng(seed)
    return MFModel(
        U=jnp.asarray(rng.normal(size=(num_users, RANK)).astype(np.float32)),
        V=jnp.asarray(rng.normal(size=(num_items, RANK)).astype(np.float32)),
        users=flat_index(np.arange(num_users, dtype=np.int64)),
        items=flat_index(np.arange(num_items, dtype=np.int64)))


def _noted_flush(t, walls, *, stage_s=0.01, version=1, degraded=False,
                 rows=None, admission_level=None):
    """Drive one synthetic flush through the real noting path: the
    oldest request waited ``max(walls)``, the flush itself took
    ``stage_s`` of gather."""
    end = time.perf_counter()
    t0 = end - stage_s
    led = t.ledger(t0)
    led.mark("gather", t0 + stage_s)
    stamps = tuple(end - w for w in sorted(walls, reverse=True))
    t.note_flush(led, end, stamps, version=version, degraded=degraded,
                 rows=rows, admission_level=admission_level)
    return end, stamps


# --------------------------------------------------------------------------
# Ledger math: exact-by-construction reconciliation
# --------------------------------------------------------------------------


class TestLedgerMath:
    def test_marks_partition_the_wall_exactly(self):
        led = FlushLedger(100.0)
        led.mark("batch_form", 100.25)
        led.mark("gather", 100.5)
        led.mark("score_stage1", 101.0)
        total = led.finish(101.1)
        assert total == 101.1 - 100.0
        # the fsum of the stages IS the wall — equality, not approx
        assert math.fsum(led.stages.values()) == total
        assert led.stages["batch_form"] == 0.25
        assert led.stages["gather"] == 0.25
        assert led.stages["score_stage1"] == 0.5
        # the residual landed in host_post
        assert led.stages["host_post"] == pytest.approx(0.1)

    def test_residual_stage_is_configurable(self):
        led = FlushLedger(0.0)
        led.mark("score_stage1", 1.0)
        led.finish(1.5, residual_stage="topk_merge")
        assert led.stages["topk_merge"] == pytest.approx(0.5)
        assert math.fsum(led.stages.values()) == 1.5

    def test_repeated_marks_accumulate(self):
        led = FlushLedger(0.0)
        led.mark("gather", 1.0)
        led.mark("score_stage1", 2.0)
        led.mark("gather", 2.5)  # second chunk's gather
        led.finish(3.0)
        assert led.stages["gather"] == 1.5
        assert math.fsum(led.stages.values()) == 3.0

    def test_shared_clock_read_is_honored(self):
        """Passing ``now`` must not read the clock — the engine shares
        its assembly-histogram read with the batch_form mark."""
        led = FlushLedger(5.0)
        led.mark("batch_form", 7.0)
        assert led.stages["batch_form"] == 2.0

    def test_per_request_sum_equals_the_slo_float(self):
        """The flush-level contract lifted per request: for awkward
        floats (a submit stamp far from the flush), the noted stage
        values still fsum to the IDENTICAL ``end - ts`` wall."""
        t = _telemetry()
        end, stamps = _noted_flush(
            t, [0.3, 0.0421739214, 1e-9], stage_s=0.0137)
        for ex in t.exemplars():
            assert math.fsum(ex["stages"].values()) == ex["wall_s"]
        walls = sorted((end - ts for ts in stamps), reverse=True)
        got = sorted((e["wall_s"] for e in t.exemplars()), reverse=True)
        assert got == walls[:len(got)]

    def test_pow2_bucket(self):
        assert [_pow2_bucket(n) for n in (0, 1, 2, 3, 8, 9, 1000)] == \
            [1, 1, 2, 4, 8, 16, 1024]


# --------------------------------------------------------------------------
# Reservoir policy
# --------------------------------------------------------------------------


class TestReservoir:
    def test_violating_always_kept_newest_win(self):
        t = _telemetry(max_exemplars=3)
        for i in range(6):
            _noted_flush(t, [0.5 + i], version=i)  # all violate 0.1
        ex = [e for e in t.exemplars() if e["kind"] == "violating"]
        assert len(ex) == 3  # bounded
        assert t.kept_evicted == 3  # evictions counted, not silent
        # newest win: the survivors are the three latest versions
        assert sorted(e["catalog_version"] for e in ex) == [3, 4, 5]

    def test_shed_always_kept_with_rung_and_burn(self):
        t = _telemetry()
        t.note_shed(version=7, level="shed", burn=5.5, queue_depth=3)
        (ex,) = t.exemplars()
        assert ex["kind"] == "shed"
        assert ex["admission_level"] == "shed"
        assert ex["burn_rate"] == 5.5
        assert ex["queue_depth"] == 3
        assert ex["catalog_version"] == 7
        assert ex["stages"] == {}  # never entered a flush
        assert t.shed == 1

    def test_degraded_kept_even_within_slo(self):
        t = _telemetry()
        _noted_flush(t, [0.01], degraded=True)  # inside the 0.1 target
        (ex,) = t.exemplars()
        assert ex["kind"] == "degraded" and ex["degraded"] is True
        assert ex["violating"] is False

    def test_healthy_requests_keep_only_the_slowest_n(self):
        t = _telemetry(slow_keep=3)
        for w in (0.01, 0.05, 0.02, 0.08, 0.03, 0.001):
            _noted_flush(t, [w], stage_s=w / 2)
        ex = t.exemplars()
        assert all(e["kind"] == "slow" for e in ex)
        assert len(ex) == 3
        # worst-first, and the floor replacement kept the slowest three
        got = [round(e["wall_s"], 3) for e in ex]
        assert got == sorted(got, reverse=True)
        assert got[0] == pytest.approx(0.08, abs=1e-3)
        assert 0.001 not in [round(w, 3) for w in got]

    def test_queue_depth_is_the_submit_index(self):
        t = _telemetry()
        _noted_flush(t, [0.3, 0.2, 0.15])
        depths = sorted(e["queue_depth"] for e in t.exemplars())
        assert depths == [0, 1, 2]

    def test_rows_annotate_the_pow2_bucket(self):
        t = _telemetry()
        _noted_flush(t, [0.3, 0.2], rows=[5, 8])
        buckets = sorted(e["bucket"] for e in t.exemplars())
        assert buckets == [8, 8]

    def test_exemplars_limit_and_order(self):
        t = _telemetry()
        _noted_flush(t, [0.5, 0.4, 0.3, 0.2])
        top2 = t.exemplars(limit=2)
        assert len(top2) == 2
        assert top2[0]["wall_s"] > top2[1]["wall_s"]

    def test_snapshot_counters_and_burn(self):
        t = _telemetry()  # objective 0.9 -> budget 0.1
        _noted_flush(t, [0.5])  # violates
        for _ in range(3):
            _noted_flush(t, [0.01])
        snap = t.snapshot()
        assert snap["count"] == 4
        assert snap["violations"] == 1
        assert snap["window_fill"] == 4
        assert snap["burn_rate"] == pytest.approx((1 / 4) / 0.1)
        assert snap["p99_ms"] >= snap["p50_ms"] > 0
        # fractions sum to 1 over a non-empty window
        assert math.fsum(snap["stage_frac"].values()) == \
            pytest.approx(1.0)
        assert snap["dominant_stage"] in STAGES

    def test_window_eviction_keeps_sums_consistent(self):
        t = _telemetry(window=4)
        for w in (0.5, 0.5, 0.01, 0.01, 0.01, 0.01):
            _noted_flush(t, [w])
        snap = t.snapshot()
        assert snap["window_fill"] == 4
        # both violations rolled out of the window
        assert snap["burn_rate"] == 0.0
        assert snap["violations"] == 2  # lifetime survives the window

    def test_stage_quantiles_shape(self):
        t = _telemetry()
        for _ in range(5):
            _noted_flush(t, [0.02])
        q = t.stage_quantiles()
        assert set(q) == set(STAGES)
        assert q["gather"]["p99"] >= q["gather"]["p50"] > 0.0
        assert q["score_stage2"]["p99"] == 0.0

    def test_reset_clears_everything(self):
        t = _telemetry()
        _noted_flush(t, [0.5])
        t.note_shed(version=1)
        t.reset()
        snap = t.snapshot()
        assert snap["count"] == snap["violations"] == snap["shed"] == 0
        assert snap["exemplars"] == []

    def test_validation(self):
        with pytest.raises(ValueError):
            RequestTelemetry(0.1, objective=1.0)
        with pytest.raises(ValueError):
            RequestTelemetry(0.1, window=0)
        with pytest.raises(ValueError):
            RequestTelemetry(0.1, max_exemplars=0)
        with pytest.raises(ValueError):
            RequestTelemetry(0.1, slow_keep=0)
        with pytest.raises(ValueError):
            RequestStageCheck(_telemetry(), frac_bar=0.0)


# --------------------------------------------------------------------------
# Plane lifecycle & the zero-cost pin
# --------------------------------------------------------------------------


class TestPlaneLifecycle:
    def test_default_is_none_and_slowz_notes(self, null_obs):
        assert get_requests() is None
        doc = slowz()
        assert "enable_requests" in doc["note"]
        assert doc["exemplars"] == []

    def test_disabled_scope_is_the_shared_singleton(self, null_obs,
                                                    monkeypatch):
        """The TestNullPathZeroWork pin for this plane: with no
        telemetry installed ``request_scope`` hands out the one
        module-level null context — no allocation, and NO clock read
        (pinned by making the clock explode)."""
        import time as _time

        def _boom():  # pragma: no cover - must never run
            raise AssertionError("clock read on the disabled path")

        monkeypatch.setattr(_time, "perf_counter", _boom)
        assert request_scope(1) is _NULL_CONTEXT
        with request_scope(1):
            pass

    def test_engine_binds_none_and_allocates_no_ledger(self, null_obs):
        from large_scale_recommendation_tpu.serving import ServingEngine

        eng = ServingEngine(_model(), k=4)
        assert eng._requests is None
        # the flush path runs ledger-free end to end
        eng.submit(np.arange(4))
        assert eng.flush()

    def test_enable_requests_installs_and_disable_clears(self, null_obs):
        t = obs.enable_requests(0.2, objective=0.95, window=32,
                                max_exemplars=4, slow_keep=2)
        try:
            assert t is get_requests()
            assert t.target_s == 0.2 and t.objective == 0.95
            assert request_scope(3) is not _NULL_CONTEXT
        finally:
            obs.disable()
        assert get_requests() is None

    def test_request_scope_times_and_notes(self, null_obs):
        t = _telemetry()
        set_requests(t)
        with request_scope(version=9) as scope:
            scope.mark("gather")
        snap = t.snapshot()
        assert snap["count"] == 1
        (ex,) = snap["exemplars"]
        assert ex["catalog_version"] == 9
        assert ex["stages"]["gather"] > 0.0
        assert math.fsum(ex["stages"].values()) == ex["wall_s"]


# --------------------------------------------------------------------------
# Tracer.complete: the span-tree emission primitive
# --------------------------------------------------------------------------


class TestTracerComplete:
    def test_complete_event_shape_and_span_tree(self, null_obs):
        from large_scale_recommendation_tpu.obs.trace import Tracer

        tracer = Tracer()
        t0 = time.perf_counter() - 0.25
        parent = tracer.complete("request", t0, t0 + 0.2,
                                 cat="request", tid=42, kind="slow")
        child = tracer.complete("request/gather", t0, t0 + 0.1,
                                cat="request_stage", tid=42,
                                parent_span_id=parent)
        assert parent and child and parent != child
        ev = [e for e in tracer.events() if e.get("ph") == "X"]
        assert len(ev) == 2
        root = next(e for e in ev if e["name"] == "request")
        assert root["dur"] == pytest.approx(0.2e6)
        assert root["tid"] == 42
        assert root["args"]["kind"] == "slow"
        leaf = next(e for e in ev if e["name"] == "request/gather")
        assert leaf["args"]["parent_span_id"] == parent

    def test_complete_respects_max_events(self, null_obs):
        from large_scale_recommendation_tpu.obs.trace import Tracer

        tracer = Tracer(max_events=2)
        assert tracer.complete("a", 0.0, 1.0) is not None
        assert tracer.complete("b", 0.0, 1.0) is not None
        assert tracer.complete("c", 0.0, 1.0) is None
        assert tracer.dropped == 1

    def test_null_tracer_complete_is_none(self):
        from large_scale_recommendation_tpu.obs.trace import NullTracer

        assert NullTracer().complete("x", 0.0, 1.0) is None
        assert NullTracer().complete_tree("x", 0.0, 1.0,
                                          [("x/a", 0.5)]) is None

    def test_complete_tree_nests_exactly_at_epoch_magnitudes(self,
                                                             null_obs):
        """Sibling boundaries must be BITWISE abutting in the stored
        microsecond floats: the trace origin anchors perf_counter to
        the epoch (~1e15 us, one ulp ~0.25 us), so converting each
        child boundary from seconds independently can un-nest abutting
        siblings and fail ``validate_chrome_trace`` — the layout has
        to happen in the event's own microsecond space."""
        from large_scale_recommendation_tpu.obs.trace import (
            Tracer,
            validate_chrome_trace,
        )

        tracer = Tracer()
        t0 = time.perf_counter()
        # irrational-ish stage walls maximize rounding exposure
        stages = [("request/queue_wait", 0.001234567),
                  ("request/batch_form", 0.0007654321),
                  ("request/gather", 0.0601112131),
                  ("request/score_stage1", 0.0023456789),
                  ("request/topk_merge", 0.0009876543),
                  ("request/host_post", 0.0004321987)]
        wall = math.fsum(dt for _, dt in stages)
        for i in range(50):
            span = tracer.complete_tree(
                "request", t0 + i * 0.1, t0 + i * 0.1 + wall, stages,
                cat="request", child_cat="request_stage", tid=7000 + i)
            assert span is not None
        complete = validate_chrome_trace(
            {"traceEvents": tracer.events()})
        kids = [e for e in complete if e["cat"] == "request_stage"]
        assert len(kids) == 50 * len(stages)
        # per-tid exact abutment: child N+1 starts at the very float
        # child N's ts + dur produces
        by_tid = {}
        for e in kids:
            by_tid.setdefault(e["tid"], []).append(e)
        for evs in by_tid.values():
            evs.sort(key=lambda e: e["ts"])
            for a, b in zip(evs, evs[1:]):
                assert a["ts"] + a["dur"] == b["ts"]

    def test_exemplar_emits_perfetto_loadable_tree(self, null_obs):
        """A kept exemplar renders in the trace buffer: a parent
        ``request`` complete-event plus stage children whose durs sum
        to the parent's."""
        reg, tracer = obs.enable()
        try:
            t = _telemetry()
            set_requests(t)
            _noted_flush(t, [0.5], stage_s=0.2)
            ev = [e for e in tracer.events() if e.get("ph") == "X"]
            root = next(e for e in ev if e["name"] == "request")
            kids = [e for e in ev if e["cat"] == "request_stage"]
            assert kids
            assert sum(k["dur"] for k in kids) == \
                pytest.approx(root["dur"], rel=1e-6)
            assert all(k["args"]["parent_span_id"] ==
                       root["args"]["span_id"] for k in kids)
        finally:
            obs.disable()


# --------------------------------------------------------------------------
# Server route, health gate
# --------------------------------------------------------------------------


class TestServerAndHealth:
    def test_slowz_route_and_index(self, null_obs):
        obs.enable()
        try:
            t = obs.enable_requests(0.1, objective=0.9)
            _noted_flush(t, [0.5, 0.3])
            with ObsServer() as server:
                code, body = http_get(server.url + "/slowz")
                lcode, lbody = http_get(server.url + "/slowz?limit=1")
                bcode, _ = http_get(server.url + "/slowz?limit=junk")
                icode, ibody = http_get(server.url + "/")
        finally:
            obs.disable()
        assert code == 200
        doc = json.loads(body)
        assert doc["count"] == 2 and len(doc["exemplars"]) == 2
        assert len(json.loads(lbody)["exemplars"]) == 1
        assert bcode == 400
        assert "/slowz" in json.loads(ibody)["routes"]

    def test_slowz_without_plane_is_a_note(self, null_obs):
        obs.enable()
        try:
            with ObsServer() as server:
                code, body = http_get(server.url + "/slowz")
        finally:
            obs.disable()
        assert code == 200
        assert "enable_requests" in json.loads(body)["note"]

    def test_stage_check_needs_burn_and_domination(self, null_obs):
        t = _telemetry()
        check = RequestStageCheck(t, frac_bar=0.5)
        assert check().status == "ok"  # idle plane
        # dominant stage but inside budget: still OK (just a profile)
        _noted_flush(t, [0.01], stage_s=0.009)
        res = check()
        assert res.status == "ok"
        assert res.detail["dominant_stage"] == "gather"
        # now the SLO burns AND gather dominates: DEGRADED, culprit
        # named
        for _ in range(4):
            _noted_flush(t, [0.5], stage_s=0.45)
        res = check()
        assert res.status == "degraded"
        assert res.detail["dominant_stage"] == "gather"
        assert "gather" in res.detail["note"]
        assert res.detail["burn_rate"] > 1.0

    def test_burning_without_domination_stays_ok(self, null_obs):
        t = _telemetry()
        check = RequestStageCheck(t, frac_bar=0.9)  # bar out of reach
        for _ in range(4):
            _noted_flush(t, [0.5], stage_s=0.25)
        assert check().status == "ok"

    def test_watch_requests_flips_healthz(self, null_obs):
        mon = HealthMonitor()
        t = _telemetry()
        mon.watch_requests(t)
        assert mon.run()["status"] == "ok"
        for _ in range(4):
            _noted_flush(t, [0.5], stage_s=0.45)
        report = mon.run()
        assert report["checks"]["requests"]["status"] == "degraded"
        assert report["status"] == "degraded"


# --------------------------------------------------------------------------
# Fleet worst-first merge
# --------------------------------------------------------------------------


class TestFleet:
    def test_pod_view_merges_exemplars_worst_first(self, null_obs):
        from large_scale_recommendation_tpu.obs.fleet import (
            FleetAggregator,
            FleetServer,
        )

        obs.enable()
        try:
            t = obs.enable_requests(0.1, objective=0.9)
            _noted_flush(t, [0.5, 0.01])
            t.note_shed(version=1, level="shed", burn=4.0)
            with ObsServer() as s1, ObsServer() as s2:
                # two real sockets over the one process plane: the
                # worst-first merge contract is what's under test
                view = FleetAggregator([s1.url, s2.url]).requests()
                with FleetServer(FleetAggregator([s1.url])) as fleet:
                    code, body = http_get(fleet.url + "/slowz")
                    lcode, lbody = http_get(fleet.url +
                                            "/slowz?limit=1")
        finally:
            obs.disable()
        assert len(view["targets"]) == 2
        ex = view["exemplars"]
        assert ex and all("host" in e for e in ex)
        walls = [e.get("wall_s") or 0.0 for e in ex]
        assert walls == sorted(walls, reverse=True)
        # pod stage totals sum across members, fractions re-derive
        assert view["stage_totals_s"]["gather"] > 0.0
        assert view["dominant_stage"] in STAGES
        assert code == 200
        assert json.loads(body)["exemplars"]
        assert len(json.loads(lbody)["exemplars"]) == 1

    def test_unreachable_member_is_listed_not_fatal(self, null_obs):
        from large_scale_recommendation_tpu.obs.fleet import (
            FleetAggregator,
        )

        obs.enable()
        try:
            obs.enable_requests(0.1)
            with ObsServer() as s1:
                dead = "http://127.0.0.1:1"
                view = FleetAggregator([s1.url, dead],
                                       timeout_s=3.0).requests()
        finally:
            obs.disable()
        assert view["unreachable"] == ["127.0.0.1:1"]
        assert len(view["targets"]) == 1


# --------------------------------------------------------------------------
# Postmortem bundles: v8 round-trip, archived v7 synthesized
# --------------------------------------------------------------------------


class TestBundle:
    def test_v8_bundle_carries_requests_and_v7_stays_loadable(
            self, null_obs, tmp_path):
        import os

        from large_scale_recommendation_tpu.obs.recorder import (
            BUNDLE_VERSION,
            load_bundle,
            write_bundle,
        )

        obs.enable()
        obs.enable_flight_recorder(interval_s=0.05)
        try:
            t = obs.enable_requests(0.1, objective=0.9)
            _noted_flush(t, [0.5], version=5)
            path = write_bundle(str(tmp_path / "b"), trigger="manual")
            docs = load_bundle(path)
            assert BUNDLE_VERSION == 8
            assert docs["manifest"]["bundle_version"] == 8
            assert docs["requests"]["count"] == 1
            (ex,) = docs["requests"]["exemplars"]
            assert ex["catalog_version"] == 5
            # an archived version-7 bundle (pre-request-plane) stays
            # loadable with the note synthesized
            manifest_path = str(tmp_path / "b" / "manifest.json")
            with open(manifest_path) as f:
                manifest = json.load(f)
            manifest["bundle_version"] = 7
            manifest["files"] = [x for x in manifest["files"]
                                 if x != "requests.json"]
            with open(manifest_path, "w") as f:
                json.dump(manifest, f)
            os.unlink(str(tmp_path / "b" / "requests.json"))
            docs7 = load_bundle(path)
            assert docs7["requests"]["exemplars"] == []
            assert "version-7" in docs7["requests"]["note"]
        finally:
            obs.disable()

    def test_bundle_without_plane_freezes_the_note(self, null_obs,
                                                   tmp_path):
        from large_scale_recommendation_tpu.obs.recorder import (
            load_bundle,
            write_bundle,
        )

        obs.enable()
        obs.enable_flight_recorder(interval_s=0.05)
        try:
            path = write_bundle(str(tmp_path / "b"), trigger="manual")
            docs = load_bundle(path)
        finally:
            obs.disable()
        assert "not enabled" in docs["requests"]["note"]


# --------------------------------------------------------------------------
# Renderer
# --------------------------------------------------------------------------


class TestRenderer:
    def test_render_requests_local_and_fleet(self, null_obs):
        import os
        import sys

        sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                        ".."))
        from scripts.obs_report import render_requests

        t = _telemetry()
        _noted_flush(t, [0.5], rows=[5], admission_level="normal")
        out = render_requests(t.snapshot())
        assert "gather" in out and "violating" in out
        assert "dominant" in out
        fleet_doc = {
            "stage_frac": {"gather": 0.8, "host_post": 0.2},
            "stage_totals_s": {"gather": 4.0, "host_post": 1.0},
            "dominant_stage": "gather",
            "exemplars": [{"host": "h1:9100", "kind": "violating",
                           "wall_s": 0.5, "dominant_stage": "gather",
                           "catalog_version": 1, "queue_depth": 0,
                           "bucket": 8, "admission_level": None}],
            "targets": [{"host": "h1:9100", "count": 3,
                         "violations": 1, "shed": 0, "p99_ms": 500.0,
                         "dominant_stage": "gather", "note": None}],
        }
        out = render_requests(fleet_doc)
        assert "h1:9100" in out
        out = render_requests(slowz())  # absent-plane note form
        assert "enable_requests" in out


# --------------------------------------------------------------------------
# THE acceptance pin: real engine, armed admission, real socket
# --------------------------------------------------------------------------


class TestE2ESlowRequestAttribution:
    def test_slowz_names_where_the_tail_went(self, null_obs):
        """Mixed traffic against a REAL two-stage ``ServingEngine``
        with admission armed: a planted drag (attributed to the gather
        stage) makes one cohort slow, the burn walks the ladder
        through DEGRADE into SHED. ``/slowz`` over a real socket must
        hold at least one shed and one degraded exemplar, EVERY
        exemplar's stage fsum must EQUAL its measured wall, the
        slowest injected request must lead worst-first with gather
        named dominant, and the plane's violation accounting must
        agree with the engine's ``SLOTracker`` over the same window."""
        from large_scale_recommendation_tpu.obs.health import SLOTracker
        from large_scale_recommendation_tpu.serving import (
            AdmissionConfig,
            AdmissionController,
            RetrievalConfig,
            ServingEngine,
        )
        from large_scale_recommendation_tpu.serving.admission import (
            AdmissionRejectedError,
        )

        obs.enable()
        telemetry = obs.enable_requests(
            0.030, objective=0.9, window=64, max_exemplars=64,
            slow_keep=8)
        try:
            slo = SLOTracker(target_s=0.030, objective=0.9, window=64)
            adm = AdmissionController(
                slo, AdmissionConfig(min_samples=4, widen_burn=1.0,
                                     degrade_burn=2.0, shed_burn=6.0,
                                     shed_probe=0.25))
            eng = ServingEngine(
                _model(num_items=512), k=5, max_batch=64,
                retrieval=RetrievalConfig(n_clusters=None, overfetch=4))
            assert eng._requests is telemetry
            # the planted drag: 50ms attributed to gather — the
            # injected slowest request the reservoir must surface
            orig = eng._serve_rows

            def dragging(rows, stage1_only=False, seam=None):
                with seam("serving/engine/gather"):
                    time.sleep(0.05)
                return orig(rows, stage1_only=stage1_only, seam=seam)

            rng = np.random.default_rng(11)
            eng.serve([rng.integers(0, 50, 4).astype(np.int64)])
            # warm the stage1-only (degraded) executable too: compile
            # wall is not the signal, the planted drag is
            import jax.numpy as jnp

            empty_excl = (np.zeros(8, np.int32), np.zeros(8, np.int32),
                          np.full(8, np.inf, np.float32))
            eng.retriever.topk(jnp.zeros((8, RANK), jnp.float32),
                               empty_excl, k=5, stage1_only=True)
            # arm admission AFTER the warmup so the tracker and the
            # plane price the same post-warm request stream
            eng.attach_admission(adm)
            telemetry.reset()  # compile wall is not the signal
            eng._serve_rows = dragging
            shed = 0
            with ObsServer() as server:
                for _ in range(40):
                    try:
                        eng.submit(rng.integers(0, 50, 4).astype(
                            np.int64))
                        eng.flush()
                    except AdmissionRejectedError:
                        shed += 1
                code, body = http_get(server.url + "/slowz")
            slo_snap = slo.snapshot()
            eng_version = eng.version
        finally:
            obs.disable()

        assert code == 200
        doc = json.loads(body)
        ex = doc["exemplars"]
        assert ex

        # at least one shed and one degraded request were captured
        assert shed >= 1
        kinds = {e["kind"] for e in ex}
        assert "shed" in kinds, doc["kept"]
        assert any(e["degraded"] for e in ex), doc["kept"]
        assert doc["shed"] == shed

        # EVERY exemplar's stage sum reconciles exactly with its wall
        for e in ex:
            if e["kind"] == "shed":
                continue  # never entered a flush: no stages by design
            assert math.fsum(e["stages"].values()) == e["wall_s"], e

        # the slowest injected request leads worst-first with the
        # dominant stage correctly named — the drag went to gather
        flushed = [e for e in ex if e["kind"] != "shed"]
        worst = flushed[0]
        assert worst["wall_s"] >= 0.05
        assert worst["kind"] == "violating"
        assert worst["dominant_stage"] == "gather"
        assert doc["dominant_stage"] == "gather"

        # exemplar accounting agrees with the engine's SLOTracker over
        # the same window: both priced the IDENTICAL end - ts floats
        assert doc["violations"] == slo_snap["violations"]
        assert doc["window_fill"] == slo_snap["window_fill"]
        assert 1.0 - doc["violations"] / doc["window_fill"] == \
            pytest.approx(slo_snap["attainment"])
        # every flushed request violated the 30ms target under a 50ms
        # drag, so the plane's p99 must sit above the drag
        assert doc["p99_ms"] >= 50.0

        # exemplars carry the joinable annotations
        assert worst["catalog_version"] == eng_version
        assert worst["rows"] == 4 and worst["bucket"] == 4
        assert any(e["admission_level"] in ("degrade", "shed")
                   for e in ex)
