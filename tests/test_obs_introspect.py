"""XLA introspection tests (ISSUE 9): compile-boundary capture on the
production jit geometries, the roofline join math pinned against a hand
reference, the XLA-vs-hand-model bytes cross-check for the DSGD sweep,
device-memory telemetry with the CPU graceful-absent path, profiler
capture layer routing, and the /rooflinez + /profilez endpoint routes
over a real socket."""

from __future__ import annotations

import json
import os
import warnings

import numpy as np
import pytest

from large_scale_recommendation_tpu import obs
from large_scale_recommendation_tpu.obs import introspect as intro
from large_scale_recommendation_tpu.obs.introspect import (
    Introspector,
    capture_profile,
    profile_trace,
    render_key,
    roofline_rows,
)
from large_scale_recommendation_tpu.obs.registry import MetricsRegistry
from large_scale_recommendation_tpu.obs.trace import Tracer


@pytest.fixture
def live_introspection(null_obs):
    """A live obs layer (fresh registry/tracer) with an installed
    introspector, fully restored after — rides null_obs so the previous
    layer (an OBS_OUT session's, say) comes back exactly."""
    reg, tracer = obs.enable(MetricsRegistry(), Tracer())
    introspector = obs.enable_introspection(start=False)
    assert introspector.installed
    yield reg, tracer, introspector
    # null_obs's teardown restores the previous layer; disable() here
    # removes OUR hook first so layers can't stack
    obs.disable()


def _tiny_ratings(n=6000, users=300, items=120, seed=0):
    from large_scale_recommendation_tpu.core.generators import (
        SyntheticMFGenerator,
    )

    return SyntheticMFGenerator(num_users=users, num_items=items, rank=4,
                                noise=0.1, seed=seed).generate(n)


class TestRenderKey:
    def test_forms(self):
        assert render_key("serving_flush") == "serving_flush"
        assert render_key(("online_train", 512)) == "online_train/512"
        assert render_key(("train_segment", "dsgd", (300, 8))) == \
            "train_segment/dsgd/(300, 8)"

    def test_stable(self):
        key = ("train_segment", "dsgd", (300, 8), (120, 8))
        assert render_key(key) == render_key(tuple(key))


class TestCompileCapture:
    """Cost-analysis capture on every production jit geometry, CPU
    backend: keys present, flops > 0, bytes > 0."""

    def test_dsgd_segment_key(self, live_introspection):
        _, _, ins = live_introspection
        from large_scale_recommendation_tpu.models.dsgd import (
            DSGD,
            DSGDConfig,
        )

        DSGD(DSGDConfig(num_factors=8, iterations=2, num_blocks=2,
                        minibatch_size=512, learning_rate=0.05)
             ).fit(_tiny_ratings(), checkpoint_every=1)
        recs = [r for r in ins.records()
                if r["key"].startswith("train_segment/dsgd")]
        assert recs, [r["key"] for r in ins.records()]
        dom = max(recs, key=lambda r: r["bytes_accessed"])
        assert dom["flops"] > 0
        assert dom["bytes_accessed"] > 0
        assert dom["compile_wall_s"] > 0
        assert dom["compiles"] >= 1

    def test_als_segment_key(self, live_introspection):
        _, _, ins = live_introspection
        from large_scale_recommendation_tpu.models.als import ALS, ALSConfig

        ALS(ALSConfig(num_factors=8, iterations=2, lambda_=0.1,
                      seed=0)).fit(_tiny_ratings())
        recs = [r for r in ins.records()
                if r["key"].startswith("train_segment/als")]
        assert recs, [r["key"] for r in ins.records()]
        dom = max(recs, key=lambda r: r["bytes_accessed"])
        assert dom["flops"] > 0 and dom["bytes_accessed"] > 0

    def test_online_partial_fit_key(self, live_introspection):
        _, _, ins = live_introspection
        from large_scale_recommendation_tpu.models.online import (
            OnlineMF,
            OnlineMFConfig,
        )

        model = OnlineMF(OnlineMFConfig(num_factors=8, minibatch_size=256))
        model.partial_fit(_tiny_ratings(2000))
        recs = [r for r in ins.records()
                if r["key"].startswith("online_train")]
        assert recs, [r["key"] for r in ins.records()]
        assert max(r["bytes_accessed"] for r in recs) > 0

    def test_serving_flush_key(self, live_introspection):
        _, _, ins = live_introspection
        import jax.numpy as jnp

        from large_scale_recommendation_tpu.data.blocking import flat_index
        from large_scale_recommendation_tpu.models.mf import MFModel
        from large_scale_recommendation_tpu.serving.engine import (
            ServingEngine,
        )

        rng0 = np.random.default_rng(0)
        model = MFModel(
            U=jnp.asarray(rng0.normal(size=(300, 8)).astype(np.float32)),
            V=jnp.asarray(rng0.normal(size=(128, 8)).astype(np.float32)),
            users=flat_index(np.arange(300, dtype=np.int64)),
            items=flat_index(np.arange(128, dtype=np.int64)),
        )
        engine = ServingEngine(model, k=5, max_batch=64)
        rng = np.random.default_rng(3)
        engine.serve([rng.integers(0, 300, 8).astype(np.int64)
                      for _ in range(4)])
        recs = [r for r in ins.records()
                if r["key"].startswith("serving_flush")]
        assert recs, [r["key"] for r in ins.records()]
        assert max(r["flops"] for r in recs) > 0

    def test_stable_across_recompiles(self, live_introspection):
        """Recompiling the same geometry records the same analysis —
        cost_analysis is a function of the program, and the record
        keeps per-key totals across compiles."""
        _, tracer, ins = live_introspection
        import jax
        import jax.numpy as jnp

        x = jnp.ones((32, 32))
        results = []
        for _ in range(2):
            f = jax.jit(lambda a: jnp.tanh(a @ a.T).sum())  # fresh fn →
            with tracer.span("t", key=("recompile_pin", 32)):  # recompile
                f(x).block_until_ready()
            rec = [r for r in ins.records()
                   if r["key"] == "recompile_pin/32"]
            dom = max(rec, key=lambda r: r["bytes_accessed"])
            results.append((dom["flops"], dom["bytes_accessed"]))
        assert results[0] == results[1]
        dom = max((r for r in ins.records()
                   if r["key"] == "recompile_pin/32"),
                  key=lambda r: r["bytes_accessed"])
        assert dom["compiles"] == 2

    def test_metrics_published(self, live_introspection):
        reg, tracer, ins = live_introspection
        import jax
        import jax.numpy as jnp

        with tracer.span("t", key="metrics_pin"):
            jax.jit(lambda a: a * 2)(jnp.ones(64)).block_until_ready()
        names = reg.names()
        for name in ("compile_count", "compile_wall_s", "xla_flops",
                     "xla_bytes_accessed"):
            assert name in names, (name, sorted(names))

    def test_uninstall_restores_pristine_funnel(self, null_obs):
        import jax._src.compiler as compiler

        # force the true uninstalled state (an OBS_OUT session patches
        # suite-wide), then check install/uninstall round-trips
        prev = intro.get_introspector()
        if prev is not None:
            prev.uninstall()
        try:
            before = compiler.compile_or_get_cached
            assert not hasattr(before, "__lsr_introspector__")
            ins = Introspector()
            assert ins.install()
            assert compiler.compile_or_get_cached is not before
            # a second introspector cannot stack on the funnel
            assert Introspector().install() is False
            ins.uninstall()
            assert compiler.compile_or_get_cached is before
        finally:
            if prev is not None:
                prev.install()


class TestRooflineJoin:
    """The join math pinned against a hand-computed reference."""

    def test_pinned_reference(self):
        records = [
            {"key": "k1", "module": "jit_big", "compiles": 2,
             "compile_wall_s": 0.5, "flops": 2.0e9,
             "bytes_accessed": 4.0e8, "memory": None},
            {"key": "k1", "module": "jit_helper", "compiles": 1,
             "compile_wall_s": 0.1, "flops": 10.0,
             "bytes_accessed": 100.0, "memory": None},
            {"key": "k2", "module": "jit_cold", "compiles": 1,
             "compile_wall_s": 0.2, "flops": 5.0,
             "bytes_accessed": 50.0, "memory": None},
        ]
        # k1: 4 executions totalling 2 s, 8 iterations (2 per exec)
        walls = {"k1": {"compile_count": 1, "compile_total_s": 0.6,
                        "execute_count": 4, "execute_total_s": 2.0,
                        "execute_min_s": 0.4, "execute_max_s": 0.6,
                        "iterations": 8}}
        model_costs = {"k1": {"bytes_per_iteration": 1.0e8}}
        rows = roofline_rows(records, walls, model_costs,
                             hbm_peak_gbs=800.0, fp32_peak_tflops=50.0)
        by_key = {r["key"]: r for r in rows}
        r1 = by_key["k1"]
        # dominant module is jit_big; family sums compiles/walls
        assert r1["module"] == "jit_big"
        assert r1["compiles"] == 3
        assert r1["compile_wall_s"] == pytest.approx(0.6)
        # wall/exec = 2.0/4 = 0.5 s → 4e8 B / 0.5 s = 0.8 GB/s
        assert r1["wall_per_exec_s"] == pytest.approx(0.5)
        assert r1["achieved_gbs"] == pytest.approx(0.8)
        # 0.8 / 800 GB/s = 0.1% of HBM peak
        assert r1["pct_of_hbm_peak"] == pytest.approx(0.1)
        # 2e9 flops / 0.5 s = 4e-3 TFLOP/s → 0.008% of 50 TFLOP/s
        assert r1["achieved_tflops"] == pytest.approx(4.0e-3)
        assert r1["pct_of_fp32_peak"] == pytest.approx(0.008)
        # model: 1e8 B/iter × (8 iters / 4 execs) = 2e8 B/exec →
        # xla/model = 4e8 / 2e8 = 2.0
        assert r1["model_bytes_per_exec"] == pytest.approx(2.0e8)
        assert r1["xla_vs_model_bytes"] == pytest.approx(2.0)
        # k2 never executed: analysis present, measured columns None
        r2 = by_key["k2"]
        assert r2["xla_flops"] == 5.0
        assert r2["wall_per_exec_s"] is None
        assert r2["pct_of_hbm_peak"] is None

    def test_note_compiled_drives_same_path(self, null_obs):
        ins = Introspector(registry=null_obs)
        ins.note_compiled("fake_key", "jit_fake", flops=100.0,
                          bytes_accessed=200.0, wall_s=0.05)
        recs = ins.records()
        assert len(recs) == 1
        assert recs[0]["key"] == "fake_key"
        assert recs[0]["flops"] == 100.0
        assert ins.compile_count == 1
        assert ins.compile_wall_s == pytest.approx(0.05)

    def test_record_table_bounded(self, null_obs):
        ins = Introspector(registry=null_obs, max_records=3)
        for i in range(6):
            ins.note_compiled(f"k{i}", "jit_m", flops=1.0,
                              bytes_accessed=1.0)
        assert len(ins.records()) == 3
        assert ins.dropped == 3

    def test_tracer_key_walls_bounded(self, null_obs):
        """Compile keys embed shapes, so churning geometries mint fresh
        keys forever — the wall-aggregate table is hard-capped like
        every other obs table, overflow counted."""
        tracer = Tracer()
        tracer.max_key_walls = 3
        for i in range(6):
            with tracer.span("t", key=("churn", i)):
                pass
        assert len(tracer.key_walls()) == 3
        assert tracer.key_walls_dropped == 3
        # existing keys keep aggregating past the cap
        with tracer.span("t", key=("churn", 0)):
            pass
        assert tracer.key_walls()[("churn", 0)]["execute_count"] == 1


class TestDSGDBytesCrossCheck:
    """Acceptance: XLA's bytes-accessed for the XLA-route sweep agrees
    with ops.sgd.dsgd_bytes_per_sweep within the documented factor.

    XLA's static analysis counts each HLO's operand bytes (a gather is
    charged index+slice bytes once per op); the hand model charges 4
    full row transactions per rating — the latency-bound DRAM view.
    They agree to well within an order of magnitude on the production
    sweep geometry (measured ~0.4–2× on CPU across geometries); the
    documented acceptance band here is [1/16, 16] — a break means one
    of the two models changed meaning, which is exactly what this pin
    exists to catch (docs/OBSERVABILITY.md "Device introspection")."""

    def test_xla_route_sweep_within_documented_factor(
            self, live_introspection):
        _, _, ins = live_introspection
        from large_scale_recommendation_tpu.models.dsgd import (
            DSGD,
            DSGDConfig,
        )

        DSGD(DSGDConfig(num_factors=16, iterations=3, num_blocks=2,
                        minibatch_size=1024, learning_rate=0.05)
             ).fit(_tiny_ratings(20_000, users=600, items=300),
                   checkpoint_every=1)
        rows = [r for r in ins.roofline()["rows"]
                if r["key"].startswith("train_segment/dsgd")]
        assert rows
        row = max(rows, key=lambda r: r["xla_bytes_accessed"])
        ratio = row["xla_vs_model_bytes"]
        assert ratio is not None, row
        assert 1.0 / 16.0 <= ratio <= 16.0, row


class TestDeviceMemory:
    def test_cpu_graceful_absent(self, live_introspection):
        """CPU devices have no allocator stats surface: stats come back
        null, supported False, no byte gauges — and nothing raises."""
        reg, _, ins = live_introspection
        doc = ins.sample_device_memory()
        assert doc["supported"] is False
        assert len(doc["devices"]) >= 1
        assert all(d["stats"] is None for d in doc["devices"])
        assert "device_bytes_in_use" not in reg.names()
        # live-array accounting works regardless of allocator stats
        import jax.numpy as jnp

        keep = jnp.ones((64, 64), jnp.float32)
        doc = ins.sample_device_memory()
        assert doc["live_arrays"]["count"] >= 1
        assert doc["live_arrays"]["bytes"] >= keep.nbytes
        assert "float32" in doc["live_arrays"]["by_dtype"]
        assert "live_arrays_bytes" in reg.names()

    def test_bundle_carries_device_memory(self, live_introspection,
                                          tmp_path):
        from large_scale_recommendation_tpu.obs.recorder import (
            FlightRecorder,
            load_bundle,
        )

        rec = FlightRecorder(bundle_dir=str(tmp_path))
        rec.sample()
        path = rec.dump(trigger="manual")
        docs = load_bundle(path)  # validates device_memory.json shape
        assert docs["device_memory"]["supported"] is False
        assert isinstance(docs["device_memory"]["devices"], list)
        assert "live_arrays" in docs["device_memory"]

    def test_version1_bundle_still_loads(self, live_introspection,
                                         tmp_path):
        """Backward compat: an ARCHIVED incident bundle written before
        the device-introspection layer (bundle_version 1, no
        device_memory.json) must stay loadable — it is exactly the
        artifact the flight recorder exists to preserve."""
        from large_scale_recommendation_tpu.obs.recorder import (
            FlightRecorder,
            load_bundle,
        )

        rec = FlightRecorder(bundle_dir=str(tmp_path))
        rec.sample()
        path = rec.dump(trigger="manual")
        # rewrite as a faithful version-1 bundle
        os.remove(os.path.join(path, "device_memory.json"))
        mpath = os.path.join(path, "manifest.json")
        with open(mpath) as f:
            manifest = json.load(f)
        manifest["bundle_version"] = 1
        manifest["files"] = [n for n in manifest["files"]
                             if n != "device_memory.json"]
        with open(mpath, "w") as f:
            json.dump(manifest, f)
        docs = load_bundle(path)
        assert docs["manifest"]["bundle_version"] == 1
        assert docs["device_memory"]["devices"] == []  # synthesized note


class TestProfilerCapture:
    def test_capture_profile_writes_artifacts(self, null_obs, tmp_path):
        out = capture_profile(str(tmp_path / "prof"), seconds=0.05)
        assert out["files"], out
        assert os.path.isdir(out["dir"])
        assert intro.CAPTURE_COUNT >= 1

    def test_concurrent_capture_refused(self, null_obs, tmp_path):
        with profile_trace(str(tmp_path / "p1")):
            with pytest.raises(RuntimeError, match="already in progress"):
                with profile_trace(str(tmp_path / "p2")):
                    pass

    def test_utils_profile_shim_routes_through_capture_layer(
            self, null_obs, tmp_path):
        """Satellite: utils.metrics.profile no longer drives
        jax.profiler on its own — it routes through profile_trace (the
        shared lock + accounting) and warns about its deprecation."""
        from large_scale_recommendation_tpu.utils.metrics import profile

        before = intro.CAPTURE_COUNT
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            with profile(str(tmp_path / "legacy")):
                pass
        assert intro.CAPTURE_COUNT == before + 1
        assert any(issubclass(w.category, DeprecationWarning)
                   for w in caught)
        # the None fast path stays a pure no-op: no capture, no warning
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            with profile(None):
                pass
        assert intro.CAPTURE_COUNT == before + 1
        assert not caught

    def test_trip_bundle_attaches_profile(self, null_obs, tmp_path):
        from large_scale_recommendation_tpu.obs.recorder import (
            FlightRecorder,
        )

        rec = FlightRecorder(bundle_dir=str(tmp_path),
                             profile_on_trip_s=0.05)
        path = rec.dump(trigger="watchdog_trip")
        prof = os.path.join(path, "profile")
        assert os.path.isdir(prof)
        assert any(os.scandir(prof))
        # manual dumps stay capture-free (dumps are cheap by contract)
        path2 = rec.dump(trigger="manual")
        assert not os.path.isdir(os.path.join(path2, "profile"))


class TestEndpointRoutes:
    def test_rooflinez_and_profilez_over_socket(self, live_introspection,
                                                tmp_path):
        import jax
        import jax.numpy as jnp

        from large_scale_recommendation_tpu.obs.server import (
            ObsServer,
            http_get,
        )

        reg, tracer, ins = live_introspection
        with tracer.span("t", key=("endpoint_pin", 16)):
            jax.jit(lambda a: (a @ a.T).sum())(
                jnp.ones((16, 16))).block_until_ready()
        with tracer.span("t", key=("endpoint_pin", 16)) as sp:
            sp.out = jax.jit(lambda a: (a @ a.T).sum())(jnp.ones((16, 16)))
        with ObsServer(profile_dir=str(tmp_path)) as server:
            code, body = http_get(server.url + "/rooflinez")
            assert code == 200
            doc = json.loads(body)
            keys = [r["key"] for r in doc["rows"]]
            assert "endpoint_pin/16" in keys
            row = next(r for r in doc["rows"]
                       if r["key"] == "endpoint_pin/16")
            assert row["xla_flops"] > 0
            assert row["execute_count"] == 1  # first span was compile-cat
            # achieved rates on any device; a share of the v5e peaks only
            # on a v5e — this CPU run must not print one
            assert row["achieved_gbs"] is not None
            assert row["pct_of_hbm_peak"] is None
            assert row["pct_of_fp32_peak"] is None
            assert doc["hbm_peak_gbs"] is None
            # generous timeout: the capture itself is 0.05 s, but the
            # profiler's start/stop overhead scales with process state
            # (python tracer walks every thread) — in a full tier-1
            # session the round trip measurably exceeds http_get's 10 s
            # default
            code, body = http_get(server.url + "/profilez?seconds=0.05",
                                  timeout=180.0)
            assert code == 200, body
            out = json.loads(body)
            assert out["files"], out
            assert out["dir"].startswith(str(tmp_path))
            # a malformed seconds param is a CLIENT error (400), not a
            # capture-layer failure (500)
            code, body = http_get(server.url + "/profilez?seconds=abc")
            assert code == 400, (code, body)
            # the route list advertises both
            code, body = http_get(server.url + "/")
            assert "/rooflinez" in body and "/profilez" in body

    def test_rooflinez_without_introspector(self, null_obs):
        from large_scale_recommendation_tpu.obs.server import (
            ObsServer,
            http_get,
        )

        with ObsServer() as server:
            code, body = http_get(server.url + "/rooflinez")
            assert code == 200
            assert json.loads(body)["rows"] == []


class TestRooflineRenderer:
    def test_render_roofline_table(self, null_obs):
        from scripts.obs_report import render_roofline

        ins = Introspector(registry=null_obs)
        ins.note_compiled("train_segment/dsgd/x", "jit_dsgd_train",
                          flops=1e9, bytes_accessed=5e8, wall_s=0.3)
        text = render_roofline(ins.roofline())
        assert "train_segment/dsgd/x" in text
        assert "compile key" in text and "%HBM" in text
        # empty doc renders a note, not a crash
        from large_scale_recommendation_tpu.obs.server import ObsServer

        empty = ObsServer(registry=null_obs).rooflinez()
        assert "no compile records" in render_roofline(empty)
