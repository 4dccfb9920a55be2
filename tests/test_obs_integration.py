"""End-to-end observability: the instrumented runtime tiers populate the
documented metric names, the null path does zero registry/tracer work,
and one demo-shaped run produces all three artifacts (Prometheus text,
metrics JSONL, Chrome trace) with a schema-valid, compile/execute-
distinguishable trace.
"""

import json

import numpy as np
import pytest

from large_scale_recommendation_tpu import obs
from large_scale_recommendation_tpu.core.generators import (
    SyntheticMFGenerator,
)
from large_scale_recommendation_tpu.models.online import (
    OnlineMF,
    OnlineMFConfig,
)
from large_scale_recommendation_tpu.obs.registry import (
    NULL_INSTRUMENT,
    get_registry,
    set_registry,
)
from large_scale_recommendation_tpu.obs.trace import (
    get_tracer,
    set_tracer,
    validate_chrome_trace,
)
from large_scale_recommendation_tpu.serving.engine import ServingEngine
from large_scale_recommendation_tpu.streams.driver import (
    StreamingDriver,
    StreamingDriverConfig,
)
from large_scale_recommendation_tpu.streams.log import EventLog


@pytest.fixture
def live_obs():
    """A fresh registry+tracer installed for the test, with whatever was
    installed before (usually the nulls) restored after."""
    prev_r, prev_t = get_registry(), get_tracer()
    reg, tracer = obs.enable()
    yield reg, tracer
    set_registry(prev_r)
    set_tracer(prev_t)


# null_obs comes from tests/conftest.py: ONE copy of the full-layer
# save/disable/restore-and-restart invariant, shared by every obs file


def _tiny_model(num_users=300, num_items=128, rank=8, seed=0):
    import jax.numpy as jnp

    from large_scale_recommendation_tpu.data.blocking import flat_index
    from large_scale_recommendation_tpu.models.mf import MFModel

    rng = np.random.default_rng(seed)
    return MFModel(
        U=jnp.asarray(rng.normal(size=(num_users, rank)).astype(np.float32)),
        V=jnp.asarray(rng.normal(size=(num_items, rank)).astype(np.float32)),
        users=flat_index(np.arange(num_users, dtype=np.int64)),
        items=flat_index(np.arange(num_items, dtype=np.int64)),
    )


def _fill_log(log, n_batches=3, batch=400, seed=0):
    gen = SyntheticMFGenerator(num_users=200, num_items=64, rank=4,
                               seed=seed)
    for _ in range(n_batches):
        ru, ri, rv, _ = gen.generate(batch).to_numpy()
        log.append_arrays(0, ru, ri, rv)
    return n_batches * batch


class TestServingEngineMetrics:
    # the documented serving metric catalog (docs/OBSERVABILITY.md) —
    # the end-to-end pin that instrumentation stays wired through the
    # engine's submit/flush/refresh paths
    EXPECTED = {
        "serving_queue_wait_s", "serving_batch_assembly_s",
        "serving_flush_s", "serving_bucket_occupancy",
        "serving_requests_total", "serving_rows_total",
        "serving_microbatches_total", "serving_catalog_swaps_total",
        "serving_catalog_version",
    }

    def test_serve_populates_expected_names(self, live_obs):
        reg, _ = live_obs
        engine = ServingEngine(_tiny_model(), k=5, max_batch=64)
        rng = np.random.default_rng(1)
        engine.serve([rng.integers(0, 300, 12).astype(np.int64)
                      for _ in range(10)])
        missing = self.EXPECTED - reg.names()
        assert not missing, f"unpopulated metrics: {missing}"
        assert reg.counter("serving_requests_total").value == 10
        assert reg.counter("serving_rows_total").value == 120
        assert reg.histogram("serving_queue_wait_s").count == 10
        # per-pow2-bucket labels on the micro-batch counters
        buckets = {dict(h.labels)["bucket"]
                   for h in reg.find("serving_microbatches_total")}
        assert buckets  # at least one bucket exercised
        assert all(int(b) & (int(b) - 1) == 0 for b in buckets)

    def test_refresh_counts_catalog_swap_with_version_label(self,
                                                            live_obs):
        reg, _ = live_obs
        engine = ServingEngine(_tiny_model(), k=5, max_batch=64)
        v0 = engine.version
        v1 = engine.refresh(_tiny_model(seed=9))
        assert v1 != v0
        versions = {dict(c.labels)["version"]
                    for c in reg.find("serving_catalog_swaps_total")}
        assert {str(v0), str(v1)} <= versions
        assert reg.gauge("serving_catalog_version").value == v1


class TestStreamingDriverMetrics:
    EXPECTED = {
        "streams_batches_total", "streams_records_total",
        "streams_checkpoint_s", "streams_lag_records",
        "online_batch_s", "online_batches_total", "online_ratings_total",
    }

    def test_run_populates_expected_names(self, live_obs, tmp_path):
        reg, _ = live_obs
        log = EventLog(str(tmp_path / "log"))
        n = _fill_log(log)
        model = OnlineMF(OnlineMFConfig(num_factors=4, minibatch_size=128))
        driver = StreamingDriver(
            model, log, str(tmp_path / "ckpt"),
            config=StreamingDriverConfig(batch_records=400))
        applied = driver.run()
        tel = driver.telemetry()  # publishes the lag + queue gauges
        assert tel["lag_records"] == 0
        missing = self.EXPECTED - reg.names()
        assert not missing, f"unpopulated metrics: {missing}"
        part = {"partition": "0"}
        (batches,) = [c for c in reg.find("streams_batches_total")
                      if dict(c.labels) == part]
        assert batches.value == applied
        (records,) = [c for c in reg.find("streams_records_total")
                      if dict(c.labels) == part]
        assert records.value == n
        assert reg.histogram("streams_checkpoint_s",
                             partition="0").count == applied
        (lag,) = [g for g in reg.find("streams_lag_records")
                  if dict(g.labels) == part]
        assert lag.value == 0
        # queue-stat gauges mirrored from IngestStats via telemetry()
        assert "streams_queue_enqueued_records" in reg.names()


class TestNullPathZeroWork:
    def test_engine_binds_null_singletons(self, null_obs):
        """The disabled-hot-path pin: with the null layer installed the
        engine's instrument handles ARE the shared no-op singletons, the
        obs gate is off (no clock reads, no stamp list), and nothing is
        recorded anywhere."""
        engine = ServingEngine(_tiny_model(), k=5, max_batch=64)
        assert engine._obs_on is False
        assert engine._m_flush is NULL_INSTRUMENT
        assert engine._m_qwait is NULL_INSTRUMENT
        assert engine._m_requests is NULL_INSTRUMENT
        assert not engine._trace.enabled
        rng = np.random.default_rng(2)
        out = engine.serve([rng.integers(0, 300, 8).astype(np.int64)
                            for _ in range(5)])
        assert len(out) == 5
        assert engine._pending_t == []  # no queue-wait stamps kept
        assert null_obs.snapshot()["metrics"] == []
        assert null_obs.to_prometheus() == ""

    def test_tiered_store_binds_null(self, null_obs):
        """The STORE plane extension of the zero-cost pin: with the
        null layer installed the tiered store's instruments ARE the
        shared no-op singletons, `_obs_on` is off (no per-acquire gauge
        writes), and a full acquire/release/evict cycle records
        nothing anywhere."""
        from large_scale_recommendation_tpu.core.initializers import (
            PseudoRandomFactorInitializer,
        )
        from large_scale_recommendation_tpu.obs.store import set_store
        from large_scale_recommendation_tpu.store import (
            TieredFactorStore,
        )

        store = TieredFactorStore(PseudoRandomFactorInitializer(4),
                                  capacity=32, slot_capacity=8)
        try:
            assert store._obs_on is False
            assert store._m_hit_rate is NULL_INSTRUMENT
            assert store._m_wait is NULL_INSTRUMENT
            assert store._m_evictions is NULL_INSTRUMENT
            assert store._m_host_bytes is NULL_INSTRUMENT
            for lo in (0, 8):  # second window evicts the first
                rows = store.acquire_rows(np.arange(lo, lo + 8))
                store.release_rows(rows)
            assert store.stats.evictions > 0  # host counters still on
            assert null_obs.names() == set()
            assert null_obs.snapshot()["metrics"] == []
        finally:
            set_store(None)

    def test_driver_and_online_bind_null(self, null_obs, tmp_path):
        log = EventLog(str(tmp_path / "log"))
        _fill_log(log, n_batches=1)
        model = OnlineMF(OnlineMFConfig(num_factors=4, minibatch_size=128))
        driver = StreamingDriver(
            model, log, str(tmp_path / "ckpt"),
            config=StreamingDriverConfig(batch_records=400))
        assert driver._obs_on is False
        assert driver._m_ckpt is NULL_INSTRUMENT
        assert model._obs_on is False
        driver.run()
        assert driver.telemetry()["lag_records"] == 0
        assert null_obs.names() == set()

    def test_flight_recorder_and_events_default_off_everywhere(
            self, null_obs, tmp_path):
        """The flight-recorder extension of the zero-cost pin: with
        nothing installed, get_events()/get_recorder() are None (not
        null objects), every emitting component binds that None — one
        pointer test per hook — and no sampler thread, journal ring, or
        bundle machinery exists anywhere."""
        from large_scale_recommendation_tpu.obs.events import (
            get_events,
            set_events,
        )
        from large_scale_recommendation_tpu.obs.recorder import (
            get_recorder,
            set_recorder,
        )

        # force the true disabled state (an OBS_OUT session conftest may
        # have a journal/recorder installed for the whole suite)
        prev_j, prev_r = get_events(), get_recorder()
        set_events(None)
        set_recorder(None)
        try:
            self._assert_null_everywhere(null_obs, tmp_path)
        finally:
            set_events(prev_j)
            set_recorder(prev_r)

    def _assert_null_everywhere(self, null_obs, tmp_path):
        from large_scale_recommendation_tpu.models.adaptive import (
            AdaptiveMF,
            AdaptiveMFConfig,
        )
        from large_scale_recommendation_tpu.models.dsgd import DSGD
        from large_scale_recommendation_tpu.obs.events import get_events
        from large_scale_recommendation_tpu.obs.health import (
            TrainingWatchdog,
        )
        from large_scale_recommendation_tpu.obs.recorder import (
            get_recorder,
        )
        from large_scale_recommendation_tpu.serving.engine import (
            ServingEngine,
        )
        from large_scale_recommendation_tpu.streams.sources import (
            IngestQueue,
        )

        assert get_events() is None
        assert get_recorder() is None
        engine = ServingEngine(_tiny_model(), k=3, max_batch=32)
        assert engine._events is None
        model = OnlineMF(OnlineMFConfig(num_factors=4, minibatch_size=64))
        assert model._events is None
        assert DSGD()._events is None
        assert AdaptiveMF(AdaptiveMFConfig(num_factors=4))._events is None
        assert IngestQueue()._events is None
        log = EventLog(str(tmp_path / "log"))
        assert log._parts[0]._events is None
        driver = StreamingDriver(model, log, str(tmp_path / "ckpt"))
        assert driver._events is None
        # the uninstrumented hot paths still run clean end to end,
        # recording nothing anywhere
        _fill_log(log, n_batches=1)
        driver.run()
        wd = TrainingWatchdog(policy="observe")
        wd.observe_loss(float("nan"))  # trip: no journal, no bundle
        assert wd.tripped and wd.last_bundle is None
        assert null_obs.names() == set()

    def test_model_plane_default_off_everywhere(self, null_obs,
                                                tmp_path):
        """The ISSUE-10 extension of the zero-cost pin: with nothing
        enabled, get_lineage() is None and every stamping/joining site
        binds that None — the engine's swap/flush hooks, the driver's
        ingest watermark, the adaptive install — and a driver built
        without an inspector/evaluator carries None hooks: one pointer
        test per batch, no reservoir, no window deques, no journal."""
        from large_scale_recommendation_tpu.models.adaptive import (
            AdaptiveMF,
            AdaptiveMFConfig,
        )
        from large_scale_recommendation_tpu.obs.lineage import (
            get_lineage,
            set_lineage,
        )
        from large_scale_recommendation_tpu.serving.engine import (
            ServingEngine,
        )
        from large_scale_recommendation_tpu.streams.driver import (
            StreamingDriver,
        )

        prev = get_lineage()
        set_lineage(None)  # an OBS_OUT session runs one suite-wide
        try:
            assert get_lineage() is None
            engine = ServingEngine(_tiny_model(), k=3, max_batch=32)
            assert engine._lineage is None
            model = OnlineMF(OnlineMFConfig(num_factors=4,
                                            minibatch_size=64))
            log = EventLog(str(tmp_path / "log"))
            driver = StreamingDriver(model, log, str(tmp_path / "ckpt"))
            assert driver._lineage is None
            assert driver.inspector is None
            assert driver.evaluator is None
            adaptive = AdaptiveMF(AdaptiveMFConfig(num_factors=4))
            assert adaptive._lineage is None
            # the offline trainers' quality hook defaults off too
            from large_scale_recommendation_tpu.models.als import ALS
            from large_scale_recommendation_tpu.models.dsgd import DSGD

            assert DSGD().evaluator is None
            assert ALS().evaluator is None
            # the whole null stream path still runs clean, recording
            # nothing anywhere
            _fill_log(log, n_batches=1)
            driver.serving_engine(k=3, max_batch=32)
            driver.run()
            driver.refresh_serving()
            assert null_obs.names() == set()
        finally:
            set_lineage(prev)

    def test_disttrace_default_off_everywhere(self, null_obs, tmp_path):
        """The ISSUE-12 extension of the zero-cost pin: with nothing
        enabled, get_disttrace() is None and every stamping site binds
        that None — the WAL append, the driver marks, the engine
        serve-note, the adaptive swap-note — and the default-off
        tracer means NO context stamps anywhere: batches carry
        ctx=None, capture_context() is None, and no wal/ingest spans,
        clock reads or registry names appear."""
        from large_scale_recommendation_tpu.models.adaptive import (
            AdaptiveMF,
            AdaptiveMFConfig,
        )
        from large_scale_recommendation_tpu.obs.disttrace import (
            get_disttrace,
            set_disttrace,
        )
        from large_scale_recommendation_tpu.obs.trace import get_tracer
        from large_scale_recommendation_tpu.serving.engine import (
            ServingEngine,
        )
        from large_scale_recommendation_tpu.streams.sources import (
            LogTailSource,
        )

        prev = get_disttrace()
        set_disttrace(None)  # an OBS_OUT session runs one suite-wide
        try:
            assert get_disttrace() is None
            assert get_tracer().capture_context() is None
            log = EventLog(str(tmp_path / "log"))
            assert log._disttrace is None
            _fill_log(log, n_batches=1)
            # default-off tracer ⇒ no per-batch context mints
            for batch in LogTailSource(log, batch_records=128):
                assert batch.ctx is None
                break
            engine = ServingEngine(_tiny_model(), k=3, max_batch=32)
            assert engine._disttrace is None
            model = OnlineMF(OnlineMFConfig(num_factors=4,
                                            minibatch_size=64))
            driver = StreamingDriver(model, log, str(tmp_path / "ckpt"))
            assert driver._disttrace is None
            assert AdaptiveMF(
                AdaptiveMFConfig(num_factors=4))._disttrace is None
            # the whole null stream path still runs clean end to end
            eng = driver.serving_engine(k=3, max_batch=32)
            driver.run()
            driver.refresh_serving()
            eng.recommend(np.arange(3, dtype=np.int64))
            assert null_obs.names() == set()
        finally:
            set_disttrace(prev)

    def test_introspection_default_off_and_funnel_unpatched(
            self, null_obs):
        """The ISSUE-9 extension of the zero-cost pin: with nothing
        enabled, get_introspector() is None (producer hooks bind that
        None — TrainSegmentTimer.finish, the bundle writer, the
        /rooflinez route) and the jax compile funnel is the PRISTINE
        function — no wrapper, no per-compile work of any kind. An
        OBS_OUT session patches suite-wide, so the installed hook (if
        any) is parked for the duration of the check and restored."""
        import jax._src.compiler as compiler

        from large_scale_recommendation_tpu.obs.introspect import (
            get_introspector,
        )
        from large_scale_recommendation_tpu.obs.server import ObsServer

        assert get_introspector() is None  # null_obs cleared it
        suite_ins = None
        current = compiler.compile_or_get_cached
        if hasattr(current, "__lsr_introspector__"):
            suite_ins = current.__lsr_introspector__
            suite_ins.uninstall()
        try:
            assert not hasattr(compiler.compile_or_get_cached,
                               "__lsr_introspector__")
            # the disabled-route answer carries no introspector either
            assert ObsServer().rooflinez()["rows"] == []
        finally:
            if suite_ins is not None:
                suite_ins.install()


class TestLegacyShimMigration:
    """utils.metrics helpers keep their surfaces but mirror into the
    registry when one is live (satellite: the pre-obs timing logic is
    deprecated in favor of the registry)."""

    def test_step_timer_mirrors_histogram(self, live_obs):
        reg, _ = live_obs
        from large_scale_recommendation_tpu.utils import metrics as M

        t = M.StepTimer("sweep")
        with t.time():
            pass
        assert t.count == 1  # original surface intact
        assert reg.histogram("step_timer_s", name="sweep").count == 1

    def test_throughput_meter_mirrors_counters(self, live_obs):
        reg, _ = live_obs
        from large_scale_recommendation_tpu.utils import metrics as M

        m = M.ThroughputMeter(name="serve")
        m.record(1000, 2.0)
        assert m.rate == 500.0
        assert reg.counter("meter_elements_total", name="serve").value \
            == 1000
        assert reg.counter("meter_seconds_total", name="serve").value \
            == 2.0

    def test_ingest_stats_publish(self, live_obs):
        reg, _ = live_obs
        from large_scale_recommendation_tpu.utils.metrics import IngestStats

        s = IngestStats(enqueued_records=42, depth=3)
        s.publish(partition="1")
        assert reg.gauge("ingest_enqueued_records",
                         partition="1").value == 42
        assert reg.gauge("ingest_depth", partition="1").value == 3
        assert s.snapshot()["enqueued_records"] == 42  # surface intact

    def test_metrics_log_counts_events(self, live_obs):
        reg, _ = live_obs
        from large_scale_recommendation_tpu.utils.metrics import MetricsLog

        log = MetricsLog(log_to=None)
        log.log("epoch", rmse=0.1)
        log.log("epoch", rmse=0.05)
        assert len(log.of("epoch")) == 2
        assert reg.counter("metrics_log_events_total",
                           event="epoch").value == 2

    def test_shims_are_noop_when_disabled(self, null_obs):
        from large_scale_recommendation_tpu.utils import metrics as M

        t = M.StepTimer("x")
        with t.time():
            pass
        m = M.ThroughputMeter()
        m.record(10, 1.0)
        M.IngestStats().publish()
        assert null_obs.names() == set()


class TestEndToEndArtifacts:
    def test_train_serve_stream_dump_all_three_artifacts(self, live_obs,
                                                         tmp_path):
        """The acceptance demo in test form: one run produces a
        Prometheus snapshot, a metrics JSONL, and a Chrome trace whose
        schema validates — with compile and execute spans
        distinguishable."""
        from large_scale_recommendation_tpu.models.dsgd import (
            DSGD,
            DSGDConfig,
        )

        reg, tracer = live_obs
        # train: 2 one-iteration segments → the first carries the
        # compile (span cat "compile"), the second is steady ("execute")
        gen = SyntheticMFGenerator(num_users=120, num_items=60, rank=4,
                                   seed=3)
        ratings = gen.generate(4000)
        solver = DSGD(DSGDConfig(num_factors=8, iterations=2,
                                 minibatch_size=512, num_blocks=2,
                                 learning_rate=0.05))
        model = solver.fit(ratings, checkpoint_every=1)
        assert reg.histogram("train_segment_s", model="dsgd").count == 2
        steady = reg.gauge("train_throughput_ratings_per_s",
                           model="dsgd", phase="steady")
        assert steady.value > 0

        # serve + stream
        engine = ServingEngine(model, k=5, max_batch=64)
        rng = np.random.default_rng(4)
        engine.serve([rng.integers(0, 120, 9).astype(np.int64)
                      for _ in range(6)])
        log = EventLog(str(tmp_path / "log"))
        _fill_log(log, n_batches=2)
        om = OnlineMF(OnlineMFConfig(num_factors=4, minibatch_size=128))
        StreamingDriver(om, log, str(tmp_path / "ckpt"),
                        config=StreamingDriverConfig(
                            batch_records=400)).run()

        # artifact 1: Prometheus text
        prom = reg.to_prometheus()
        assert "serving_flush_s" in prom
        assert "train_segment_s" in prom
        assert "streams_batches_total" in prom

        # artifact 2: metrics JSONL
        jsonl = str(tmp_path / "metrics.jsonl")
        reg.append_jsonl(jsonl)
        snap = json.loads(open(jsonl).read().splitlines()[-1])
        names = {m["name"] for m in snap["metrics"]}
        assert {"serving_flush_s", "train_segment_s",
                "online_batch_s"} <= names

        # artifact 3: Chrome trace, schema-validated from disk
        trace_path = str(tmp_path / "trace.json")
        tracer.to_chrome_trace(trace_path)
        doc = json.load(open(trace_path))
        events = validate_chrome_trace(doc)
        cats = {e["cat"] for e in events}
        assert "compile" in cats and "execute" in cats, cats
        train_spans = [e for e in events
                       if e["name"] == "fit/dsgd/segment"]
        assert [e["cat"] for e in train_spans] == ["compile", "execute"]
