"""Unified observability: metrics registry + JAX-aware span tracing.

The paper's whole argument is a *comparison* of execution styles
(offline DSGD, PS offline, combined online+batch, pure streaming), and a
comparison is only as good as its instrumentation: ALX (arXiv:2112.02194)
attributes its TPU MF wins via step-level timing breakdowns, and FLAME
(arXiv:2509.22681) stands on per-request latency percentiles. This
package is that instrumentation layer, shared by every runtime tier:

- ``obs.registry`` — a thread-safe ``MetricsRegistry`` of labeled
  counters, gauges, and log-bucketed histograms (p50/p90/p99), with
  snapshot / JSONL / Prometheus-text exporters.
- ``obs.trace`` — a nested-span ``Tracer`` (context-manager API,
  thread-local span stack) that is JAX-aware: spans can
  ``block_until_ready`` their outputs so async dispatch doesn't hide
  device time, and a compile-key hook labels first-call spans
  ``compile`` vs steady-state ``execute``. Exports Chrome trace-event
  JSON loadable in Perfetto (https://ui.perfetto.dev).
- ``obs.health`` — the ACTIVE half: ``HealthMonitor`` (pluggable
  OK/DEGRADED/CRITICAL checks), ``SLOTracker`` (latency-target
  attainment + error-budget burn, wired into ``ServingEngine``),
  ``TrainingWatchdog`` (NaN/divergence guard with halt/rollback
  policies, hooked into the training tiers).
- ``obs.server`` — a zero-dependency stdlib HTTP endpoint server:
  ``/metrics`` (Prometheus text), ``/healthz`` (non-200 on CRITICAL),
  ``/varz`` (snapshot JSON), ``/tracez`` (recent spans), ``/seriesz``
  (flight-recorder history), ``/eventz`` (structured event journal).
- ``obs.recorder`` / ``obs.events`` / ``obs.anomaly`` — the FLIGHT
  RECORDER: a fixed-memory time-series store sampling every registry
  instrument on a cadence (tiered downsampling bounds the heap), a
  ring-bounded structured event journal correlated to trace span ids,
  EWMA/rate-of-change anomaly checks that learn a series' normal
  instead of needing static thresholds, and atomic postmortem bundle
  directories frozen on watchdog trips / CRITICAL health transitions
  (``validate_bundle`` is the schema contract;
  ``scripts/obs_report.py --bundle`` renders one).

- ``obs.quality`` / ``obs.dataquality`` / ``obs.lineage`` — the MODEL
  plane: a reservoir-holdout ``OnlineEvaluator`` shadow-scoring the
  live model on a cadence (``eval_rmse``/``eval_ndcg_at_k``/
  ``eval_hr_at_k``/``eval_coverage`` gauges, watched threshold-free by
  the anomaly machinery), a per-batch ingest ``DataQualityInspector``
  (NaN/range/vocab/duplicate/skew classes behind a
  ``DataQualityCheck``), and a ``LineageJournal`` stamping every
  catalog swap with ``{catalog_version, wal_offset_watermark,
  train_step, retrain_id, wall_time}`` — joined per request against
  ``RecResult.catalog_version`` into staleness/freshness telemetry and
  an ingest→serve ``FreshnessCheck`` SLO (``/lineagez``).

- ``obs.contention`` — the CONCURRENCY plane: instrumented
  ``Lock``/``RLock``/``Condition`` wrappers over the named hot locks
  (``lock_wait_s{lock=}``/``lock_hold_s{lock=}`` histograms,
  acquisition/contention counters, a current-waiters gauge), a
  per-thread CPU sampler (utilization + runnable-vs-blocked fractions
  per named thread), and a ``SaturationAnalyzer`` joining lock waits,
  thread windows and the per-partition ``streams_*`` gauges into an
  Amdahl decomposition of an N-consumer run — Karp–Flatt
  ``serial_fraction``, top contended locks, per-partition blocked
  share, projected speedup at 2N (``/contentionz``;
  ``scripts/obs_report.py --contention``).

- ``obs.disttrace`` — the CAUSAL plane: deterministic cross-process
  trace identity (``record_trace_id`` — WAL offsets are the
  propagation tokens; ``TraceContext`` carries trace id + parent span
  across thread/process boundaries), pod trace assembly
  (``assemble_pod_trace`` merges per-process Chrome exports into one
  Perfetto-loadable timeline — ``/podtracez`` on the ``FleetServer``;
  ``resolve_record_trace`` resolves one record id to its WAL append →
  ingest → partial_fit → swap → flush chain), and a
  ``CriticalPathAnalyzer`` decomposing each sampled record's
  ingest→servable wall into ``critical_path_s{stage}`` gauges that
  reconcile against the lineage freshness histogram
  (``/criticalpathz``).

- ``obs.transfers`` — the TRANSFER plane: a named-site device↔host
  ledger (``transfer_bytes_total{site,dir}`` /
  ``transfer_wait_s{site}`` at every deliberate crossing — tiered
  prefetch/write-back/cold gathers, checkpoint pulls/pushes, delta
  ships, minibatch staging — with per-site effective GB/s joining
  ``/rooflinez``), scoped ``jax.transfer_guard`` wrappers attributing
  implicit transfers to sites (``implicit_transfers_total`` — the
  runtime twin of graftlint's static ``host-sync`` rule), and a
  retrace watch over the hot jitted kernels (``retrace_total{fn}`` +
  a bounded ring of signature diffs) feeding a steady-state
  ``HealthMonitor`` gate (``/transferz``;
  ``scripts/obs_report.py --transfers``).

- ``obs.budget`` — the ROLLOUT plane: a multi-window error-budget
  engine (the SRE fast/slow burn-rate pair,
  ``slo_burn_rate{window=}``, ``error_budget_remaining``), a
  per-``catalog_version`` attribution ledger (every served request's
  latency/shed/degraded outcome plus the ``OnlineEvaluator``'s shadow
  scores land in the cohort of the *deploy* that served it), and a
  ``CanaryVerdictEngine`` comparing canary-vs-incumbent cohorts under
  minimum-sample and effect-size thresholds into PROMOTE/HOLD/ROLLBACK
  verdicts — stamped into lineage, paged by
  ``HealthMonitor.watch_rollout`` while a ROLLBACK sits un-acted-on
  (``/budgetz``; ``scripts/obs_report.py --budget``).

- ``obs.requests`` — the REQUEST plane: per-request stage
  decomposition (``queue_wait``/``batch_form``/``gather``/
  ``score_stage1``/``score_stage2``/``topk_merge``/``host_post``
  ledgers whose sums reconcile against the SLO-recorded walls by
  construction, ``request_stage_s{stage=}`` histograms +
  ``request_stage_frac{stage=}`` window gauges) and Dapper-style
  tail-based exemplar sampling — SLO-violating, shed, and degraded
  requests are always kept, otherwise the window's slowest N, each
  exemplar carrying its ledger, catalog version, pow2 bucket,
  admission rung, queue depth, and a Perfetto-renderable span tree —
  with ``RequestStageCheck`` paging when one stage dominates while
  the SLO burns (``/slowz``; ``scripts/obs_report.py --requests``).

Zero-cost when disabled — the design invariant every instrumented hot
path relies on: the module-level defaults are a ``NullRegistry`` and
``NullTracer`` whose instruments are shared stateless singletons (no
locks, no allocations, no clock reads). Call sites cache
``registry.enabled`` once and skip even ``perf_counter`` when off.

Usage::

    from large_scale_recommendation_tpu import obs

    reg, tracer = obs.enable()         # install live registry + tracer
    ...  # build engines/drivers/models AFTER enabling: instruments
    ...  # bind at construction time
    print(reg.to_prometheus())
    reg.append_jsonl("metrics.jsonl")
    tracer.to_chrome_trace("trace.json")
    obs.disable()                      # back to the null layer

See docs/OBSERVABILITY.md for the metric-name catalog and span taxonomy.
"""

from __future__ import annotations

from large_scale_recommendation_tpu.obs.anomaly import (
    AnomalyCheck,
    MonotonicGrowthCheck,
    ewma_zscore,
    rate_of_change,
)
from large_scale_recommendation_tpu.obs.budget import (
    CanaryVerdictEngine,
    RolloutBudget,
    RolloutCheck,
    budgetz,
    get_budget,
    serve_scope,
    set_budget,
)
from large_scale_recommendation_tpu.obs.contention import (
    ContentionTracker,
    InstrumentedCondition,
    InstrumentedLock,
    InstrumentedRLock,
    SaturationAnalyzer,
    amdahl_speedup,
    get_contention,
    karp_flatt_serial_fraction,
    named_condition,
    named_lock,
    named_rlock,
    set_contention,
)
from large_scale_recommendation_tpu.obs.dataquality import (
    DataQualityInspector,
)
from large_scale_recommendation_tpu.obs.disttrace import (
    CriticalPathAnalyzer,
    assemble_pod_trace,
    get_disttrace,
    record_trace_id,
    resolve_record_trace,
    set_disttrace,
)
from large_scale_recommendation_tpu.obs.events import (
    EventJournal,
    get_events,
    set_events,
)
from large_scale_recommendation_tpu.obs.fleet import (
    FleetAggregator,
    FleetServer,
    merge_prometheus,
    parse_prometheus,
)
from large_scale_recommendation_tpu.obs.health import (
    CRITICAL,
    DEGRADED,
    OK,
    CheckResult,
    DataQualityCheck,
    HealthMonitor,
    SLOTracker,
    TrainingDivergedError,
    TrainingWatchdog,
)
from large_scale_recommendation_tpu.obs.introspect import (
    Introspector,
    capture_profile,
    get_introspector,
    profile_trace,
    set_introspector,
)
from large_scale_recommendation_tpu.obs.lineage import (
    FreshnessCheck,
    LineageJournal,
    get_lineage,
    set_lineage,
)
from large_scale_recommendation_tpu.obs.quality import (
    OnlineEvaluator,
    PercentileRankEvaluator,
    catalog_coverage,
    sampled_ranking_metrics,
)
from large_scale_recommendation_tpu.obs.recorder import (
    FlightRecorder,
    get_recorder,
    load_bundle,
    series_key,
    set_recorder,
    validate_bundle,
    write_bundle,
)
from large_scale_recommendation_tpu.obs.registry import (
    MetricsRegistry,
    NullRegistry,
    get_registry,
    set_registry,
)
from large_scale_recommendation_tpu.obs.requests import (
    FlushLedger,
    RequestStageCheck,
    RequestTelemetry,
    get_requests,
    request_scope,
    set_requests,
    slowz,
)
from large_scale_recommendation_tpu.obs.server import ObsServer
from large_scale_recommendation_tpu.obs.store import (
    get_store,
    set_store,
    storez,
)
from large_scale_recommendation_tpu.obs.trace import (
    NullTracer,
    TraceContext,
    Tracer,
    get_tracer,
    process_namespace,
    set_tracer,
    validate_chrome_trace,
)
from large_scale_recommendation_tpu.obs.transfers import (
    TransferLedger,
    get_transfers,
    set_transfers,
    transferz,
)

__all__ = [
    "MetricsRegistry",
    "NullRegistry",
    "Tracer",
    "NullTracer",
    "get_registry",
    "set_registry",
    "get_tracer",
    "set_tracer",
    "validate_chrome_trace",
    "enable",
    "disable",
    "enabled",
    "enable_flight_recorder",
    "enable_introspection",
    "Introspector",
    "get_introspector",
    "set_introspector",
    "capture_profile",
    "profile_trace",
    "FleetAggregator",
    "FleetServer",
    "merge_prometheus",
    "parse_prometheus",
    "FlightRecorder",
    "EventJournal",
    "AnomalyCheck",
    "MonotonicGrowthCheck",
    "ewma_zscore",
    "rate_of_change",
    "get_recorder",
    "set_recorder",
    "get_events",
    "set_events",
    "series_key",
    "validate_bundle",
    "load_bundle",
    "write_bundle",
    "HealthMonitor",
    "CheckResult",
    "SLOTracker",
    "TrainingWatchdog",
    "TrainingDivergedError",
    "DataQualityCheck",
    "DataQualityInspector",
    "OnlineEvaluator",
    "PercentileRankEvaluator",
    "sampled_ranking_metrics",
    "catalog_coverage",
    "LineageJournal",
    "FreshnessCheck",
    "get_lineage",
    "set_lineage",
    "enable_lineage",
    "ContentionTracker",
    "SaturationAnalyzer",
    "InstrumentedLock",
    "InstrumentedRLock",
    "InstrumentedCondition",
    "karp_flatt_serial_fraction",
    "amdahl_speedup",
    "named_lock",
    "named_rlock",
    "named_condition",
    "get_contention",
    "set_contention",
    "enable_contention",
    "TraceContext",
    "process_namespace",
    "CriticalPathAnalyzer",
    "assemble_pod_trace",
    "resolve_record_trace",
    "record_trace_id",
    "get_disttrace",
    "set_disttrace",
    "enable_disttrace",
    "ObsServer",
    "get_store",
    "set_store",
    "storez",
    "TransferLedger",
    "get_transfers",
    "set_transfers",
    "transferz",
    "enable_transfers",
    "RolloutBudget",
    "CanaryVerdictEngine",
    "RolloutCheck",
    "get_budget",
    "set_budget",
    "serve_scope",
    "budgetz",
    "enable_budget",
    "RequestTelemetry",
    "FlushLedger",
    "RequestStageCheck",
    "get_requests",
    "set_requests",
    "request_scope",
    "slowz",
    "enable_requests",
    "OK",
    "DEGRADED",
    "CRITICAL",
]


def enable(registry: MetricsRegistry | None = None,
           tracer: Tracer | None = None):
    """Install a live registry + tracer as the module-level defaults.

    Returns ``(registry, tracer)``. Instrumented components read the
    defaults at construction time, so enable BEFORE building the
    engines/drivers/models you want instrumented."""
    registry = registry or MetricsRegistry()
    tracer = tracer or Tracer()
    set_registry(registry)
    set_tracer(tracer)
    return registry, tracer


def enable_flight_recorder(interval_s: float = 1.0,
                           bundle_dir: str | None = None,
                           event_capacity: int = 4096,
                           event_jsonl: str | None = None,
                           start: bool = True,
                           **recorder_kwargs):
    """Install the flight-recorder layer: an ``EventJournal`` as the
    module-level journal and a ``FlightRecorder`` as the module-level
    recorder (started unless ``start=False``). Call AFTER ``enable()``
    (the recorder samples the live registry; the journal stamps the
    live tracer's span ids) and BEFORE building the engines/drivers/
    models whose emissions you want journaled — event hooks bind at
    construction, same as the instruments. Returns
    ``(recorder, journal)``."""
    prev = get_recorder()
    if prev is not None:  # re-enable must not leak the old sampler
        prev.stop()       # thread (unreachable once replaced)
    journal = EventJournal(capacity=event_capacity, jsonl_path=event_jsonl)
    set_events(journal)
    recorder = FlightRecorder(interval_s=interval_s, bundle_dir=bundle_dir,
                              **recorder_kwargs)
    set_recorder(recorder)
    if start:
        recorder.start()
    return recorder, journal


def enable_introspection(interval_s: float = 1.0, start: bool = True,
                         **introspector_kwargs) -> Introspector:
    """Install the XLA-introspection layer: an ``Introspector`` hooked
    into the jax compile funnel as the module-level default, with its
    device-memory/roofline sampler running every ``interval_s`` unless
    ``start=False``. Call AFTER ``enable()`` (the introspector binds
    the live registry/tracer at construction — under the null layer it
    still captures records, but publishes nothing). Returns the
    introspector (``.installed`` is False when the jax internal moved
    and the hook could not be placed)."""
    prev = get_introspector()
    if prev is not None:  # re-enable must not stack compile hooks or
        prev.close()      # leak the old sampler thread
    introspector = Introspector(**introspector_kwargs)
    introspector.install()
    set_introspector(introspector)
    if start:
        introspector.start(interval_s)
    return introspector


def enable_lineage(capacity: int = 1024,
                   ingest_marks: int = 512) -> LineageJournal:
    """Install a ``LineageJournal`` as the module-level default — the
    catalog-provenance layer every swap site stamps and every engine
    flush joins against. Call AFTER ``enable()`` (the journal binds the
    live registry for its staleness/freshness instruments) and BEFORE
    building the engines/drivers whose swaps you want stamped — lineage
    hooks bind at construction, same as the instruments. Returns the
    journal (served at ``/lineagez`` by any subsequently built
    ``ObsServer``)."""
    journal = LineageJournal(capacity=capacity, ingest_marks=ingest_marks)
    set_lineage(journal)
    return journal


def enable_disttrace(capacity: int = 256,
                     marks: int = 1024) -> CriticalPathAnalyzer:
    """Install a ``CriticalPathAnalyzer`` as the module-level default —
    the ingest→servable critical-path layer the WAL, driver, adaptive
    and engine tiers stamp. Call AFTER ``enable()`` (the analyzer binds
    the live registry for its ``critical_path_s{stage}`` gauges) and
    BEFORE building the logs/drivers/engines whose path you want
    attributed — hooks bind at construction, same as the instruments.
    Returns the analyzer (served at ``/criticalpathz`` by any
    subsequently built ``ObsServer``)."""
    analyzer = CriticalPathAnalyzer(capacity=capacity, marks=marks)
    set_disttrace(analyzer)
    return analyzer


def enable_contention(interval_s: float = 1.0, start: bool = True,
                      **tracker_kwargs) -> ContentionTracker:
    """Install a ``ContentionTracker`` as the module-level default —
    the concurrency plane every ``named_lock``/``named_rlock``/
    ``named_condition`` site resolves. Call AFTER ``enable()`` (the
    tracker binds the live registry for its ``lock_*``/``thread_*``/
    ``contention_*`` instruments; under the null layer it still tracks
    its own lock/thread stats and publishes nothing) and BEFORE
    building the models/engines/drivers whose locks you want
    instrumented — primitives bind at construction, same as every
    other plane. Starts the thread sampler unless ``start=False``.
    Returns the tracker (served at ``/contentionz`` by any subsequently
    built ``ObsServer``)."""
    prev = get_contention()
    if prev is not None:  # re-enable must not leak the old sampler
        prev.stop()
    tracker = ContentionTracker(**tracker_kwargs)
    set_contention(tracker)
    if start:
        tracker.start(interval_s)
    return tracker


def enable_transfers(guard: str = "off", watch_hot: bool = True,
                     **ledger_kwargs) -> TransferLedger:
    """Install a ``TransferLedger`` as the module-level default — the
    host↔device TRANSFER plane every deliberate boundary crossing
    notes into, the implicit-transfer guard the hot paths scope, and
    the retrace watch over the hot jitted kernels. ``guard`` arms the
    ``jax.transfer_guard`` scopes (``"off"`` production default /
    ``"log"`` / ``"disallow"`` for debug+CI); ``watch_hot`` registers
    the repo's hot jitted functions (``online_train``, ``dsgd_train``,
    the tiered store's scatter/commit kernels) for retrace watching.
    Call AFTER ``enable()`` (the ledger binds the live registry for
    its ``transfer_*``/``retrace_*``/``implicit_*`` instruments;
    under the null layer it still keeps its own totals and publishes
    nothing). Returns the ledger (served at ``/transferz`` by any
    subsequently built ``ObsServer``)."""
    ledger = TransferLedger(guard_mode=guard, **ledger_kwargs)
    set_transfers(ledger)
    if watch_hot:
        # lazy: obs must not pull the kernel modules at import time
        from large_scale_recommendation_tpu.ops import sgd as _sgd
        from large_scale_recommendation_tpu.store import tiered as _tiered

        ledger.watch("online_train", _sgd.online_train)
        ledger.watch("dsgd_train", _sgd.dsgd_train)
        ledger.watch("store_scatter_slots", _tiered._scatter_slots)
        ledger.watch("store_commit_slots", _tiered._commit_slots)
    return ledger


def enable_budget(target_s: float, objective: float = 0.99,
                  **budget_kwargs) -> RolloutBudget:
    """Install a ``RolloutBudget`` as the module-level default — the
    ROLLOUT plane the serving seams note version-keyed outcomes into
    and the canary verdict engine decides over. ``target_s`` /
    ``objective`` define the latency SLO the budget burns against;
    ``budget_kwargs`` pass through to ``RolloutBudget`` (window sizes,
    cohort bounds, and the verdict thresholds — ``min_samples``,
    ``sample_budget``, ``burn_ratio``, ``p99_ratio``, ``shed_tol``,
    ``eval_tol``). Call AFTER ``enable()`` (the budget binds the live
    registry for its ``slo_*``/``rollout_*`` instruments) and BEFORE
    building the engines whose outcomes you want attributed — the
    noting handle binds at construction, same as every other plane.
    Returns the budget (served at ``/budgetz`` by any subsequently
    built ``ObsServer``)."""
    budget = RolloutBudget(target_s, objective=objective, **budget_kwargs)
    set_budget(budget)
    return budget


def enable_requests(target_s: float, objective: float = 0.99,
                    **telemetry_kwargs) -> RequestTelemetry:
    """Install a ``RequestTelemetry`` as the module-level default — the
    REQUEST plane the serving seams mark stage ledgers into and the
    tail exemplars land in. ``target_s``/``objective`` define the SLO
    the violation class keys off (give it the SAME target as the
    engine's ``SLOTracker`` so the exemplar p99 and the SLO reservoir
    price one stream); ``telemetry_kwargs`` pass through to
    ``RequestTelemetry`` (``window``, ``max_exemplars``,
    ``slow_keep``). Call AFTER ``enable()`` (the plane binds the live
    registry for its ``request_stage_*`` instruments) and BEFORE
    building the engines whose requests you want decomposed — the
    noting handle binds at construction, same as every other plane.
    Returns the telemetry (served at ``/slowz`` by any subsequently
    built ``ObsServer``)."""
    telemetry = RequestTelemetry(target_s, objective=objective,
                                 **telemetry_kwargs)
    set_requests(telemetry)
    return telemetry


def disable() -> None:
    """Restore the zero-cost defaults: null registry/tracer, no flight
    recorder, event journal, lineage journal or contention tracker,
    and no introspector (its compile hook is removed and sampler
    threads are stopped first)."""
    from large_scale_recommendation_tpu.obs import registry as _r
    from large_scale_recommendation_tpu.obs import trace as _t

    recorder = get_recorder()
    if recorder is not None:
        recorder.stop()
    introspector = get_introspector()
    if introspector is not None:
        introspector.close()
    contention = get_contention()
    if contention is not None:
        contention.stop()
    set_contention(None)
    set_introspector(None)
    set_recorder(None)
    set_events(None)
    set_lineage(None)
    set_disttrace(None)
    set_store(None)
    set_transfers(None)
    set_budget(None)
    set_requests(None)
    set_registry(_r.NULL_REGISTRY)
    set_tracer(_t.NULL_TRACER)


def enabled() -> bool:
    """Whether a live (non-null) registry is currently installed."""
    return get_registry().enabled
