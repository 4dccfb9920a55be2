"""The serving engine: sustained-throughput top-K over a versioned catalog.

``MFModel.recommend(mesh=...)`` is a per-call surface: every call maps
ids, sizes its chunk to the request (``chunk = min(chunk, pow2_pad(n))``)
and walks the catalog. Fine for one big batch; wrong shape for a request
stream, where (a) every new request size compiles a fresh executable,
(b) tiny requests leave the MXU idle, and (c) a retrain swap must be
noticed by hand. ``ServingEngine`` is the serving loop those calls were
missing (the FLAME argument, arxiv 2509.22681: recommendation serving
needs its own batching/caching engine, not per-call model invocation):

- **request micro-batching** — ``submit`` accumulates user rows across
  requests; ``flush`` packs them into micro-batches of at most
  ``max_batch`` rows, each padded to a pow2 bucket, so the whole request
  stream executes against a *bounded* executable family
  (``utils.shapes.pow2_buckets``: O(log max_batch) shapes, not
  O(#requests)). ``recommend`` is the submit+flush convenience for one
  request; ``serve`` drives a whole request iterable.
- **versioned catalog** — the engine binds a ``ShardedCatalog`` stamped
  with ``catalog_version(model.V)``. ``refresh()`` re-shards the current
  (or a newly passed) model in O(1) calls — one ``device_put`` per
  table, **zero recompiles** (the scoring step is shape-keyed, and the
  refreshed catalog has the same geometry) — which makes the
  retrain-swap → serve handoff (``AdaptiveMF``) a first-class operation
  instead of a stale-cache hazard.
- **bf16 scoring** (``dtype="bfloat16"``) — catalog and query rows are
  held in bf16 (half the HBM reads and ICI bytes in the all_gather+dot
  hot loop); scores accumulate in f32, so the merge and the dead-slot
  sentinel contract are unchanged. Parity with f32 is test-bounded.
- **pipelined dispatch** — micro-batches run two deep: host-side
  exclusion building for batch i+1 overlaps device scoring of batch i
  (same pattern as ``mesh_top_k_recommend``'s chunk loop), with buffer
  donation on non-CPU meshes.

- **two-stage fast path** (``retrieval=RetrievalConfig(...)``) — stage 1
  scores an int8-quantized catalog (optionally routed through a
  k-means-clustered MIPS index, ``serving.retrieval``) for
  ``k·overfetch`` candidates; stage 2 rescores them exactly in f32.
  Per-request cost stops scaling with the catalog; recall@k vs the
  exact path is test-pinned (≥0.95 at overfetch 4).
- **admission control** (``admission=AdmissionController(...)``) — the
  SLO error budget (``obs.health.SLOTracker``) drives a brownout
  ladder: widen batching → serve stage-1-only (results flagged
  ``degraded``) → reject with ``AdmissionRejectedError``
  (``serving.admission``).
- **delta catalog swaps** (``apply_delta``) — install only the rows
  touched since the last version (the streaming driver knows them from
  its WAL batches): one device scatter per table plus re-quantization
  of exactly the dirty int8 rows — no full-table rebuild, zero
  recompiles, bit-equivalent to a rebuild (test-pinned).

Throughput accounting lives in ``stats`` (requests, rows, micro-batches,
bucket histogram, delta swaps) plus ``executable_variants`` — the number
of compiled shape variants actually backing the stream, the O(#buckets)
pin the compile-count regression test asserts on. Results are
``RecResult`` tuples — ``(ids, scores[, mask])`` exactly as before, plus
``.catalog_version`` (which build answered; clients detect mid-flight
swaps) and ``.degraded`` (stage-1-only admission fallback) attributes.
"""

from __future__ import annotations

import functools
import threading
import time

import numpy as np

import jax.numpy as jnp

from large_scale_recommendation_tpu.models.mf import MFModel, _assemble_topk
from large_scale_recommendation_tpu.obs.budget import get_budget
from large_scale_recommendation_tpu.obs.contention import named_rlock
from large_scale_recommendation_tpu.obs.disttrace import get_disttrace
from large_scale_recommendation_tpu.obs.events import get_events
from large_scale_recommendation_tpu.obs.lineage import get_lineage
from large_scale_recommendation_tpu.obs.registry import get_registry
from large_scale_recommendation_tpu.obs.requests import get_requests
from large_scale_recommendation_tpu.obs.trace import NULL_SPAN, get_tracer
from large_scale_recommendation_tpu.obs.transfers import (
    get_transfers,
    guard_scope,
)
from large_scale_recommendation_tpu.parallel.partitioner import (
    as_partitioner,
)
from large_scale_recommendation_tpu.parallel.serving import (
    _mesh_topk_step,
    catalog_version,
    mesh_supports_donation,
    run_pipelined_topk,
    shard_catalog,
)
from large_scale_recommendation_tpu.serving.admission import (
    AdmissionController,
)
from large_scale_recommendation_tpu.serving.retrieval import (
    RetrievalConfig,
    TwoStageRetriever,
)
from large_scale_recommendation_tpu.utils.metrics import (
    ThroughputMeter,
    _exclusion_builder,
)
from large_scale_recommendation_tpu.utils.shapes import pow2_buckets, pow2_pad


class RecResult(tuple):
    """One request's result: unpacks exactly like the historical
    ``(ids, scores)`` / ``(ids, scores, mask)`` tuples, with serving
    metadata on top — ``catalog_version`` (the build that answered;
    compare across requests to detect a mid-flight swap) and
    ``degraded`` (True when admission control served stage-1-only
    approximate scores)."""

    catalog_version: int
    degraded: bool

    def __new__(cls, parts, catalog_version: int, degraded: bool = False):
        self = tuple.__new__(cls, parts)
        self.catalog_version = int(catalog_version)
        self.degraded = bool(degraded)
        return self


class ServingEngine:
    """Micro-batching top-K engine over one model snapshot.

    Parameters: ``model`` (an ``MFModel``; streaming/adaptive models
    snapshot via ``to_model()``), ``k`` results per user, ``mesh`` (the
    catalog shards over it; default = all devices), ``train`` (a
    ``Ratings`` or ``(user_ids, item_ids)`` exclusion set, same contract
    as ``MFModel.recommend``), ``dtype`` (``"bfloat16"`` opts into the
    half-width catalog), ``max_batch``/``min_bucket`` (the pow2 bucket
    policy — ``max_batch`` must be a power of two), ``slo`` (an
    ``obs.health.SLOTracker``; every flushed REQUEST's end-to-end
    latency — queue wait since submit plus the synced flush wall — is
    recorded into its attainment window), ``retrieval`` (a
    ``RetrievalConfig`` or ``"two_stage"``: the int8 score-then-rescore
    fast path), ``admission`` (an ``AdmissionController``: the SLO-burn
    brownout ladder).

    Results carry the ``recommend`` conventions exactly: int64 ids,
    unknown users → -1/0.0 rows, below-catalog slots → -1/0.0.

    Thread-safety: ``submit``/``flush``/``refresh`` serialize on one
    lock, so a refresh landing from another thread (the ``AdaptiveMF``
    swap auto-refresh) can never rebind the catalog mid-flush — every
    flush serves entirely from one catalog version.
    """

    def __init__(self, model: MFModel, k: int = 10, mesh=None,
                 train=None, dtype=None, max_batch: int = 1024,
                 min_bucket: int = 8, slo=None, retrieval=None,
                 admission: AdmissionController | None = None,
                 user_store=None):
        # store-backed user side (store.TieredFactorStore): the engine
        # holds NO user table — each micro-batch's user rows gather
        # straight from the tiered store at serve time (serve_rows: hot
        # rows from the device pool, cold rows from host RAM). A cold
        # row's transfer wall lands inside the flush, so tier misses
        # are priced into the SLO tracker like any other serving cost.
        # The store and the bound model must share one row space (the
        # store IS the model's user table).
        self._user_store = user_store
        if max_batch & (max_batch - 1):
            raise ValueError(f"max_batch must be a power of two, "
                             f"got {max_batch}")
        if min_bucket & (min_bucket - 1) or not 0 < min_bucket <= max_batch:
            raise ValueError(f"min_bucket must be a power of two in "
                             f"[1, max_batch], got {min_bucket}")
        self.k = int(k)
        # two-stage fast path: a RetrievalConfig (or "two_stage" for the
        # defaults) swaps the exact mesh scorer for int8
        # score-then-rescore (serving.retrieval). None = exact path,
        # byte-for-byte the historical engine.
        if retrieval == "two_stage":
            retrieval = RetrievalConfig()
        if retrieval is not None and not isinstance(retrieval,
                                                    RetrievalConfig):
            raise TypeError(f"retrieval must be a RetrievalConfig or "
                            f"'two_stage', got {type(retrieval).__name__}")
        self._retrieval_cfg: RetrievalConfig | None = retrieval
        self._retriever: TwoStageRetriever | None = None
        # ``mesh`` accepts a raw Mesh (legacy), a Partitioner, or None
        # (default global partitioner) — the catalog and the scoring step
        # resolve their shardings through the partitioner's rules table
        self.partitioner = as_partitioner(mesh)
        self.mesh = self.partitioner.mesh
        self.max_batch = int(max_batch)
        self.min_bucket = int(min_bucket)
        # the full static shape family requests can execute against —
        # its LENGTH is the compile bound the regression test pins
        self.bucket_family = pow2_buckets(min_bucket, max_batch)
        self._dtype = jnp.dtype(dtype or jnp.float32)
        self._train = train
        self._pending: list[np.ndarray] = []
        # submit stamps: one clock read per request, consumed at flush —
        # the queue-wait half of the per-REQUEST latency the SLO tracker
        # records (flush wall alone recovers the moment shedding shrinks
        # batches, which let the admission ladder relax while backlogged
        # requests were still seconds late — measured in the traffic sim)
        self._pending_t: list[float] = []
        # named_rlock: raw unless the contention plane is armed, in
        # which case the engine's submit/flush/refresh serialization
        # publishes as lock_*{lock="serving.engine"}
        self._lock = named_rlock("serving.engine")
        self.stats = {"requests": 0, "rows": 0, "microbatches": 0,
                      "flushes": 0, "refreshes": 0, "delta_swaps": 0,
                      "deferred_delta_rows": 0, "delta_flushes": 0,
                      "buckets": {}}
        # swap-coalescing buffers (apply_delta(defer=True)): row →
        # newest full-precision vector, installed as ONE swap by
        # flush_deltas() — how N ingest consumers ship deltas without
        # N version bumps thrashing the catalog (ISSUE 13)
        self._pending_items: dict[int, np.ndarray] = {}
        self._pending_users: dict[int, np.ndarray] = {}
        self.meter = ThroughputMeter()
        # observability binds at CONSTRUCTION: with the default null
        # registry the handles below are shared no-op singletons and
        # _obs_on gates every clock read, so an uninstrumented engine
        # does zero registry/tracer work on the hot path (pinned by
        # tests/test_obs_integration.py)
        obs = get_registry()
        self._obs_on = obs.enabled
        self._trace = get_tracer()
        # structured event journal (obs.events): None unless installed —
        # the catalog-swap emission below is one `is not None` test
        self._events = get_events()
        # lineage journal (obs.lineage): None unless installed — every
        # swap stamps its provenance, every flush joins the served
        # version back (the staleness gauge); one `is not None` test
        # per swap/flush. Bound BEFORE the constructor's refresh() so
        # the initial catalog build is stamped too.
        self._lineage = get_lineage()
        # critical-path analyzer (obs.disttrace): every flush notes the
        # served version (the first one prices the flush_wait stage) —
        # one `is not None` test per flush, and the analyzer side is
        # non-blocking, same rule as the lineage join below
        self._disttrace = get_disttrace()
        # rollout budget (obs.budget): every flush attributes each
        # request's latency to the cohort of the catalog_version that
        # served it, every shed submit notes the rejection against the
        # live version — one `is not None` test per seam
        self._budget = get_budget()
        # request telemetry (obs.requests): every flush marks a stage
        # ledger whose per-request sums reconcile against the SLO-
        # recorded walls, and the tail exemplars land in /slowz — one
        # `is not None` test per seam, no ledger allocation when off
        self._requests = get_requests()
        self._m_qwait = obs.histogram("serving_queue_wait_s")
        self._m_assembly = obs.histogram("serving_batch_assembly_s")
        self._m_flush = obs.histogram("serving_flush_s")
        self._m_requests = obs.counter("serving_requests_total")
        self._m_rows = obs.counter("serving_rows_total")
        self._obs = obs
        # SLO wiring (obs.health.SLOTracker): each flushed request's
        # end-to-end latency (submit stamp → synced flush end) feeds
        # the sliding attainment window. None (the default) is one
        # pointer test per flush: no tracker, no recording.
        # An admission controller brings its own tracker: when no
        # separate slo was given, the engine records into the
        # controller's, so the burn the ladder reads is the burn this
        # engine produces (pass both only if they share a tracker).
        self._admission = admission
        # _slo_adopted marks a tracker taken FROM a controller (vs an
        # explicit slo= argument, which the caller owns): only adopted
        # trackers are rebound when attach_admission swaps controllers —
        # otherwise the swapped-in ladder would read a tracker nobody
        # records into and sit at "normal" through any overload.
        self._slo_adopted = slo is None and admission is not None
        if self._slo_adopted:
            slo = admission.slo
        self._slo = slo
        # swap-observation hook: called as ``on_refresh(version)`` after
        # every successful refresh, INSIDE the engine lock so concurrent
        # refreshes report their versions in swap order (the lock is
        # re-entrant, so a hook that re-enters the engine from the same
        # thread cannot deadlock; a hook must not block on another
        # thread that needs this engine). The seam the streaming driver
        # hangs its catalog-swap telemetry on — how an ingest tier
        # *observes* that a retrain actually reached serving.
        self.on_refresh = None
        self.refresh(model)

    # -- catalog lifecycle ---------------------------------------------------

    def refresh(self, model: MFModel | None = None) -> int:
        """(Re)bind the engine to ``model`` (default: the current one).

        The swap-in path after a retrain: re-shards U and the catalog
        (one ``device_put`` each), restamps the version, and rebinds the
        scoring step. No recompilation happens unless the table
        *geometry* changed (vocab growth) — the executable cache is
        keyed on shapes, not versions. Returns the new catalog version
        (and reports it to ``on_refresh``, if set).
        """
        swap_detail = None
        with self._lock:
            version = self._refresh(model)
            hook = self.on_refresh
            if hook is not None:
                hook(version)
            if self._events is not None:
                swap_detail = {"version": version,
                               "refreshes": self.stats["refreshes"],
                               "rows": int(self.catalog_rows)}
        if self._lineage is not None:
            # provenance stamp at the swap instant; layers that know
            # more (the streaming driver's WAL watermark, the adaptive
            # retrain id) enrich the SAME record by version. Outside
            # the engine lock, same rule as the event emit.
            self._lineage.record_swap(version, source="engine_refresh")
        if swap_detail is not None:
            # journaled OUTSIDE the engine lock: the emit may hit the
            # journal's JSONL disk mirror, and every submit/flush/serve
            # serializes on this lock
            self._events.emit("serving.catalog_swap", **swap_detail)
        return version

    def _refresh(self, model: MFModel | None) -> int:
        if model is not None:
            self.model = model
        model = self.model
        # a full rebuild supersedes anything still deferred: the new
        # snapshot already carries every row's current value, and a
        # later flush_deltas() scattering stale pre-refresh vectors
        # over it would silently revert rows
        self._pending_items.clear()
        self._pending_users.clear()
        self._item_ids_of_row = np.asarray(model.items.ids)
        item_mask = self._item_ids_of_row >= 0
        if self._retrieval_cfg is not None:
            # fast path: int8 stage-1 structure + f32 rescore table
            # (serving.retrieval; single-host replicated — the int8
            # catalog is ~4× smaller than the f32 one the mesh path
            # shards). ``dtype`` doesn't apply: stage 1 is already
            # int8 and stage 2 must rescore full-precision.
            self._catalog = None
            self._retriever = TwoStageRetriever(
                model.V, item_mask=item_mask,
                config=self._retrieval_cfg,
                partitioner=self.partitioner)
        else:
            self._catalog = shard_catalog(
                model.V, self.partitioner, item_mask=item_mask,
                dtype=self._dtype)
            n_dev = self.partitioner.num_blocks
            rpb = self._catalog.rows_per_shard
            self._k_local = min(self.k, rpb)
            self._k_out = min(self.k, n_dev * self._k_local)
            self._step = _mesh_topk_step(
                self.mesh, self._k_local, self._k_out, rpb,
                donate=mesh_supports_donation(self.mesh))
        if self._user_store is not None:
            # store-backed: no engine-held user table at all (the whole
            # point — the user table may be 10-100× device memory);
            # _serve_rows gathers each micro-batch through the store
            self._U = None
            n_users = int(self._user_store.num_rows)
        else:
            U = jnp.asarray(model.U)
            want = (jnp.float32 if self._retrieval_cfg is not None
                    else self._dtype)
            self._U = U.astype(want) if U.dtype != want else U
            n_users = int(U.shape[0])
        tu, ti = model._train_rows(self._train)
        self._build_excl = _exclusion_builder(tu, ti, n_users)
        self.stats["refreshes"] += 1
        if self._obs_on:
            # version-labeled swap counter: the serving-side proof of
            # WHICH retrain snapshots actually reached this engine
            self._obs.counter("serving_catalog_swaps_total",
                              version=self.version).inc()
            self._obs.gauge("serving_catalog_version").set(self.version)
            self._trace.instant("serving/catalog_swap",
                                version=self.version)
        return self.version

    def apply_delta(self, item_rows=None, V_rows=None,
                    user_rows=None, U_rows=None,
                    defer: bool = False) -> int:
        """Install ONLY the touched factor rows — the streaming
        ingest→serve handoff without a whole-table rebuild. ``*_rows``
        are indices into the bound model's row space (geometry must be
        unchanged; vocab growth is a full ``refresh``), ``V_rows`` /
        ``U_rows`` the matching full-precision factors. The bound
        model's arrays are patched too (so a later ``refresh()``
        re-shards the post-delta state, never silently reverts it),
        the catalog version restamps from the patched table, and the
        fast path re-quantizes exactly the dirty int8 rows. Zero
        recompiles — executables are keyed on shapes, and a delta
        never changes one. Returns the new catalog version (reported
        to ``on_refresh``, same as a full refresh).

        ``defer=True`` is the swap-COALESCING form: the rows buffer
        (newest value per row wins) instead of installing, and the next
        ``flush_deltas()`` installs everything pending as ONE swap —
        one scatter per table, one version bump, one lineage stamp —
        however many consumers shipped deltas in between. Deferred rows
        are invisible to serving until that flush (the freshness the
        coalescing window trades for not thrashing catalog versions);
        the flushed state is bit-equal to applying each delta eagerly
        in arrival order. Returns the (unchanged) current version."""
        if self._user_store is not None:
            # the store IS the live user state — serve_rows reads it
            # directly, so there is nothing to install on the user
            # side (shipping stale copies could only go backwards)
            user_rows, U_rows = None, None
        if defer:
            with self._lock:
                sides = []
                for rows, vals, bound, pending, what in (
                        (item_rows, V_rows, int(self.model.V.shape[0]),
                         self._pending_items, "catalog"),
                        (user_rows, U_rows, int(self.model.U.shape[0]),
                         self._pending_users, "table")):
                    if rows is None or not len(rows):
                        continue
                    rows = np.asarray(rows)
                    if rows.max() >= bound:
                        # the loud vocab-growth error must fire at
                        # defer time, not surface later from an
                        # unrelated flush — and BEFORE either side
                        # buffers, so a rejected delta never leaves a
                        # torn half pending
                        raise ValueError(
                            f"delta row {int(rows.max())} outside "
                            f"{what} of {bound} rows — vocab grew; "
                            f"use refresh()")
                    sides.append((rows, np.asarray(vals), pending))
                for rows, vals, pending in sides:
                    for j, r in enumerate(rows.tolist()):
                        pending[int(r)] = vals[j]
                    self.stats["deferred_delta_rows"] += len(rows)
                return self.version
        swap_detail = None
        with self._lock:
            model = self.model
            n_items = int(model.V.shape[0])
            n_users = int(model.U.shape[0])
            if item_rows is not None and len(item_rows):
                item_rows = np.asarray(item_rows)
                if item_rows.max() >= n_items:
                    raise ValueError(
                        f"delta item row {int(item_rows.max())} outside "
                        f"catalog of {n_items} rows — vocab grew; use "
                        f"refresh()")
                ledger = get_transfers()
                t0 = time.perf_counter() if ledger is not None else 0.0
                vals = jnp.asarray(V_rows)
                idx = jnp.asarray(item_rows)
                if ledger is not None:  # the delta ship crosses h2d
                    ledger.note_transfer("serving.delta", "h2d",
                                         int(vals.nbytes),
                                         time.perf_counter() - t0)
                V = jnp.asarray(model.V)
                model.V = V.at[idx].set(vals.astype(V.dtype))
                version = catalog_version(model.V)
                if self._catalog is not None:
                    self._catalog = self._catalog.apply_delta(
                        item_rows, vals, version=version)
                else:
                    self._retriever.apply_delta(item_rows, vals, version)
            if user_rows is not None and len(user_rows):
                user_rows = np.asarray(user_rows)
                if user_rows.max() >= n_users:
                    raise ValueError(
                        f"delta user row {int(user_rows.max())} outside "
                        f"table of {n_users} rows — vocab grew; use "
                        f"refresh()")
                ledger = get_transfers()
                t0 = time.perf_counter() if ledger is not None else 0.0
                uvals = jnp.asarray(U_rows)
                uidx = jnp.asarray(user_rows)
                if ledger is not None:
                    ledger.note_transfer("serving.delta", "h2d",
                                         int(uvals.nbytes),
                                         time.perf_counter() - t0)
                U = jnp.asarray(model.U)
                model.U = U.at[uidx].set(uvals.astype(U.dtype))
                self._U = self._U.at[uidx].set(
                    uvals.astype(self._U.dtype))
            self.stats["delta_swaps"] += 1
            version = self.version
            hook = self.on_refresh
            if hook is not None:
                hook(version)
            if self._obs_on:
                self._obs.counter("serving_catalog_delta_total").inc()
                self._obs.gauge("serving_catalog_version").set(version)
            if self._events is not None:
                swap_detail = {
                    "version": version,
                    "item_rows": int(0 if item_rows is None
                                     else len(item_rows)),
                    "user_rows": int(0 if user_rows is None
                                     else len(user_rows)),
                    "delta_swaps": self.stats["delta_swaps"]}
        if self._lineage is not None:
            self._lineage.record_swap(version, source="engine_delta")
        if swap_detail is not None:
            # journaled OUTSIDE the engine lock, same rule as refresh()
            self._events.emit("serving.catalog_delta", **swap_detail)
        return version

    def flush_deltas(self) -> int:
        """Install every ``apply_delta(defer=True)`` row pending as ONE
        swap (no-op when nothing is pending). Deltas deferred AFTER the
        pending set is taken ride the next flush — never lost. Returns
        the catalog version serving now runs on.

        The (re-entrant) engine lock is held across take AND install:
        releasing between them would let a full ``refresh()`` land in
        the gap and then be overwritten by the already-taken stale rows
        — the silent row reversion the refresh-clears-pending rule
        exists to prevent. The one cost is that the install's journal
        emit runs under the lock on THIS (rare, coalescing) path; the
        common direct ``apply_delta``/``refresh`` paths keep the
        emit-outside-lock discipline."""
        with self._lock:
            items, self._pending_items = self._pending_items, {}
            users, self._pending_users = self._pending_users, {}
            if not items and not users:
                return self.version
            self.stats["delta_flushes"] += 1

            def pack(pending):
                if not pending:
                    return None, None
                rows = np.fromiter(pending.keys(), np.int64,
                                   len(pending))
                return rows, np.stack([pending[int(r)] for r in rows])

            i_rows, i_vals = pack(items)
            u_rows, u_vals = pack(users)
            return self.apply_delta(item_rows=i_rows, V_rows=i_vals,
                                    user_rows=u_rows, U_rows=u_vals)

    @property
    def pending_delta_rows(self) -> int:
        """Rows buffered by ``apply_delta(defer=True)`` awaiting the
        next ``flush_deltas()``."""
        with self._lock:
            return len(self._pending_items) + len(self._pending_users)

    @property
    def version(self) -> int:
        """The bound catalog's version token (``catalog_version``)."""
        if self._catalog is not None:
            return self._catalog.version
        return self._retriever.version

    @property
    def admission(self) -> AdmissionController | None:
        """The attached admission controller (None = no ladder)."""
        return self._admission

    @property
    def retriever(self):
        """The two-stage fast path's ``TwoStageRetriever`` (None on
        the exact path) — its ``catalog.stats`` carry the index
        geometry the bench publishes."""
        return self._retriever

    @property
    def catalog_rows(self) -> int:
        """Real catalog height of the bound build (either path)."""
        if self._catalog is not None:
            return self._catalog.n_rows
        return self._retriever.n_rows

    @property
    def executable_variants(self) -> int:
        """Compiled shape variants behind the bound scoring step — grows
        with the bucket family (O(#buckets)), NOT the request count.
        Exact path: the per-mesh step cache (shared per (mesh,
        geometry): other same-geometry users of this mesh add their
        shape variants to this count too). Fast path: the distinct
        (layout, bucket, candidate-width) shapes THIS retriever
        dispatched (the module-level jits additionally share compiled
        code across engines — this counts what the engine asked for)."""
        if self._retriever is not None:
            return len(self._retriever.buckets_seen)
        return self._step._cache_size()

    def attach_admission(self, controller: AdmissionController) -> None:
        """Arm (or swap) admission control on a live engine — the
        traffic-simulator idiom: probe raw capacity admission-free,
        then attach the controller without rebuilding the catalog.
        Unless the constructor was given its own ``slo=`` tracker, the
        controller's tracker becomes the engine's — INCLUDING on a
        swap, so a newly attached ladder always reads the burn this
        engine's flushes produce (a previously adopted tracker would
        otherwise keep receiving the samples while the new ladder
        starved below its warmup guard forever)."""
        with self._lock:
            self._admission = controller
            if controller is not None and (self._slo is None
                                           or self._slo_adopted):
                self._slo = controller.slo
                self._slo_adopted = True

    # -- request intake ------------------------------------------------------

    def submit(self, user_ids) -> int:
        """Queue one request; returns its index into ``flush()``'s
        result list. Nothing runs until ``flush`` (or ``recommend``/
        ``serve``, which flush for you). With admission control at the
        ``shed`` level this raises ``AdmissionRejectedError`` — already
        queued requests still flush (shedding bounds the queue, it
        never drops accepted work)."""
        if self._admission is not None:
            try:
                self._admission.check_admit()  # raises when shedding
            except Exception as e:
                if self._budget is not None:
                    # the shed outcome is attributed to the version that
                    # WOULD have served — overload during a canary
                    # charges the canary's cohort, not a wall-clock bin
                    self._budget.note_shed(self.version)
                if self._requests is not None:
                    # a shed IS a tail exemplar: always kept, carrying
                    # the rung and burn that drove the rejection
                    self._requests.note_shed(
                        version=self.version,
                        level=getattr(e, "level", "shed"),
                        burn=getattr(e, "burn", None),
                        queue_depth=len(self._pending))
                raise
        with self._lock:
            self._pending.append(np.asarray(user_ids))
            self._pending_t.append(time.perf_counter())
            return len(self._pending) - 1

    def recommend(self, user_ids, return_mask: bool = False):
        """Serve one request now (micro-batched internally: a request
        larger than ``max_batch`` still executes in bucketed slices).
        Requests already queued via ``submit`` are served in the same
        pass — ``flush()`` first if you need their results."""
        with self._lock:  # submit+flush as ONE step: a concurrent
            # recommend() must not drain this ticket into its own flush
            idx = self.submit(user_ids)
            return self.flush(return_mask=return_mask)[idx]

    def serve(self, requests, return_mask: bool = False) -> list:
        """Serve an iterable of requests, coalescing them into shared
        micro-batches: rows from small adjacent requests pack into one
        padded kernel call. Returns one result per request, in order —
        a ``RecResult`` normally, or the ``AdmissionRejectedError``
        INSTANCE for a request the admission ladder shed (the ladder
        can flip mid-stream via the per-flush ``observe``; raising
        there would discard every already-computed result and leave
        this stream's unflushed tickets to misalign the next caller's
        ``flush()``). Requests already queued via ``submit`` are served
        in the same pass but NOT returned here — ``flush()`` first if
        you need their results. Holds the engine lock for the whole
        stream, so concurrent producers cannot interleave tickets into
        this stream's flushes."""
        from large_scale_recommendation_tpu.serving.admission import (
            AdmissionRejectedError,
        )

        with self._lock:
            out: list = []
            next_fill = 0  # first not-yet-filled placeholder in out
            queued_rows = 0
            skip = len(self._pending)  # pre-queued tickets: not ours

            def drain():
                nonlocal skip, queued_rows, next_fill
                for res in self.flush(return_mask=return_mask)[skip:]:
                    while out[next_fill] is not None:
                        next_fill += 1  # skip shed markers
                    out[next_fill] = res
                skip = 0
                queued_rows = 0

            for r in requests:
                r = np.asarray(r)
                try:
                    self.submit(r)
                    out.append(None)  # filled by the covering flush
                    queued_rows += len(r)
                except AdmissionRejectedError as e:
                    out.append(e)
                    continue
                # under admission WIDEN the flush threshold stretches to
                # widen_factor × max_batch: more rows coalesce per
                # flush (fewer dispatches, fuller buckets) at the cost
                # of per-request latency — the cheapest throughput the
                # brownout ladder can buy
                limit = self.max_batch
                if self._admission is not None:
                    limit = int(limit * self._admission.widen_factor)
                if queued_rows >= limit:
                    drain()
            if self._pending:
                drain()
            return out

    # -- execution -----------------------------------------------------------

    def flush(self, return_mask: bool = False) -> list:
        """Run every queued request through bucketed micro-batches and
        return their results in submit order (``RecResult`` tuples —
        ``(ids, scores[, mask])`` plus the serving catalog version and
        the degraded flag). Holds the engine lock: the whole flush
        serves from one catalog version — the version every result of
        this flush carries."""
        with self._lock:
            requests, self._pending = self._pending, []
            if not requests:
                return []
            # the admission level is read ONCE per flush: every result
            # of a flush is uniformly exact or uniformly degraded
            degraded = (self._admission is not None
                        and self._admission.degrade_active
                        and self._retriever is not None)
            t0 = time.perf_counter()
            stamps, self._pending_t = self._pending_t, []
            # stage ledger (obs.requests): anchored on the SAME t0 the
            # flush wall measures from — None when the plane is off (no
            # allocation, no clock reads on the null path)
            led = (self._requests.ledger(t0)
                   if self._requests is not None else None)
            if self._obs_on:
                for ts in stamps:
                    self._m_qwait.observe(t0 - ts)
            # the flush's seams (obs.trace.SEAMS): profiler annotations
            # on the device trace's clock, each also a span on a live
            # tracer; with the request plane armed the ledger is the sink
            # of every seam's close (one clock read per seam)
            seam = (self._trace.seam if led is None else
                    functools.partial(self._trace.seam, sink=led.on_seam))
            # id → row space per request, then one shared row stream:
            # rows from all requests pack together, so ten 30-user
            # requests cost one 512-row micro-batch, not ten 32-row
            # calls
            with seam("serving/engine/form"):
                known_masks, row_slices, bounds = [], [], [0]
                for ids in requests:
                    u_rows, u_mask = self.model.users.rows_for(ids)
                    known = u_mask > 0
                    known_masks.append((len(ids), known))
                    row_slices.append(u_rows[known])
                    bounds.append(bounds[-1] + int(known.sum()))
                rows_all = (np.concatenate(row_slices) if row_slices
                            else np.zeros(0, np.int64))
            if self._obs_on:
                # ONE clock read feeds both the assembly histogram and
                # the ledger's batch_form mark (made as the seam closed)
                # — the shared-read discipline that keeps the stage sum
                # reconcilable
                t_asm = led.last if led is not None else time.perf_counter()
                self._m_assembly.observe(t_asm - t0)
            span = NULL_SPAN
            if self._trace.enabled:
                # compile-keyed: the first flush at a fresh catalog
                # geometry carries the bucket family's XLA compiles.
                # catalog_version in the args is the serve-side join of
                # the assembled record trace: swap watermark → version
                # → the flush that made the record's trace servable.
                # NOT a seam: the benchmark emits this name itself.
                geom = (self._catalog.rows_per_shard
                        if self._catalog is not None
                        else self._retriever.n_rows)
                span = self._trace.span(
                    "serving/flush", key=("serving_flush", geom),
                    rows=len(rows_all), requests=len(requests),
                    catalog_version=int(self.version))
            with span:
                top_rows, top_scores = self._serve_rows(
                    rows_all, stage1_only=degraded, seam=seam)
            with seam("serving/engine/results"):
                version = self.version
                results = []
                for (n_ids, known), b0, b1 in zip(known_masks, bounds,
                                                  bounds[1:]):
                    results.append(RecResult(
                        _assemble_topk(
                            n_ids, self.k, known, top_rows[b0:b1],
                            top_scores[b0:b1], self._item_ids_of_row,
                            return_mask),
                        catalog_version=version, degraded=degraded))
                self.stats["requests"] += len(requests)
                self.stats["rows"] += len(rows_all)
                self.stats["flushes"] += 1
                wall = time.perf_counter() - t0
                end = t0 + wall
                # the rung exemplars report: read BEFORE observe() below
                # re-evaluates the ladder — the level that served THIS flush
                adm_level = (self._admission.level
                             if self._admission is not None else None)
                self.meter.record(len(rows_all), wall)
                if self._slo is not None:
                    # one sample per REQUEST: queue wait since submit plus
                    # the flush wall — the latency a client saw. Tracking
                    # the flush wall alone would let the burn recover while
                    # a backlog is still seconds deep (shedding shrinks
                    # batches, walls look great, clients still suffer).
                    for ts in stamps:
                        self._slo.record(end - ts)
                if self._admission is not None:
                    # the burn just moved — re-evaluate the ladder while the
                    # lock is held, so the level the NEXT submit sees is
                    # consistent with this flush's latency
                    if degraded:
                        self._admission.count_degraded(len(requests))
                    self._admission.observe()
                if self._obs_on:
                    # results are host numpy by here, so the flush wall is a
                    # SYNCED end-to-end latency, not a dispatch time
                    self._m_flush.observe(wall)
                    self._m_requests.inc(len(requests))
                    self._m_rows.inc(len(rows_all))
        if self._lineage is not None:
            # the serve-side half of the lineage join: the version every
            # result of this flush carries resolves to its provenance,
            # pricing the per-request staleness gauge. Outside flush's
            # own lock hold, AND the journal side is NON-BLOCKING
            # (observe_serve try-acquires and skips the sample under
            # contention) — the recommend() path re-enters flush with
            # the engine RLock still held, so only the journal's own
            # guarantee keeps a /lineagez scrape or bundle freeze from
            # adding tail latency to the SLO-measured serving path.
            self._lineage.observe_serve(version, requests=len(requests))
        if self._disttrace is not None:
            # the flush_wait completion of any critical-path sample
            # awaiting this build — non-blocking on the analyzer lock,
            # same rule as observe_serve above
            self._disttrace.note_serve(version)
        if self._budget is not None:
            # version-keyed outcome attribution (obs.budget): the same
            # per-request latencies the SLO priced, landed in the
            # cohort of the catalog_version that served them — a
            # regression names the deploy, not the minute. Outside
            # flush's own lock hold; the budget holds its short
            # internal lock only, never a scrape's.
            self._budget.note_results(
                version, [end - ts for ts in stamps],
                degraded=len(requests) if degraded else 0)
        if self._requests is not None and led is not None:
            # the REQUEST plane's flush note (obs.requests): the SAME
            # end/stamps floats the SLO just recorded close the stage
            # ledger, so every request's stage sum reconciles against
            # its recorded wall by construction (host_post takes the
            # flush residual, queue_wait the per-request one). Outside
            # flush's own lock hold, same rule as the budget note.
            self._requests.note_flush(
                led, end, stamps, version=version, degraded=degraded,
                rows=[b1 - b0 for b0, b1 in zip(bounds, bounds[1:])],
                admission_level=adm_level)
        return results

    def _serve_rows(self, user_rows: np.ndarray,
                    stage1_only: bool = False, seam=None):
        """Row-space scoring through pow2-bucketed micro-batches, on the
        shared two-deep dispatch pipeline (``run_pipelined_topk`` — one
        copy of the overlap + pad-clamp machinery with the per-call
        path). Routes to the exact mesh step or the two-stage fast path
        (``stage1_only`` skips the exact rescore — the admission
        ladder's degraded operating point). ``seam`` opens the seams of
        ``obs.trace.SEAMS`` (default: the bound tracer's; ``flush`` passes
        one that also closes into the request plane's ledger): the
        exclusion build, the user gather, the score dispatches (inside
        ``TwoStageRetriever.topk``) and the drain (inside
        ``run_pipelined_topk``) each open one."""
        seam = seam or self._trace.seam
        store = self._user_store

        def gather_users(cu, want_dtype):
            # store-backed: hot rows from the device pool, cold rows
            # from the host tier (their transfer wall lands inside this
            # flush — tier misses price into the SLO automatically);
            # engine-held table: the historical one-gather path
            if store is not None:
                rows = store.serve_rows(cu)
                return (rows.astype(want_dtype)
                        if rows.dtype != want_dtype else rows)
            # jnp.take (internally jitted) instead of eager advanced
            # indexing: U[idx] normalizes the index op-by-op, shipping
            # a scalar constant host→device per chunk — the armed
            # transfer guard caught exactly that
            return jnp.take(self._U, jnp.asarray(cu), axis=0)

        if self._retriever is not None:
            ret = self._retriever

            def score_chunk(cu, c):
                with seam("serving/engine/excl"):
                    excl = self._build_excl(cu, c)
                with seam("serving/engine/gather"):
                    U_chunk = gather_users(cu, jnp.float32)
                return ret.topk(U_chunk, excl, k=self.k,
                                stage1_only=stage1_only, seam=seam)

            k_out = min(self.k, ret.candidate_count(self.k))
            n_rows = ret.n_rows
            # the clustered gather materializes [bucket, slab, rank]
            # per probe: the retrieval config's bucket cap — not the
            # engine's packing cap — bounds stage-1 memory
            slice_size = min(self.max_batch, ret.config.max_bucket)
        else:
            cat, step = self._catalog, self._step

            def score_chunk(cu, c):
                with seam("serving/engine/excl"):
                    excl = self._build_excl(cu, c)
                with seam("serving/engine/gather"):
                    U_chunk = gather_users(cu, self._dtype)
                with seam("serving/engine/score_exact"):
                    return step(U_chunk, cat.V_sh, cat.w_sh,
                                jnp.asarray(excl[0]), jnp.asarray(excl[1]),
                                jnp.asarray(excl[2]))

            k_out, n_rows, slice_size = (self._k_out, cat.n_rows,
                                         self.max_batch)

        def on_batch(bucket, c):
            self.stats["microbatches"] += 1
            hist = self.stats["buckets"]
            hist[bucket] = hist.get(bucket, 0) + 1
            if self._obs_on:
                self._obs.counter("serving_microbatches_total",
                                  bucket=bucket).inc()
                self._obs.gauge("serving_bucket_occupancy",
                                bucket=bucket).set(c / bucket)

        # armed in debug/CI, a shared null context otherwise: every
        # host→device crossing inside the scoring pipeline must be an
        # explicit device_put (store cold gathers, exclusion ships) —
        # an implicit one is attributed to this site and counted
        with guard_scope("serving.serve_rows"):
            return run_pipelined_topk(
                user_rows, k=self.k, k_out=k_out, n_rows=n_rows,
                slice_size=slice_size,
                bucket_fn=lambda c: min(pow2_pad(c, self.min_bucket),
                                        slice_size),
                score_chunk=score_chunk, on_batch=on_batch, seam=seam)
