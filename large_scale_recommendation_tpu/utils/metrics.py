"""Metrics, timing, and profiling hooks.

The reference's observability is slf4j log lines (SURVEY §5): pull-window
depth logged on every change (PSOfflineMF.scala:122,163), buffer depth every
10 elements (FlinkOnlineMF.scala:76-81), model export via log lines, and
``empiricalRisk`` as the only quality metric. The TPU-native equivalents:

- ``StepTimer``: wall-clock brackets with ``block_until_ready`` on the
  result (device execution is async — un-bracketed timing measures dispatch,
  not compute).
- ``ThroughputMeter``: ratings/sec counters — the north-star benchmark
  metric (BASELINE.md).
- ``MetricsLog``: in-memory structured records + optional stdlib logging;
  the seam a dashboard would consume.
- ``profile``: DEPRECATED capture shim — routes through the unified
  ``obs.introspect.profile_trace`` layer (one process-singleton
  profiler lock shared with ``/profilez`` and watchdog postmortem
  captures) instead of calling ``jax.profiler`` on its own.

These helpers predate the unified observability layer (``obs/``) and are
now thin **shims over it**: each one keeps its original surface (every
existing caller, incl. ``StreamingDriver.telemetry()``, works unchanged)
but mirrors its measurements into the process registry whenever
``obs.enable()`` has installed one — so an old ``StepTimer`` call site
shows up in the same snapshot/Prometheus/JSONL exports as the new
instrumentation. New code should use ``obs`` directly: the
latency-distribution / labeling / export logic lives THERE, not here
(the pre-obs duplicated timing logic in this module is deprecated).
With the default null registry the mirroring is a no-op singleton call.
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import time
from typing import Any, Iterator

logger = logging.getLogger("large_scale_recommendation_tpu")

# The top-K dead-slot sentinel contract, shared by every scoring surface
# (``top_k_recommend`` / ``ranking_metrics`` here, the mesh path in
# ``parallel.serving``, and id-space assembly in ``models.mf``):
# excluded/masked catalog slots have ``DEAD_SLOT_OFFSET`` scatter-min'ed
# onto their scores, so a surfaced dead slot carries ``dot + OFFSET``
# — not exactly the offset. Consumers therefore classify by
# ``score > DEAD_SLOT_THRESHOLD`` (one decade above the offset), which is
# exact for any model with |U·V| < 9e29. ONE definition, imported
# everywhere, so the contract cannot drift between surfaces.
DEAD_SLOT_OFFSET = -1e30
DEAD_SLOT_THRESHOLD = -1e29


def block(x: Any) -> Any:
    """Block until device work producing ``x`` (array or pytree) finishes."""
    import jax

    for leaf in jax.tree_util.tree_leaves(x):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()
    return x


@dataclasses.dataclass
class StepTimer:
    """Accumulating wall-clock timer for repeated steps.

    Registry shim: each timed step also lands in the process
    ``step_timer_s{name=...}`` histogram (p50/p90/p99 live in ``obs``,
    which supersedes the mean-only accounting here)."""

    name: str = "step"
    total_s: float = 0.0
    count: int = 0
    last_s: float = 0.0

    def __post_init__(self):
        from large_scale_recommendation_tpu.obs.registry import get_registry

        self._hist = get_registry().histogram("step_timer_s", name=self.name)

    @contextlib.contextmanager
    def time(self, result_holder: list | None = None) -> Iterator[None]:
        """Time one step. If ``result_holder`` ends up holding device
        values, they are blocked on before the clock stops."""
        t0 = time.perf_counter()
        yield
        if result_holder is not None:
            block(result_holder)
        self.last_s = time.perf_counter() - t0
        self.total_s += self.last_s
        self.count += 1
        self._hist.observe(self.last_s)

    @property
    def mean_s(self) -> float:
        return self.total_s / self.count if self.count else 0.0


@dataclasses.dataclass
class ThroughputMeter:
    """Elements/second over the lifetime and per window.

    Registry shim: recorded elements/seconds also feed the
    ``meter_elements_total``/``meter_seconds_total`` counters (labeled by
    ``name``), so long-lived meters are visible in registry exports."""

    total_elements: int = 0
    total_s: float = 0.0
    name: str = "throughput"

    def __post_init__(self):
        from large_scale_recommendation_tpu.obs.registry import get_registry

        reg = get_registry()
        self._c_elems = reg.counter("meter_elements_total", name=self.name)
        self._c_secs = reg.counter("meter_seconds_total", name=self.name)

    def record(self, elements: int, seconds: float) -> None:
        self.total_elements += elements
        self.total_s += seconds
        self._c_elems.inc(elements)
        self._c_secs.inc(seconds)

    @property
    def rate(self) -> float:
        return self.total_elements / self.total_s if self.total_s else 0.0


@dataclasses.dataclass
class IngestStats:
    """Ingest-side counters for the streaming runtime (``streams/``) —
    the structured twin of the reference's pull-window/buffer-depth log
    lines (PSOfflineMF.scala:122,163, FlinkOnlineMF.scala:76-81), plus
    the durability counters those engines kept internal: queue depth and
    high-water mark, block/drop/dead-letter outcomes, and poison-record
    quarantines. Mutated under the owning queue's lock; ``snapshot()``
    returns a plain dict for telemetry consumers (the driver merges it
    with lag-in-records from the log)."""

    enqueued_batches: int = 0
    enqueued_records: int = 0
    dequeued_batches: int = 0
    dequeued_records: int = 0
    dropped_batches: int = 0
    dropped_records: int = 0
    dead_letter_batches: int = 0
    dead_letter_records: int = 0
    poison_records: int = 0
    blocked_puts: int = 0
    depth: int = 0
    depth_high_water: int = 0

    def snapshot(self) -> dict:
        return dataclasses.asdict(self)

    def publish(self, registry=None, prefix: str = "ingest",
                **labels) -> None:
        """Mirror every counter field into ``registry`` (default: the
        process one) as ``{prefix}_{field}`` gauges, so ingest counters
        show up in the same exports as the first-class instrumentation
        (``StreamingDriver.telemetry`` publishes its queue snapshot
        through the same ``publish_fields`` helper under the
        ``streams_queue`` prefix). Gauges, not counters: these fields
        are cumulative values owned by the queue, re-published wholesale
        each telemetry pass."""
        publish_fields(dataclasses.asdict(self), registry=registry,
                       prefix=prefix, **labels)


def publish_fields(fields: dict, registry=None, prefix: str = "ingest",
                   **labels) -> None:
    """ONE copy of the mapping→gauges mirroring used by
    ``IngestStats.publish`` and the streaming driver's telemetry path:
    every ``{field: number}`` item lands as a ``{prefix}_{field}`` gauge
    with the given labels. No-op under the null registry."""
    if registry is None:
        from large_scale_recommendation_tpu.obs.registry import get_registry

        registry = get_registry()
    if not registry.enabled:
        return
    for field, value in fields.items():
        registry.gauge(f"{prefix}_{field}", **labels).set(value)


class MetricsLog:
    """Append-only structured metric records.

    ≙ the role of the reference's in-band log lines, as data instead of
    strings. Registry shim: each logged event also bumps
    ``metrics_log_events_total{event=...}`` so legacy event streams are
    countable next to the first-class instrumentation."""

    def __init__(self, log_to: logging.Logger | None = logger,
                 level: int = logging.DEBUG):
        from large_scale_recommendation_tpu.obs.registry import get_registry

        self.records: list[dict] = []
        self._logger = log_to
        self._level = level
        self._registry = get_registry()

    def log(self, event: str, **fields) -> None:
        rec = {"event": event, "t": time.time(), **fields}
        self.records.append(rec)
        self._registry.counter("metrics_log_events_total",
                               event=event).inc()
        if self._logger is not None:
            self._logger.log(self._level, "%s %s", event, fields)

    def of(self, event: str) -> list[dict]:
        return [r for r in self.records if r["event"] == event]


# --------------------------------------------------------------------------
# Ranking quality (implicit-feedback evaluation)
# --------------------------------------------------------------------------

_RANK_KERNEL = None


def _rank_kernel():
    """Jitted chunk evaluator, built lazily (this module avoids a
    top-level jax import) and cached so repeated chunks reuse one
    compile per (chunk, exclusion-bucket, k) shape family."""
    global _RANK_KERNEL
    if _RANK_KERNEL is None:
        from functools import partial

        import jax
        import jax.numpy as jnp

        @partial(jax.jit, static_argnames=("k",))
        def kern(U_rows, V, pos_items, excl_rows, excl_cols, excl_w,
                 item_w, *, k):
            # [C, n_items] scores in ONE matmul — the rank of the positive
            # is a compare-and-count against its row, so no top-k sort
            # ever materializes (O(C·I) compares ride the VPU; the scores
            # ride the MXU)
            scores = U_rows @ V.T + item_w[None, :]
            # train-seen exclusion: scatter-MIN a large negative onto
            # seen slots — idempotent under duplicate (user, item) train
            # pairs (an additive scatter would stack, ranking a
            # twice-excluded target below once-excluded items — caught by
            # the fuzz oracle); padded entries carry +inf and are no-ops
            scores = scores.at[excl_rows, excl_cols].min(excl_w)
            st = jnp.take_along_axis(scores, pos_items[:, None], axis=1)
            rank = jnp.sum((scores > st).astype(jnp.int32), axis=1)
            hit = rank < k
            nd = jnp.where(
                hit, 1.0 / jnp.log2(rank.astype(jnp.float32) + 2.0), 0.0)
            return hit.astype(jnp.float32), nd

        _RANK_KERNEL = kern
    return _RANK_KERNEL


def _exclusion_builder(train_u, train_i, num_users: int):
    """Per-chunk train-seen exclusion lists, pow2-bucketed.

    Returns ``build(cu, c) -> (excl_rows, excl_cols, excl_w)`` mapping a
    (padded) chunk of user rows to the scatter-min exclusion triple the
    ranked-score kernels consume; shared by ``ranking_metrics`` (rank of
    a held-out positive) and ``top_k_recommend`` (serving) so the
    exclusion semantics cannot drift between evaluation and serving."""
    import numpy as np

    from large_scale_recommendation_tpu.utils.shapes import pow2_pad

    if train_u is None:
        # same pow2-bucketed shape as the with-train e=0 case, so the
        # jitted kernels compile ONE empty-exclusion variant either way
        ep = pow2_pad(1)

        def build_empty(cu, c):
            z = np.zeros(ep, np.int32)
            return z, z, np.full(ep, np.inf, np.float32)

        return build_empty

    train_u = np.asarray(train_u)
    order = np.argsort(train_u, kind="stable")
    tu = train_u[order]
    ti = np.asarray(train_i, dtype=np.int32)[order]
    starts = np.searchsorted(tu, np.arange(num_users + 1))

    def build(cu, c):
        counts = (starts[cu + 1] - starts[cu])[:c]
        e = int(counts.sum())
        rows = np.repeat(np.arange(c, dtype=np.int32), counts)
        # absolute positions of each user's train slice, vectorized
        offs = np.repeat(
            starts[cu[:c]].astype(np.int64)
            - np.concatenate([[0], np.cumsum(counts)[:-1]]), counts)
        cols = ti[(np.arange(e) + offs)] if e else np.zeros(0, np.int32)
        ep = pow2_pad(max(e, 1))
        excl_rows = np.zeros(ep, np.int32)
        excl_cols = np.zeros(ep, np.int32)
        excl_w = np.full(ep, np.inf, np.float32)  # pads: min() no-ops
        excl_rows[:e], excl_cols[:e], excl_w[:e] = (
            rows, cols, DEAD_SLOT_OFFSET)
        return excl_rows, excl_cols, excl_w

    return build


def ranking_metrics(U, V, eval_u, eval_i, k: int = 10,
                    train_u=None, train_i=None, chunk: int = 2048,
                    item_mask=None) -> dict:
    """HR@K and NDCG@K by FULL-catalog ranking of held-out positives.

    Protocol (Hu/Koren/Volinsky-style implicit evaluation, the quality
    twin of the reference's RMSE-only ``empiricalRisk``
    — MatrixFactorization.scala:133-192): each ``(eval_u, eval_i)`` pair
    is one positive; the user's scores against every item are ranked,
    items the user interacted with in TRAINING (``train_u``/``train_i``)
    are excluded, and the positive's rank r scores HR = 1[r < K],
    NDCG = 1/log2(r+2). Returns ``{"hr", "ndcg", "n"}`` (means over
    pairs). No sampled-negative shortcut: sampled HR@K is known to be
    rank-inconsistent, and the full catalog is one [chunk, n_items]
    matmul per chunk here, so honesty is affordable.

    ``U``/``V`` are factor tables (device or host); eval/train ids are
    ROW indices into them. Chunks are fixed-size (last one padded) and
    exclusion lists pow2-bucketed, so the jitted evaluator compiles a
    bounded shape family regardless of eval-set size.

    ``item_mask`` ([n_item_rows] bool, True = real item) excludes
    non-catalog rows from the ranked list — block-padded factor tables
    carry random-init rows that would otherwise act as phantom items and
    deflate HR/NDCG by the pad ratio.
    """
    import numpy as np

    from large_scale_recommendation_tpu.utils.shapes import pow2_pad

    eval_u = np.asarray(eval_u)
    eval_i = np.asarray(eval_i, dtype=np.int32)
    n = len(eval_u)
    if n == 0:
        return {"hr": float("nan"), "ndcg": float("nan"), "n": 0}
    num_users = int(U.shape[0])

    build_excl = _exclusion_builder(train_u, train_i, num_users)
    kern = _rank_kernel()
    item_w = np.zeros(int(V.shape[0]), np.float32)
    if item_mask is not None:
        item_w[~np.asarray(item_mask)] = DEAD_SLOT_OFFSET
    chunk = min(chunk, pow2_pad(n))
    hits = ndcg = 0.0
    for c0 in range(0, n, chunk):
        cu = eval_u[c0:c0 + chunk]
        ci = eval_i[c0:c0 + chunk]
        c = len(cu)
        if c < chunk:  # pad the tail chunk to the fixed shape
            cu = np.concatenate([cu, np.zeros(chunk - c, cu.dtype)])
            ci = np.concatenate([ci, np.zeros(chunk - c, ci.dtype)])
        excl_rows, excl_cols, excl_w = build_excl(cu, c)
        hit, nd = kern(U[np.asarray(cu)], V, ci, excl_rows, excl_cols,
                       excl_w, item_w, k=k)
        hits += float(np.asarray(hit[:c]).sum())
        ndcg += float(np.asarray(nd[:c]).sum())
    return {"hr": hits / n, "ndcg": ndcg / n, "n": n}


_PERCENTILE_KERNEL = None


def _percentile_kernel():
    """Jitted per-chunk percentile ranks (lazy, as ``_rank_kernel``)."""
    global _PERCENTILE_KERNEL
    if _PERCENTILE_KERNEL is None:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def kern(U, V, eu, ei, item_ok):
            # float32 products on a TPU too (its default multiplies float32
            # in bfloat16, and near-ties of a converged model then flip)
            scores = jnp.matmul(U[eu], V.T,
                                precision=jax.lax.Precision.HIGHEST)
            own = jnp.take_along_axis(scores, ei[:, None], axis=1)
            above = jnp.sum((scores > own) & item_ok[None, :], axis=1)
            ties = jnp.sum((scores == own) & item_ok[None, :], axis=1) - 1
            return ((above + 0.5 * ties.astype(jnp.float32))
                    / jnp.maximum(jnp.sum(item_ok) - 1, 1))

        _PERCENTILE_KERNEL = kern
    return _PERCENTILE_KERNEL


# the most one score matrix of expected_percentile_rank may hold
SCORE_BUDGET_BYTES = 2 << 30


def score_chunk(n_items: int, chunk: int = 2048) -> int:
    """Rows of a ``[rows, n_items]`` float32 score matrix: ``chunk``, or
    the largest power of two (8 at least) whose matrix stays under
    ``SCORE_BUDGET_BYTES``: 2048 at 41,140 items (337 MB), 128 at
    2,262,292 (1.16 GB), where 2048 rows would be 18.5 GB. A chunk reads
    all of ``V`` and passes it through the matrix unit once, whatever its
    rows up to the unit's 128: fewer rows than that cost as much a
    chunk."""
    fit = SCORE_BUDGET_BYTES // (4 * max(int(n_items), 1))
    return int(min(chunk, max(8, 1 << max(int(fit).bit_length() - 1, 0))))


def expected_percentile_rank(U, V, eval_u, eval_i, weights=None,
                             item_mask=None, chunk: int = 2048) -> float:
    """Expected percentile rank of held-out interactions (Hu, Koren and
    Volinsky, ICDM 2008, eq. 8): over the pairs ``(eval_u, eval_i)``,
    weighted by ``weights`` (the held-out counts ``r_ui``; None = 1), the
    share of the catalog that the user scores above the held-out item.
    0 is a perfect ranking, 0.5 what a random model gives; lower is
    better. A tie counts half, so a user whose scores are all equal (a
    zero row) reads 0.5 and not 0. Nothing is excluded from the ranked
    list but the rows ``item_mask`` marks False (padding rows); the
    held-out item must be a row it marks True.

    Row-space ids into the tables, as ``ranking_metrics``; one
    ``[chunk, n_items]`` score matrix at a time, on the device, so the
    whole catalog is ranked for every pair. ``chunk`` is a most: a tall
    catalog takes fewer rows (``score_chunk``). The chunks are queued
    and their ranks read once at the end."""
    import jax.numpy as jnp
    import numpy as np

    eval_u = np.asarray(eval_u, np.int32)
    eval_i = np.asarray(eval_i, np.int32)
    n = len(eval_u)
    w = (np.ones(n, np.float64) if weights is None
         else np.asarray(weights, np.float64))
    if n == 0 or w.sum() <= 0:
        return float("nan")
    item_ok = jnp.asarray(np.ones(int(V.shape[0]), bool) if item_mask is None
                          else np.asarray(item_mask, bool))
    U, V = jnp.asarray(U, jnp.float32), jnp.asarray(V, jnp.float32)
    kern = _percentile_kernel()
    chunk = min(score_chunk(V.shape[0], chunk), n)
    pad = -n % chunk  # the tail chunk keeps the one compiled shape
    eval_u, eval_i = np.pad(eval_u, (0, pad)), np.pad(eval_i, (0, pad))
    ranks = [kern(U, V, jnp.asarray(eval_u[c0:c0 + chunk]),
                  jnp.asarray(eval_i[c0:c0 + chunk]), item_ok)
             for c0 in range(0, n, chunk)]
    ranks = np.concatenate([np.asarray(x, np.float64) for x in ranks])
    return float(ranks[:n] @ w) / float(w.sum())


_TOPK_KERNEL = None


def _topk_kernel():
    global _TOPK_KERNEL
    if _TOPK_KERNEL is None:
        from functools import partial

        import jax

        @partial(jax.jit, static_argnames=("k",))
        def kern(U_rows, V, excl_rows, excl_cols, excl_w, item_w, *, k):
            # same score surface as _rank_kernel: one [C, n_items] MXU
            # matmul + scatter-min exclusions + phantom-row mask — then
            # lax.top_k instead of compare-and-count
            scores = U_rows @ V.T + item_w[None, :]
            scores = scores.at[excl_rows, excl_cols].min(excl_w)
            return jax.lax.top_k(scores, k)

        _TOPK_KERNEL = kern
    return _TOPK_KERNEL


def top_k_recommend(U, V, user_rows, k: int = 10,
                    train_u=None, train_i=None, chunk: int = 2048,
                    item_mask=None):
    """Top-K item rows per user by full-catalog score — the SERVING twin
    of ``ranking_metrics`` (≙ MLlib ``MatrixFactorizationModel
    .recommendProducts``, the consumer surface of the model the
    reference's ALS branch returns). Same protocol: one
    ``[chunk, n_items]`` MXU matmul per chunk, train-seen pairs
    scatter-min-excluded, ``item_mask`` drops phantom padding rows.

    Inputs are ROW indices into ``U``/``V``; returns
    ``(top_rows int32 [n, k], top_scores float32 [n, k])`` sorted by
    descending score. Excluded/masked slots that still surface (k larger
    than the effective catalog) carry scores below ``DEAD_SLOT_THRESHOLD``
    — callers drop them by score.
    """
    import numpy as np

    from large_scale_recommendation_tpu.utils.shapes import pow2_pad

    user_rows = np.asarray(user_rows)
    n = len(user_rows)
    if n == 0:
        return (np.zeros((0, k), np.int32), np.zeros((0, k), np.float32))
    build_excl = _exclusion_builder(train_u, train_i, int(U.shape[0]))
    kern = _topk_kernel()
    item_w = np.zeros(int(V.shape[0]), np.float32)
    if item_mask is not None:
        item_w[~np.asarray(item_mask)] = DEAD_SLOT_OFFSET
    chunk = min(chunk, pow2_pad(n))
    # top_k demands k ≤ n_items; serve the clamped prefix and pad the
    # remainder as below-catalog slots (score -inf → callers drop them)
    kk = min(k, int(V.shape[0]))
    out_rows = np.zeros((n, k), np.int32)
    out_scores = np.full((n, k), -np.inf, np.float32)
    for c0 in range(0, n, chunk):
        cu = user_rows[c0:c0 + chunk]
        c = len(cu)
        if c < chunk:
            cu = np.concatenate([cu, np.zeros(chunk - c, cu.dtype)])
        excl_rows, excl_cols, excl_w = build_excl(cu, c)
        sc, rows = kern(U[np.asarray(cu)], V, excl_rows, excl_cols,
                        excl_w, item_w, k=kk)
        out_rows[c0:c0 + c, :kk] = np.asarray(rows[:c])
        out_scores[c0:c0 + c, :kk] = np.asarray(sc[:c])
    return out_rows, out_scores


@contextlib.contextmanager
def profile(log_dir: str | None) -> Iterator[None]:
    """DEPRECATED shim: trace the XLA timeline to ``log_dir``
    (TensorBoard format). No-op when ``log_dir`` is None so call sites
    can leave the hook wired unconditionally.

    This no longer calls ``jax.profiler.trace`` on its own — it routes
    through ``obs.introspect.profile_trace``, the ONE capture layer
    (shared process-singleton lock + capture accounting with
    ``/profilez`` and the watchdog postmortem auto-capture), so two
    capture paths can never race the profiler singleton. New code
    should call ``obs.introspect.profile_trace`` /
    ``obs.capture_profile`` directly; this surface stays only for
    existing callers and warns."""
    if log_dir is None:
        yield
        return
    import warnings

    warnings.warn(
        "utils.metrics.profile is deprecated: use "
        "obs.introspect.profile_trace (or GET /profilez on a running "
        "ObsServer) — this shim routes there and will be removed",
        DeprecationWarning, stacklevel=3)
    from large_scale_recommendation_tpu.obs.introspect import profile_trace

    with profile_trace(log_dir):
        yield
