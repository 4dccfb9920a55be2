"""Continuous model-quality evaluation: the plane PRs 3-9 never built.

The systems half of ``obs/`` can say *how fast* every tier runs and
*whether* the process is alive — but nothing in the stack can say
whether the model is getting better or silently rotting. ROADMAP item 4
is the cost of that blindness: ``als_implicit_ndcg=0.003`` shipped
across five bench rounds before anyone noticed the implicit path ranks
randomly. This module is the model-quality plane:

- ``sampled_ranking_metrics`` — THE ranking-metric kernel
  (the evaluator's; one copy): each held-out positive is
  ranked against ``num_negatives`` sampled negatives with train-seen
  items masked out of the negative pool — the protocol whose floor
  (random model → HR ≈ k/(n+1)) and ceiling (planted structure → ≈ 1)
  are test-pinned, so the eval itself is trustworthy.
- ``catalog_coverage`` — fraction of the real catalog surfaced in the
  top-k lists of a user sample (``top_k_recommend`` under the hood): a
  model that ranks "well" by recommending the same 50 items to everyone
  is a quality failure HR/NDCG can't see.
- ``OnlineEvaluator`` — a reservoir-sampled holdout drawn from the
  ingest stream and NEVER trained on: ``split_batch`` zeroes the
  holdout rows' weights (the existing padding contract — every kernel
  already skips weight-0 rows) *before* ``partial_fit`` sees the batch,
  so the eval set is honestly out-of-sample by construction. On a
  cadence (``ensure_periodic``, the recorder-sampler machinery) the
  reservoir is shadow-scored against the live model and
  ``eval_rmse`` / ``eval_ndcg_at_k`` / ``eval_hr_at_k`` /
  ``eval_coverage`` publish as registry gauges — which the flight
  recorder samples into series that the existing
  ``AnomalyCheck``/``watch_series`` machinery watches: a quality
  collapse flips ``/healthz`` exactly like a throughput collapse does
  today, with zero static per-model thresholds
  (``HealthMonitor.watch_quality`` wires the pair of checks).
- The ``DSGD``/``ALS`` **segment-boundary hook** (``on_segment``): the
  offline trainers call an attached evaluator with their row-space
  tables at each segment boundary (next to the watchdog scan), so a
  batch retrain's quality trajectory lands in the same gauges/series as
  the online path's.

- ``PercentileRankEvaluator`` — the same hook for implicit feedback:
  expected percentile rank (Hu et al., eq. 8) of a held-out set of
  interactions over the whole catalog, at each segment's end.

Zero-cost when unused — the package discipline: everything here is
opt-in (``StreamingDriver(evaluator=...)``, ``solver.evaluator = ...``)
and every hook in the hot paths is one ``is not None`` test.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from large_scale_recommendation_tpu.obs.registry import get_registry

_SAMPLED_KERNEL = None


def _sampled_kernel():
    """Jitted rank-against-sampled-negatives evaluator, cached like
    ``utils.metrics._rank_kernel`` (one compile per (chunk, negatives,
    k) shape family)."""
    global _SAMPLED_KERNEL
    if _SAMPLED_KERNEL is None:
        from functools import partial

        import jax
        import jax.numpy as jnp

        @partial(jax.jit, static_argnames=("k",))
        def kern(U_rows, V_pos, V_neg, valid, *, k):
            # [c, r] x [c, n, r]: the positive's rank among the VALID
            # sampled negatives — invalid slots (train-seen items, the
            # positive itself resampled) are masked out of the compare,
            # never out of the shape (static shapes, bounded compiles)
            pos = jnp.sum(U_rows * V_pos, axis=1)
            neg = jnp.einsum("cr,cnr->cn", U_rows, V_neg)
            rank = jnp.sum(((neg > pos[:, None]) & valid)
                           .astype(jnp.int32), axis=1)
            hit = rank < k
            nd = jnp.where(
                hit, 1.0 / jnp.log2(rank.astype(jnp.float32) + 2.0), 0.0)
            return hit.astype(jnp.float32), nd

        _SAMPLED_KERNEL = kern
    return _SAMPLED_KERNEL


def sampled_ranking_metrics(U, V, eval_u, eval_i, k: int = 10,
                            num_negatives: int = 100,
                            train_u=None, train_i=None, item_mask=None,
                            seed: int = 0, chunk: int = 1024) -> dict:
    """HR@K / NDCG@K of held-out positives against sampled negatives.

    Protocol (the NCF-style sampled evaluation, made honest): each
    ``(eval_u, eval_i)`` pair is one positive; ``num_negatives`` item
    rows are sampled uniformly from the REAL catalog (``item_mask``
    True rows — phantom padding rows never enter the pool), negatives
    that collide with the positive or with a train-seen item of that
    user (``train_u``/``train_i``) are masked OUT of the comparison
    (sorted-key membership, the ``serving.retrieval`` idiom), and the
    positive's rank r among the surviving negatives scores
    HR = 1[r < K], NDCG = 1/log2(r+2).

    Why this exists next to the full-catalog ``ranking_metrics``: the
    full ranking is the gold protocol but its numbers sit at the
    random floor (k/n_items ≈ 0.0002 on a 59K catalog) for any model
    that is merely *weak* — indistinguishable from a broken eval. The
    sampled protocol has a KNOWN floor (a random model ranks uniformly
    among n+1 candidates, so HR ≈ k/(n+1)) and a known ceiling, both
    pinned on planted structure in ``tests/test_obs_quality.py``, so a
    near-floor score is evidence about the MODEL, not the metric.

    ``U``/``V`` are factor tables (device or host); eval/train ids are
    ROW indices into them. Returns ``{"hr", "ndcg", "n",
    "num_negatives", "valid_negatives"}`` (means over pairs;
    ``valid_negatives`` is the mean surviving pool size — a collapse of
    it means the negative pool is mostly train-seen and the metric is
    losing resolution).
    """
    import jax.numpy as jnp

    eval_u = np.asarray(eval_u)
    eval_i = np.asarray(eval_i, dtype=np.int64)
    n = len(eval_u)
    if n == 0:
        return {"hr": float("nan"), "ndcg": float("nan"), "n": 0,
                "num_negatives": int(num_negatives),
                "valid_negatives": float("nan")}
    n_rows = int(V.shape[0])
    if item_mask is not None:
        pool = np.nonzero(np.asarray(item_mask))[0].astype(np.int64)
    else:
        pool = np.arange(n_rows, dtype=np.int64)
    if len(pool) == 0:
        return {"hr": float("nan"), "ndcg": float("nan"), "n": 0,
                "num_negatives": int(num_negatives),
                "valid_negatives": float("nan")}

    # train-seen membership via one sorted (user, item) key array — the
    # same sorted-key trick serving.retrieval uses for exclusions
    train_keys = None
    if train_u is not None and len(np.asarray(train_u)):
        tu = np.asarray(train_u, dtype=np.int64)
        ti = np.asarray(train_i, dtype=np.int64)
        train_keys = np.sort(tu * n_rows + ti)

    from large_scale_recommendation_tpu.utils.shapes import pow2_pad

    rng = np.random.default_rng(seed)
    kern = _sampled_kernel()
    U = jnp.asarray(U)  # hoisted with V: a host-numpy table must not
    V = jnp.asarray(V)  # re-upload per chunk just to gather rows
    hits = ndcg = valid_total = 0.0
    # pow2-clamped chunk (the ranking_metrics idiom): eval-set sizes
    # vary per call, and an exact-n chunk would compile one kernel
    # variant per distinct size instead of a bounded shape family
    chunk = min(chunk, pow2_pad(max(1, n)))
    for c0 in range(0, n, chunk):
        cu = eval_u[c0:c0 + chunk]
        ci = eval_i[c0:c0 + chunk]
        c = len(cu)
        if c < chunk:  # pad the tail chunk to the fixed shape
            cu = np.concatenate([cu, np.zeros(chunk - c, cu.dtype)])
            ci = np.concatenate([ci, np.zeros(chunk - c, ci.dtype)])
        neg = pool[rng.integers(0, len(pool), (chunk, num_negatives))]
        valid = neg != ci[:, None]
        if train_keys is not None:
            keys = (cu[:, None].astype(np.int64) * n_rows + neg).ravel()
            pos = np.searchsorted(train_keys, keys)
            pos_c = np.minimum(pos, len(train_keys) - 1)
            seen = (train_keys[pos_c] == keys).reshape(chunk, num_negatives)
            valid &= ~seen
        valid_total += float(valid[:c].sum())
        hit, nd = kern(U[jnp.asarray(cu)],
                       V[jnp.asarray(ci)], V[jnp.asarray(neg)],
                       jnp.asarray(valid), k=k)
        hits += float(np.asarray(hit[:c]).sum())
        ndcg += float(np.asarray(nd[:c]).sum())
    return {"hr": hits / n, "ndcg": ndcg / n, "n": n,
            "num_negatives": int(num_negatives),
            "valid_negatives": valid_total / n}


def catalog_coverage(U, V, user_rows, k: int = 10, train_u=None,
                     train_i=None, item_mask=None,
                     chunk: int = 2048) -> float:
    """Fraction of the real catalog surfaced across the top-k lists of
    ``user_rows`` — the aggregate-diversity signal HR/NDCG are blind to
    (a model serving the same head items to everyone can rank "well"
    while the catalog tail never ships). Uses the serving top-k kernel
    (``utils.metrics.top_k_recommend``), so coverage measures what
    users would actually be shown."""
    from large_scale_recommendation_tpu.utils.metrics import (
        DEAD_SLOT_THRESHOLD,
        top_k_recommend,
    )

    user_rows = np.asarray(user_rows)
    if item_mask is not None:
        n_items = int(np.asarray(item_mask).sum())
    else:
        n_items = int(V.shape[0])
    if len(user_rows) == 0 or n_items == 0:
        return float("nan")
    rows, scores = top_k_recommend(U, V, user_rows, k=k, train_u=train_u,
                                   train_i=train_i, chunk=chunk,
                                   item_mask=item_mask)
    real = scores > DEAD_SLOT_THRESHOLD  # dead/below-catalog slots out
    return float(len(np.unique(rows[real])) / n_items)


class OnlineEvaluator:
    """Reservoir-holdout continuous evaluation of a live model.

    ``model`` is an ``OnlineMF`` (the streaming driver passes its
    online model; an ``AdaptiveMF`` caller passes ``.online``) — or
    None for pure offline use (the segment hook). ``split_batch``
    routes a ``holdout_fraction`` of each arriving micro-batch into a
    bounded reservoir (classic reservoir sampling: memory is capped at
    ``reservoir_size`` rows FOREVER, and the sample stays uniform over
    everything ever held out) and zeroes those rows' weights in the
    returned batch — weight-0 is the package-wide padding contract, so
    every training kernel already skips them: the holdout is excluded
    before ``partial_fit`` sees the batch, not merely ignored after.

    ``evaluate()`` shadow-scores the reservoir against the live model
    and publishes ``eval_rmse``, ``eval_ndcg_at_k``, ``eval_hr_at_k``,
    ``eval_coverage`` (+ ``eval_holdout_rows``, ``eval_runs_total``)
    labeled ``source=<source>``. ``start(interval_s)`` runs it on the
    shared ``PeriodicTask`` cadence (``ensure_periodic`` — one copy of
    the machinery with the recorder sampler and the driver telemetry
    exporter).

    Offline form: ``set_offline_holdout(u_rows, i_rows, values)`` arms
    a ROW-SPACE holdout; ``on_segment(U, V)`` — the hook
    ``DSGD``/``ALS`` call at segment boundaries when an evaluator is
    attached (``solver.evaluator = ev``) — scores it against the
    segment's tables, publishing into the same gauges (labeled by the
    segment ``label``), so a batch retrain's quality trajectory lands
    in the same flight-recorder series the anomaly checks watch.

    Thread-safety: the reservoir lock covers split vs the cadence
    thread's evaluate; evaluation itself runs outside the lock on a
    snapshot (a slow eval must never stall ingest). The model read
    rides the package's documented ``.array`` snapshot-consistency
    point (a copy taken between ``partial_fit`` calls) — a
    cadence evaluation concurrent with a capacity-growth rehash may
    drop a pair as unseen for one tick, never corrupt anything.
    """

    def __init__(self, model=None, holdout_fraction: float = 0.1,
                 reservoir_size: int = 4096, k: int = 10,
                 num_negatives: int = 100, eval_sample: int = 1024,
                 min_eval_rows: int = 32, seed: int = 0,
                 source: str = "online", registry=None):
        if not 0.0 < holdout_fraction < 1.0:
            raise ValueError(f"holdout_fraction must be in (0, 1), "
                             f"got {holdout_fraction}")
        if reservoir_size < 1:
            raise ValueError(f"reservoir_size must be >= 1, "
                             f"got {reservoir_size}")
        self.model = model
        self.holdout_fraction = float(holdout_fraction)
        self.reservoir_size = int(reservoir_size)
        self.k = int(k)
        self.num_negatives = int(num_negatives)
        self.eval_sample = int(eval_sample)
        self.min_eval_rows = int(min_eval_rows)
        self.source = source
        # TWO generators, one per thread role: numpy Generators are not
        # thread-safe, and the documented wiring has the ingest thread
        # in split_batch while the cadence thread runs evaluate —
        # sharing one BitGenerator would silently corrupt the very
        # sampling this module exists to make trustworthy. Evaluation
        # draws additionally serialize under the reservoir lock (a
        # manual evaluate() may race the cadence thread's).
        self._split_rng = np.random.default_rng(seed)
        self._eval_rng = np.random.default_rng(seed + 1)
        self._res_u = np.zeros(self.reservoir_size, np.int64)
        self._res_i = np.zeros(self.reservoir_size, np.int64)
        self._res_v = np.zeros(self.reservoir_size, np.float32)
        self._res_n = 0          # filled rows
        self._held_out = 0       # lifetime rows routed to the holdout
        self._seen = 0           # lifetime rows offered to split_batch
        self._lock = threading.Lock()
        self._task = None
        self.evaluations = 0
        self.last_metrics: dict = {}
        # offline (row-space) holdout for the segment hook
        self._off_rows = None
        self._obs = registry or get_registry()

    # -- holdout intake ------------------------------------------------------

    @property
    def holdout_rows(self) -> int:
        with self._lock:
            return self._res_n

    @property
    def held_out_total(self) -> int:
        with self._lock:
            return self._held_out

    def split_batch(self, ratings):
        """Return ``ratings`` with the holdout rows' weights zeroed (a
        same-shape ``Ratings`` — offset stamps, padding layout and batch
        geometry all unchanged), after absorbing those rows into the
        reservoir. Rows already weight-0 (padding, quarantined) are
        never selected. The caller trains on the RETURNED batch."""
        from large_scale_recommendation_tpu.core.types import Ratings

        ru, ri, rv, rw = ratings.to_numpy()
        real = rw > 0
        with self._lock:
            pick = real & (self._split_rng.random(len(rw))
                           < self.holdout_fraction)
            n_pick = int(pick.sum())
            self._seen += int(real.sum())
            if n_pick:
                self._absorb_locked(ru[pick], ri[pick], rv[pick])
        if not n_pick:
            return ratings
        rw = rw.copy()
        rw[pick] = 0.0
        return Ratings.from_arrays(ru, ri, rv, rw)

    def _absorb_locked(self, u, i, v) -> None:
        """Reservoir sampling (Algorithm R, vectorized per batch): while
        filling, rows append; after, each new row replaces a uniformly
        random slot with probability size/held_out — uniform over the
        whole held-out stream, memory capped forever."""
        n = len(u)
        for j in range(n):  # micro-batches hold out tens of rows — the
            self._held_out += 1  # scalar loop is noise next to the update
            if self._res_n < self.reservoir_size:
                slot = self._res_n
                self._res_n += 1
            else:
                slot = int(self._split_rng.integers(0, self._held_out))
                if slot >= self.reservoir_size:
                    continue
            self._res_u[slot] = u[j]
            self._res_i[slot] = i[j]
            self._res_v[slot] = v[j]

    # -- online evaluation ---------------------------------------------------

    def evaluate(self) -> dict | None:
        """Shadow-score the reservoir against the live model and publish
        the ``eval_*`` gauges. Returns the metrics dict, or None when
        the reservoir is still below ``min_eval_rows`` (a baseline
        learned from a handful of pairs is noise — the same warming
        discipline as ``AnomalyCheck``)."""
        model = self.model
        if model is None:
            return None
        with self._lock:
            n = self._res_n
            if n < self.min_eval_rows:
                return None
            u = self._res_u[:n].copy()
            i = self._res_i[:n].copy()
            v = self._res_v[:n].copy()
        from large_scale_recommendation_tpu.core.types import Ratings

        rmse = model.rmse(Ratings.from_arrays(u, i, v))
        # ranking in row space against the live tables: pairs whose user
        # or item the model has never seen drop (the package-wide
        # inner-join contract); phantom capacity rows mask out of the
        # negative pool and the coverage denominator
        u_rows, u_mask = model.users.rows_for(u)
        i_rows, i_mask = model.items.rows_for(i)
        keep = (u_mask * i_mask) > 0
        u_rows, i_rows = u_rows[keep], i_rows[keep]
        metrics = {"rmse": float(rmse), "n": int(n),
                   "ranked": int(keep.sum()), "time": time.time()}
        if len(u_rows):
            if len(u_rows) > self.eval_sample:
                with self._lock:
                    sel = self._eval_rng.choice(
                        len(u_rows), self.eval_sample, replace=False)
                u_rows, i_rows = u_rows[sel], i_rows[sel]
            # copies (``array_copy``): the evaluation runs on them while
            # ingest goes on in place
            U, V = model.users.array, model.items.array
            item_mask = np.asarray(model.items.id_array()) >= 0
            if len(item_mask) < int(V.shape[0]):  # capacity > ids filled
                item_mask = np.concatenate([
                    item_mask,
                    np.zeros(int(V.shape[0]) - len(item_mask), bool)])
            with self._lock:
                rank_seed = int(self._eval_rng.integers(1 << 31))
            rq = sampled_ranking_metrics(
                U, V, u_rows, i_rows, k=self.k,
                num_negatives=self.num_negatives, item_mask=item_mask,
                seed=rank_seed)
            cov_users = np.unique(u_rows)
            if len(cov_users) > 256:
                with self._lock:
                    cov_users = self._eval_rng.choice(cov_users, 256,
                                                      replace=False)
            cov = catalog_coverage(U, V, cov_users,
                                   k=self.k, item_mask=item_mask)
            metrics.update(ndcg=rq["ndcg"], hr=rq["hr"], coverage=cov,
                           valid_negatives=rq["valid_negatives"])
        self._publish(metrics, self.source)
        self.evaluations += 1
        self.last_metrics = metrics
        return metrics

    def _publish(self, metrics: dict, source: str) -> None:
        """EVERY instrument resolves per publish source — the segment
        hook publishes under its segment label, and one evaluator may
        serve both a streaming driver and a batch solver; pre-bound
        instruments would stomp the online reservoir gauge with the
        offline holdout size (registry lookups are cached dict gets)."""
        obs = self._obs
        import math

        if math.isfinite(metrics.get("rmse", float("nan"))):
            obs.gauge("eval_rmse", source=source).set(metrics["rmse"])
        for key, gauge in (("ndcg", "eval_ndcg_at_k"),
                           ("hr", "eval_hr_at_k"),
                           ("coverage", "eval_coverage")):
            val = metrics.get(key)
            if val is not None and math.isfinite(val):
                obs.gauge(gauge, source=source, k=self.k).set(val)
        obs.gauge("eval_holdout_rows", source=source).set(
            metrics.get("n", 0))
        obs.counter("eval_runs_total", source=source).inc()

    # -- cadence (shared PeriodicTask machinery) -----------------------------

    def start(self, interval_s: float = 5.0) -> "OnlineEvaluator":
        """Run ``evaluate()`` every ``interval_s`` on a daemon thread —
        ``ensure_periodic``, the one copy of the cadence machinery the
        recorder sampler and driver telemetry already ride."""
        from large_scale_recommendation_tpu.obs.health import ensure_periodic

        self._task = ensure_periodic(self._task, self.evaluate, interval_s,
                                     name=f"online-eval:{self.source}")
        return self

    def stop(self) -> None:
        task, self._task = self._task, None
        if task is not None:
            task.stop()

    @property
    def running(self) -> bool:
        return self._task is not None and self._task.running

    # -- offline (segment-boundary) form -------------------------------------

    def set_offline_holdout(self, u_rows, i_rows, values,
                            item_mask=None) -> None:
        """Arm a ROW-SPACE holdout for the segment hook: ``u_rows`` /
        ``i_rows`` index the solver's factor tables directly (offline
        blocking is deterministic given ratings+seed, so a caller can
        map a held-out split to rows before or after ``fit``)."""
        self._off_rows = (np.asarray(u_rows), np.asarray(i_rows),
                          np.asarray(values, np.float32),
                          None if item_mask is None
                          else np.asarray(item_mask))

    def on_segment(self, U, V, label: str = "segment",
                   step: int | None = None) -> dict | None:
        """The ``DSGD``/``ALS`` segment-boundary hook: score the armed
        offline holdout against the segment's row-space tables and
        publish into the same ``eval_*`` gauges (labeled
        ``source=label``). A no-op without ``set_offline_holdout`` —
        attaching an online evaluator to a batch solver costs one
        pointer test per segment."""
        if self._off_rows is None:
            return None
        import jax.numpy as jnp

        from large_scale_recommendation_tpu.ops import sgd as sgd_ops

        u_rows, i_rows, vals, item_mask = self._off_rows
        n = len(u_rows)
        if n == 0:
            return None
        Uf = jnp.asarray(U).astype(jnp.float32)
        Vf = jnp.asarray(V).astype(jnp.float32)
        sse = sgd_ops.sse_rows(Uf, Vf, jnp.asarray(u_rows),
                               jnp.asarray(i_rows), jnp.asarray(vals),
                               jnp.asarray(np.ones(n, np.float32)))
        rmse = float(np.sqrt(float(sse) / n))
        sel = np.arange(n)
        with self._lock:
            if n > self.eval_sample:
                sel = self._eval_rng.choice(n, self.eval_sample,
                                            replace=False)
            rank_seed = int(self._eval_rng.integers(1 << 31))
        rq = sampled_ranking_metrics(
            Uf, Vf, u_rows[sel], i_rows[sel], k=self.k,
            num_negatives=self.num_negatives, item_mask=item_mask,
            seed=rank_seed)
        metrics = {"rmse": rmse, "n": int(n), "ndcg": rq["ndcg"],
                   "hr": rq["hr"], "step": step, "time": time.time()}
        self._publish(metrics, label)
        self.evaluations += 1
        self.last_metrics = metrics
        return metrics

    def snapshot(self) -> dict:
        """JSON-safe state for bundles / reports."""
        with self._lock:
            res_n, held, seen = self._res_n, self._held_out, self._seen
        return {"source": self.source,
                "holdout_fraction": self.holdout_fraction,
                "reservoir_size": self.reservoir_size,
                "holdout_rows": res_n,
                "held_out_total": held,
                "rows_seen": seen,
                "evaluations": self.evaluations,
                "last_metrics": dict(self.last_metrics)}


class PercentileRankEvaluator:
    """Segment-boundary hook (``solver.evaluator = ...``) for an implicit
    fit: the expected percentile rank of held-out interactions
    (``utils.metrics.expected_percentile_rank``: the whole catalog ranked
    for every pair, on the device) after each segment. ``history`` keeps
    ``(step, rank)``; with a live registry the newest reading is the gauge
    ``eval_percentile_rank{source=<segment label>}``. Row-space ids, as
    ``OnlineEvaluator.set_offline_holdout``."""

    def __init__(self, u_rows, i_rows, counts=None, item_mask=None):
        self.u_rows = np.asarray(u_rows, np.int32)
        self.i_rows = np.asarray(i_rows, np.int32)
        self.counts = None if counts is None else np.asarray(counts)
        self.item_mask = item_mask
        self.history: list[tuple[int | None, float]] = []

    def on_segment(self, U, V, label: str = "segment",
                   step: int | None = None) -> float:
        from large_scale_recommendation_tpu.utils.metrics import (
            expected_percentile_rank,
        )

        rank = expected_percentile_rank(U, V, self.u_rows, self.i_rows,
                                        self.counts, self.item_mask)
        self.history.append((step, rank))
        get_registry().gauge("eval_percentile_rank", source=label).set(rank)
        return rank
