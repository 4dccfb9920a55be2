"""Adaptive MF: continuous online updates + periodic full batch retrain.

TPU-native rebuild of the reference's two "combined" paths:

- **Spark**: ``OnlineSpark.buildModelCombineOffline``
  (spark-adaptive-recom/.../OnlineSpark.scala:26-162) — every micro-batch
  trains online (1-iteration DSGD on the new ratings); all ratings accumulate
  into ``ratingsHistory`` (:68-70); every ``offlineEvery`` batches a FULL
  retrain runs from the history — DSGD from scratch (:119-124) or MLlib ALS
  (:125-131) — and the model is swapped wholesale (:134-150).
- **Flink PS**: ``PSOfflineOnlineMF.offlineOnlinePS``
  (flink-adaptive-recom/.../mf/PSOfflineOnlineMF.scala:24-401) — an external
  trigger stream flips a 3-state machine Online → BatchInit → Batch on
  workers and servers; the PS clears its parameters on batch start
  (retrain-from-scratch, :313-314); ratings arriving during Batch are queued
  (``onlinePullQueue``) and folded back into the online flow when the batch
  ends (:204-237).

Architecture here: the online flow is ``models.online.OnlineMF``
(synchronous jitted micro-batches); the batch retrain is ``models.dsgd.DSGD``
or ``models.als.ALS`` over the accumulated history. The state machine
survives in recognizable form:

    Online  — micro-batches update the live tables directly
    Batch   — a retrain runs (optionally on a background thread, the
              analogue of the reference's in-band-signaled concurrent batch);
              arriving micro-batches are buffered, exactly the
              ``onlinePullQueue`` contract
    swap    — the retrained model replaces the online tables wholesale
              (≙ model swap OnlineSpark.scala:134-150 / PS param clear
              PSOfflineOnlineMF.scala:313-314), then buffered batches replay
              through the online path (≙ folding the queue into ``rs``)

``BatchInit`` (the reference's drain-in-flight-pulls state) has no analogue:
synchronous jitted micro-batches leave nothing in flight to drain — the
consistency problem that state solves is gone by construction.
"""

from __future__ import annotations

import dataclasses
import threading
import time
import weakref
from typing import Iterable, Iterator, Literal

import numpy as np

from large_scale_recommendation_tpu.core.limiter import ThroughputLimiter
from large_scale_recommendation_tpu.core.types import Ratings
from large_scale_recommendation_tpu.models.als import ALS, ALSConfig
from large_scale_recommendation_tpu.models.dsgd import DSGD, DSGDConfig
from large_scale_recommendation_tpu.models.mf import MFModel
from large_scale_recommendation_tpu.models.online import (
    BatchUpdates,
    OnlineMF,
    OnlineMFConfig,
)
from large_scale_recommendation_tpu.obs.contention import named_rlock
from large_scale_recommendation_tpu.obs.disttrace import get_disttrace
from large_scale_recommendation_tpu.obs.events import get_events
from large_scale_recommendation_tpu.obs.lineage import get_lineage
from large_scale_recommendation_tpu.obs.registry import get_registry
from large_scale_recommendation_tpu.obs.trace import get_tracer


@dataclasses.dataclass(frozen=True)
class AdaptiveMFConfig:
    """≙ the argument list of ``buildModelCombineOffline``
    (OnlineSpark.scala:26-35: factorInit, factorUpdate, parameters,
    checkpointEvery, offlineEvery, numberOfIterations, offlineAlgorithm) plus
    the online knobs."""

    num_factors: int = 10
    learning_rate: float = 0.01
    minibatch_size: int = 256
    offline_every: int | None = 10  # retrain each N batches; None → trigger-only
    offline_algorithm: Literal["dsgd", "als"] = "dsgd"
    offline_iterations: int = 10
    lambda_: float = 0.1
    background: bool = False  # retrain on a thread (≙ concurrent batch mode)
    history_limit: int | None = None  # cap history rows (None = unbounded)
    checkpoint_every: int | None = None  # snapshot online state each N batches
    checkpoint_dir: str | None = None  # ≙ checkpointEvery lineage truncation
    # (OnlineSpark.scala:30,93-99)


class AdaptiveMF:
    """Online MF with periodic full retrain from history.

    ≙ ``new OnlineSpark().buildModelCombineOffline(...)``
    (OnlineSpark.scala:26-36) and the PS state machine
    (PSOfflineOnlineMF.scala:28-34).
    """

    def __init__(self, config: AdaptiveMFConfig | None = None):
        self.config = cfg = config or AdaptiveMFConfig()
        self.online = OnlineMF(OnlineMFConfig(
            num_factors=cfg.num_factors,
            learning_rate=cfg.learning_rate,
            minibatch_size=cfg.minibatch_size,
        ))
        self._history: list[tuple[np.ndarray, np.ndarray, np.ndarray]] = []
        self._history_rows = 0
        self._batches_since_retrain = 0
        self.retrain_count = 0
        # Batch-state machinery (background mode)
        self._state = "Online"  # "Online" | "Batch"
        self._thread: threading.Thread | None = None
        self._retrained: MFModel | None = None
        # (batch, offset-stamp) pairs queued while a background retrain
        # runs (≙ onlinePullQueue)
        self._buffer: list[tuple[Ratings, tuple[int, int] | None]] = []
        self._engines: "weakref.WeakSet" = weakref.WeakSet()
        # guards snapshot+register vs. a swap landing in between — an
        # engine built from a pre-swap snapshot but registered after the
        # swap's refresh sweep would serve stale factors until the NEXT
        # swap
        self._engines_lock = threading.Lock()
        # observability (null singletons when disabled): retrain count/
        # duration plus retrain+swap spans — the trace view of the
        # Online → Batch → swap state machine
        obs = get_registry()
        self._obs_on = obs.enabled
        self._trace = get_tracer()
        # structured event journal (obs.events): None unless installed —
        # retrain start/install/abort emissions are one `is not None`
        # test each, all on the (cold) retrain path
        self._events = get_events()
        # lineage journal (obs.lineage): None unless installed — the
        # retrain-swap provenance stamp in _install is one `is not
        # None` test on the (cold) swap path
        self._lineage = get_lineage()
        # critical-path analyzer (obs.disttrace): retrain swaps mark
        # the servable instant per partition — one `is not None` test
        # on the same cold swap path
        self._disttrace = get_disttrace()
        self._m_retrains = obs.counter("adaptive_retrains_total")
        self._m_retrain_s = obs.histogram("adaptive_retrain_s")
        self._manager = None
        if cfg.checkpoint_dir is not None:
            from large_scale_recommendation_tpu.utils.checkpoint import (
                CheckpointManager,
            )

            self._manager = CheckpointManager(cfg.checkpoint_dir)
        self._batches_since_ckpt = 0
        # parallel-ingest mode (streams/parallel.py): N per-partition
        # consumers feed process() from N threads. The adaptive layer's
        # state machine (history union, Batch-state buffer, retrain
        # trigger counter) is inherently ORDERED, so concurrency here
        # serializes the apply itself on one lock — the WAL tail, the
        # quarantine/queue work and the host batch prep still overlap
        # across consumers. OFF by default: the single-driver path
        # never acquires it.
        self._serialize_process = False
        # named_rlock: raw unless the contention plane is armed, in
        # which case the serialized-apply lock publishes as
        # lock_*{lock="adaptive.apply_lock"}
        self.apply_lock = named_rlock("adaptive.apply_lock")

    # -- state -------------------------------------------------------------

    @property
    def state(self) -> str:
        return self._state

    @property
    def watchdog(self):
        """The divergence guard (``obs.health.TrainingWatchdog``) lives
        on the online model — micro-batches run through its
        ``partial_fit`` hook — and additionally gates every retrain
        swap here (``_install`` refuses to stream non-finite retrained
        factors into a catalog swap)."""
        return self.online.watchdog

    @watchdog.setter
    def watchdog(self, wd) -> None:
        self.online.watchdog = wd

    # -- ingest ------------------------------------------------------------

    def enable_concurrent_applies(self, enabled: bool = True) -> None:
        """Arm multi-consumer ingest (``ParallelIngestRunner``): each
        ``process`` call serializes on ``apply_lock``. Unlike the pure
        ``OnlineMF`` row-disjoint concurrent path, the adaptive combo
        cannot commute applies — history order, the retrain trigger
        counter and the Batch-state buffer are one shared sequence — so
        the parallelism N consumers buy here is the ingest pipeline
        AROUND the apply (per-partition WAL tails, quarantine, batch
        prep), not the apply itself. The frozen-offset-stamp contract
        is unchanged: batches buffered during a background retrain keep
        per-partition stamps frozen, and the runner's cross-partition
        checkpoint barrier holds until every partition's stamp catches
        its applied frontier."""
        self._serialize_process = bool(enabled)

    @property
    def concurrent_applies(self) -> bool:
        return self._serialize_process

    def process(self, batch: Ratings,
                offset: tuple[int, int] | None = None) -> BatchUpdates:
        """One micro-batch through the adaptive pipeline.

        ≙ one ``transform`` body (OnlineSpark.scala:55-158): history ∪= batch,
        online update, counters; retrain + swap when due.

        ``offset=(partition, end_offset)`` is the stream-position stamp
        (``OnlineMF.partial_fit``); batches buffered during a background
        retrain keep their stamps and apply them in replay order, so the
        checkpointed offset never claims a buffered-but-unapplied batch.
        """
        if self._serialize_process:
            with self.apply_lock:
                return self._process(batch, offset)
        return self._process(batch, offset)

    def _process(self, batch: Ratings,
                 offset: tuple[int, int] | None = None) -> BatchUpdates:
        cfg = self.config
        self._append_history(batch)

        if self._state == "Batch":
            if self._thread is not None and self._thread.is_alive():
                # ≙ enqueue to onlinePullQueue (PSOfflineOnlineMF.scala:142)
                self._buffer.append((batch, offset))
                return BatchUpdates([], [], rank=cfg.num_factors)
            # retrain finished: swap + replay the queue
            updates = self._finish_batch()
            more = self.online.partial_fit(batch, offset=offset)
            return BatchUpdates(updates.user_updates + more.user_updates,
                                updates.item_updates + more.item_updates,
                                rank=cfg.num_factors)

        out = self.online.partial_fit(batch, offset=offset)
        self._batches_since_retrain += 1
        self._maybe_checkpoint()
        if (cfg.offline_every is not None
                and self._batches_since_retrain >= cfg.offline_every):
            self.trigger_batch_training()
        return out

    def _maybe_checkpoint(self) -> None:
        """≙ the lineage-truncation snapshot every ``checkpointEvery``
        micro-batches (OnlineSpark.scala:93-99,205-212)."""
        cfg = self.config
        if self._manager is None or cfg.checkpoint_every is None:
            return
        self._batches_since_ckpt += 1
        if self._batches_since_ckpt >= cfg.checkpoint_every:
            from large_scale_recommendation_tpu.utils.checkpoint import (
                save_online_state,
            )

            save_online_state(self._manager, self.online, self.online.step)
            self._batches_since_ckpt = 0

    def resume(self) -> bool:
        """Restore the latest online-state snapshot, if any. Returns whether
        a snapshot was loaded."""
        if self._manager is None or self._manager.latest_step() is None:
            return False
        from large_scale_recommendation_tpu.utils.checkpoint import (
            restore_online_state,
        )

        restore_online_state(self._manager, self.online)
        return True

    def trigger_batch_training(self) -> None:
        """Start a full retrain from history.

        ≙ an element on ``batchTrainingTrigger``
        (PSOfflineOnlineMF.scala:37,385) / the offlineEvery counter expiring
        (OnlineSpark.scala:115).
        """
        if self._state == "Batch" or self._history_rows == 0:
            return
        self._batches_since_retrain = 0
        history = self._history_ratings()
        if self._events is not None:
            self._events.emit("adaptive.retrain_start",
                              algorithm=self.config.offline_algorithm,
                              rows=int(history.n),
                              background=self.config.background)
        if self.config.background:
            self._state = "Batch"
            self._retrained = None
            # capture the ENCLOSING trace context before the thread
            # hop: the retrain span re-enters it on the retrain thread
            # and so parents back to the triggering batch's span (and
            # carries its trace id) in the exported trace — without
            # this the retrain lane's spans parent to nothing
            ctx = (self._trace.capture_context()
                   if self._trace.enabled else None)
            self._thread = threading.Thread(
                target=self._retrain_into_slot, args=(history, ctx),
                daemon=True, name="adaptive-retrain"
            )
            self._thread.start()
        else:
            model = self._retrain(history)
            self._install(model)
            self.retrain_count += 1

    def flush(self) -> BatchUpdates:
        """Block until any background retrain completes and swap it in
        (≙ batch-finished sign propagation, PSOfflineOnlineMF.scala:316-323).
        """
        if self._state != "Batch":
            return BatchUpdates([], [], rank=self.config.num_factors)
        if self._thread is not None:
            self._thread.join()
        return self._finish_batch()

    def run(
        self,
        batches: Iterable[Ratings],
        limiter: ThroughputLimiter | None = None,
    ) -> Iterator[BatchUpdates]:
        for batch in batches:
            if limiter is not None:
                limiter.emit_batch_or_wait(int(batch.n))
            yield self.process(batch)

    # -- retrain machinery --------------------------------------------------

    def _retrain(self, history: Ratings) -> MFModel:
        """Full batch fit from scratch on the whole history.

        ≙ ``offlineDSGD(ratingsHistory, empty factors, ...)``
        (OnlineSpark.scala:119-124 — note the EMPTY initial factors: retrain
        from scratch, same as the PS param clear) or ``ALS.train``
        (:125-131).
        """
        cfg = self.config
        # retrain span runs on whichever thread retrains (background
        # mode gets its own tid lane in the trace) and blocks on the
        # fitted tables so device time is inside the span
        with self._trace.span("adaptive/retrain",
                              algorithm=cfg.offline_algorithm,
                              rows=int(history.n)) as sp:
            t0 = time.perf_counter() if self._obs_on else 0.0
            if cfg.offline_algorithm == "als":
                model = ALS(ALSConfig(
                    num_factors=cfg.num_factors, lambda_=cfg.lambda_,
                    iterations=cfg.offline_iterations,
                )).fit(history)
            else:
                model = DSGD(DSGDConfig(
                    num_factors=cfg.num_factors, lambda_=cfg.lambda_,
                    iterations=cfg.offline_iterations,
                    learning_rate=0.05, lr_schedule="constant",
                    minibatch_size=min(cfg.minibatch_size, 1024),
                )).fit(history)
            sp.out = (model.U, model.V)
            if self._obs_on:
                from large_scale_recommendation_tpu.utils.metrics import (
                    block,
                )

                block(sp.out)  # device time belongs in the measurement
                self._m_retrain_s.observe(time.perf_counter() - t0)
                self._m_retrains.inc()
        return model

    def _retrain_into_slot(self, history: Ratings, ctx=None) -> None:
        if ctx is not None:
            # re-enter the captured context on the retrain thread: the
            # retrain span (top-level on this thread's stack) exports
            # parent_span_id = the triggering batch's span
            with self._trace.activate(ctx):
                self._retrained = self._retrain(history)
        else:
            self._retrained = self._retrain(history)

    def _finish_batch(self) -> BatchUpdates:
        """Swap the retrained model in and replay the buffered queue."""
        model = self._retrained
        self._thread = None
        self._retrained = None
        self._state = "Online"
        if model is not None:
            self._install(model)
            self.retrain_count += 1
        buffered, self._buffer = self._buffer, []
        users: list = []
        items: list = []
        for b, off in buffered:  # ≙ fold onlinePullQueue into rs and resume
            out = self.online.partial_fit(b, offset=off)
            users.extend(out.user_updates)
            items.extend(out.item_updates)
        return BatchUpdates(users, items, rank=self.config.num_factors)

    def _install(self, model: MFModel) -> None:
        """Replace the online tables with the retrained factors wholesale.

        ≙ the model swap (OnlineSpark.scala:134-150). Vocabulary seen online
        but absent from the history snapshot survives with its online
        vectors.
        """
        wd = self.online.watchdog
        if wd is not None:
            # the retrain ran from history on a separate code path — a
            # diverged retrain must abort HERE, before it overwrites the
            # live tables and refreshes every serving engine (streaming
            # NaNs into a catalog swap is the failure this guards)
            try:
                wd.check_swap(model.U, model.V)
            except BaseException:
                if self._events is not None:
                    self._events.emit("adaptive.retrain_abort",
                                      severity="error",
                                      reason="diverged_retrain",
                                      retrain_count=self.retrain_count)
                raise
        U = np.asarray(model.U)
        V = np.asarray(model.V)
        for table, T, index in ((self.online.users, U, model.users),
                                (self.online.items, V, model.items)):
            real = index.ids >= 0
            ids = index.ids[real]
            rows = table.ensure(ids)
            table.load_rows(rows, T[real])
        # the swap is only COMPLETE once the serving layer sees it:
        # every live engine rebinds to a fresh snapshot (new catalog
        # version, O(1), no recompile — serving.engine.refresh). The
        # registry lock covers only the membership read: refresh()
        # acquires each engine's own lock, and holding the registry
        # lock across that would deadlock against an engine mid-serve
        # whose creator thread is waiting to register a sibling
        with self._engines_lock:
            engines = tuple(self._engines)
        snapshot = self.to_model() if engines else None
        for engine in engines:
            engine.refresh(snapshot)
        if engines and (self._lineage is not None
                        or self._disttrace is not None
                        or self._trace.enabled):
            # enrich each engine's fresh stamp (engine.refresh recorded
            # the swap instant) with what only the retrain layer knows:
            # WHICH retrain produced this build, the online step it
            # landed at, and PER PARTITION the WAL offset the online
            # tables have absorbed (offsets from different partitions
            # are independent number spaces — one flat max would let a
            # high-offset partition mask another's staleness) — during
            # a background retrain the stamps are frozen at the
            # pre-retrain offsets, which is exactly what this build's
            # history covers (buffered batches replay AFTER the swap
            # and ship with the next refresh). The critical-path mark
            # re-uses the lineage record's wall_time (the swap instant)
            # and the trace instant carries the version↔watermark join.
            offsets = dict(self.online.consumed_offsets) or {0: None}
            for engine in engines:
                for p, off in offsets.items():
                    t_swap = None
                    if self._lineage is not None:
                        rec = self._lineage.record_swap(
                            engine.version,
                            retrain_id=self.retrain_count + 1,
                            train_step=int(self.online.step),
                            wal_offset_watermark=off, partition=p,
                            source="retrain_install")
                        t_swap = rec["wall_time"]
                    if off is None:
                        continue
                    if self._disttrace is not None:
                        self._disttrace.note_swap(
                            engine.version, partition=p,
                            watermark=off, t=t_swap)
                    if self._trace.enabled:
                        self._trace.instant(
                            "lineage/swap_watermark",
                            version=int(engine.version), partition=int(p),
                            watermark=int(off),
                            source="retrain_install")
        if self._events is not None:
            self._events.emit("adaptive.retrain_install",
                              retrain_count=self.retrain_count + 1,
                              engines_refreshed=len(engines))

    def serving_engine(self, k: int = 10, **kwargs):
        """A ``ServingEngine`` bound to the CURRENT serving snapshot
        (``to_model``) that stays bound: every retrain swap
        (``_install``) refreshes it in place, so the engine's catalog
        version tracks the adaptive model's swaps automatically —
        serving a stream while the model retrains needs no manual
        refresh choreography. ``kwargs`` pass through to the engine
        (``mesh``, ``dtype``, ``train``, ``max_batch`` ...).

        Note: only the periodic *swap* auto-refreshes; per-micro-batch
        online updates are folded in at the next swap or by calling
        ``engine.refresh(adaptive.to_model())`` yourself.
        """
        from large_scale_recommendation_tpu.serving.engine import (
            ServingEngine,
        )

        with self._engines_lock:  # snapshot+register atomically vs. a
            # concurrent swap's refresh sweep
            engine = ServingEngine(self.to_model(), k=k, **kwargs)
            self._engines.add(engine)
        return engine

    # -- history ------------------------------------------------------------

    def _append_history(self, batch: Ratings) -> None:
        """≙ ``ratingsHistory = ratingsHistory union rs``
        (OnlineSpark.scala:68-70), as host arrays."""
        ru, ri, rv, rw = batch.to_numpy()
        real = rw > 0
        if not real.any():
            return
        self._history.append((ru[real], ri[real], rv[real]))
        self._history_rows += int(real.sum())
        limit = self.config.history_limit
        if limit is not None:
            while self._history_rows > limit and len(self._history) > 1:
                dropped = self._history.pop(0)
                self._history_rows -= len(dropped[0])

    def clear_history(self) -> None:
        """Drop the retrain history — the crash-recovery refill resets
        it before rebuilding from the log (``StreamingDriver.resume``),
        so resuming a warm model never duplicates rows."""
        self._history.clear()
        self._history_rows = 0

    def preload_history(self, batch: Ratings) -> None:
        """Refill the retrain history WITHOUT a gradient step — the
        crash-recovery path: factors come back from the checkpoint, but
        the history a future retrain fits from lives only in host
        memory and must be rebuilt from the durable log
        (``StreamingDriver.resume``). ``history_limit`` applies as
        usual."""
        self._append_history(batch)

    def _history_ratings(self) -> Ratings:
        ru = np.concatenate([h[0] for h in self._history])
        ri = np.concatenate([h[1] for h in self._history])
        rv = np.concatenate([h[2] for h in self._history])
        return Ratings.from_arrays(ru, ri, rv)

    # -- scoring ------------------------------------------------------------

    def predict(self, user_ids, item_ids, return_mask: bool = False):
        return self.online.predict(user_ids, item_ids,
                                   return_mask=return_mask)

    def rmse(self, data: Ratings) -> float:
        return self.online.rmse(data)

    def to_model(self) -> MFModel:
        """Snapshot the CURRENT serving state (the online tables, which
        absorb each retrain's wholesale swap) as a standard ``MFModel``
        — top-K serving / ranking / persistence for the adaptive combo,
        same contract as ``OnlineMF.to_model``."""
        return self.online.to_model()
