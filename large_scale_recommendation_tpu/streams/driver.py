"""StreamingDriver: crash-recovering online→serve ingest loop.

The runtime the reference got for free from its engines, rebuilt around
the durable pieces of this package: an ``EventLog`` partition is tailed
(``LogTailSource``) through a bounded backpressure queue
(``QueuedSource``) into ``OnlineMF``/``AdaptiveMF`` micro-batch updates,
with the consumed WAL offset checkpointed ATOMICALLY alongside the
factor tables (``utils.checkpoint.save_online_state``) — and each
adaptive retrain swap pushed into live ``ServingEngine``s through the
versioned-catalog path (PR 1), observed here via ``engine.on_refresh``.

Recovery contract (pinned by ``tests/test_streams_driver.py``):

- **at-least-once, zero loss**: a batch's offset stamp is recorded only
  when the update has been applied (``partial_fit(offset=...)``), and
  checkpoints persist factors+offset as one atomic snapshot. A crashed
  driver restarted via ``resume()`` re-tails the log from the
  checkpointed offset: every rating after it is replayed, nothing is
  skipped.
- **bounded duplication**: what IS replayed twice is at most the
  micro-batches applied since the last checkpoint — ≤
  ``checkpoint_every`` of them, i.e. ≤ ONE micro-batch at the default
  ``checkpoint_every=1``. SGD-style updates absorb a duplicated
  micro-batch as one extra (identical) gradient step — the same
  tolerance the reference's at-least-once Flink sources relied on.
  One widening: while an ``AdaptiveMF(background=True)`` retrain is in
  flight, arriving batches are buffered with a frozen offset stamp, so
  the checkpointable frontier cannot advance — a crash inside that
  window additionally replays the buffered batches (bounded by the
  retrain's duration). The driver holds checkpoints during the window
  (they could only repeat the pre-retrain offset) and writes one as
  soon as the swap flushes the buffer.
- **retrain-history rebuild**: ``AdaptiveMF``'s retrain history lives
  only in host memory (it is not part of the checkpoint); ``resume()``
  refills it from the retained log below the restored offset (capped
  at ``history_limit``), so the first post-restart retrain fits from
  the same data an uncrashed run's would have. Retention bounds this:
  records already retired by ``truncate_log`` cannot be refilled —
  aggressive retention trades rebuildable history for disk.
- **serve visibility**: after restart, the next retrain swap refreshes
  every attached engine to a fresh catalog version — the ingest→serve
  handoff survives the crash.

Telemetry (``telemetry()``): lag-in-records against the log head, queue
depth/high-water, drop/dead-letter/poison counters
(``utils.metrics.IngestStats``), checkpoint count, and the catalog
versions each swap published.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable

import numpy as np

from large_scale_recommendation_tpu.obs.disttrace import get_disttrace
from large_scale_recommendation_tpu.obs.events import get_events
from large_scale_recommendation_tpu.obs.lineage import get_lineage
from large_scale_recommendation_tpu.obs.registry import get_registry
from large_scale_recommendation_tpu.obs.trace import get_tracer
from large_scale_recommendation_tpu.streams.log import EventLog
from large_scale_recommendation_tpu.streams.sources import (
    LogTailSource,
    QueuedSource,
    StreamBatch,
)
from large_scale_recommendation_tpu.utils.checkpoint import (
    CheckpointManager,
    restore_online_state,
    save_online_state,
)


@dataclasses.dataclass(frozen=True)
class StreamingDriverConfig:
    """Ingest-loop knobs.

    ``checkpoint_every`` is the duplication bound: a crash replays at
    most that many micro-batches (default 1 → ≤ one duplicated
    micro-batch; raise it to trade recovery duplication for checkpoint
    I/O on very fast streams). ``None`` hands checkpointing to an
    EXTERNAL coordinator: the driver never snapshots on its own — the
    ``streams.parallel.ParallelIngestRunner`` barrier owns the atomic
    cross-partition ``{partition: offset}`` + (U, V, step) commit, and
    N drivers each writing their own snapshot would race it.
    ``truncate_log`` opts into retention: after each checkpoint the log
    retires segments wholly below the checkpointed offset — never
    beyond it, so the replay tail always exists.
    """

    batch_records: int = 4096
    checkpoint_every: int | None = 1
    checkpoint_keep: int = 3
    queue_capacity: int = 16
    queue_policy: str = "block"
    poll_interval_s: float = 0.01
    truncate_log: bool = False
    emit_updates: bool = False  # pure-ingest by default (poll the model)


class StreamingDriver:
    """Wire one ``EventLog`` partition into an online model and its
    serving engines.

    ``model`` is an ``OnlineMF`` (pure streaming) or ``AdaptiveMF``
    (streaming + periodic retrain; its retrain swaps auto-refresh the
    engines created via ``serving_engine``). ``checkpoint_dir`` holds
    the atomic (factors, step, WAL offset) snapshots this driver's
    ``resume``/crash-recovery contract is built on.
    """

    def __init__(self, model: Any, log: EventLog, checkpoint_dir: str,
                 partition: int = 0,
                 config: StreamingDriverConfig | None = None,
                 on_batch: Callable[[StreamBatch], None] | None = None,
                 inspector: Any = None, evaluator: Any = None):
        from large_scale_recommendation_tpu.models.adaptive import AdaptiveMF

        self.model = model
        self.log = log
        self.partition = partition
        self.config = config or StreamingDriverConfig()
        self.manager = CheckpointManager(checkpoint_dir,
                                         keep=self.config.checkpoint_keep)
        self.on_batch = on_batch
        # model-plane hooks, every one an `is not None` test per batch:
        # the data-quality inspector (obs.dataquality) sees each batch's
        # raw arrays BEFORE training; the online evaluator
        # (obs.quality) routes a holdout fraction of each batch into
        # its reservoir and zeroes those rows' weights so partial_fit
        # never trains on them; the lineage journal (obs.lineage,
        # module default — installed via obs.enable_lineage) receives
        # per-batch ingest watermarks and per-swap provenance
        self.inspector = inspector
        self.evaluator = evaluator
        self._lineage = get_lineage()
        # critical-path analyzer (obs.disttrace, module default): the
        # driver marks apply-start/applied/swap instants — one `is not
        # None` test per site, bounded deque appends when installed
        self._disttrace = get_disttrace()
        self._adaptive = isinstance(model, AdaptiveMF)
        self._online = model.online if self._adaptive else model
        # ids touched since the last serving refresh — the WAL batches
        # flowing through _apply know exactly which rows moved, which is
        # what lets refresh_serving ship DELTAS (engine.apply_delta:
        # scatter + dirty-row requantization) instead of whole-table
        # rebuilds. Sets of python ints: micro-batches touch hundreds of
        # ids, catalogs hold millions of rows. Guarded by _dirty_lock:
        # run(follow=True) applies batches on one thread while
        # refresh_serving lands from a serving-side thread — an
        # unguarded snapshot-then-clear would erase ids marked between
        # the two steps, and those rows would serve stale FOREVER (no
        # later refresh would know about them).
        self._dirty_users: set[int] = set()
        self._dirty_items: set[int] = set()
        self._dirty_lock = threading.Lock()
        self._stop = threading.Event()
        self._source: QueuedSource | None = None
        self._last_stats: dict = {}
        self.batches_processed = 0
        self.records_processed = 0
        self.checkpoints_written = 0
        self._since_checkpoint = 0
        # catalog versions observed via engine.on_refresh — the proof a
        # retrain swap actually reached serving
        self.catalog_versions: list[int] = []
        self._engines: list = []
        # observability handles bind at construction (null singletons
        # when disabled — zero hot-path cost, see obs/)
        obs = get_registry()
        self._obs = obs
        self._obs_on = obs.enabled
        self._trace = get_tracer()
        # structured event journal (obs.events): None unless installed —
        # the checkpoint-commit emission is one `is not None` test
        self._events = get_events()
        part = str(partition)
        self._m_batches = obs.counter("streams_batches_total",
                                      partition=part)
        self._m_records = obs.counter("streams_records_total",
                                      partition=part)
        self._m_ckpt = obs.histogram("streams_checkpoint_s",
                                     partition=part)
        self._m_lag = obs.gauge("streams_lag_records", partition=part)
        self._m_depth = obs.gauge("streams_queue_depth", partition=part)
        # timed telemetry cadence (start_telemetry_export): None until
        # explicitly started — zero threads, zero cost by default
        self._telemetry_task = None
        self._prefetcher = None

    # -- recovery ------------------------------------------------------------

    def resume(self) -> bool:
        """Restore the latest (factors, step, WAL offset) snapshot, if
        any — the restart half of the recovery contract. Returns whether
        a snapshot was loaded. The next ``run`` tails the log from the
        restored offset, replaying everything after it. For an
        ``AdaptiveMF``, the retrain history (host memory only, not in
        the checkpoint) is rebuilt from the retained log below the
        restored offset, so the first post-restart retrain fits from
        the same data an uncrashed run's would — up to retention:
        records already retired by ``truncate_log`` are gone."""
        if self.manager.latest_step() is None:
            return False
        restore_online_state(self.manager, self._online)
        if self._adaptive:
            self._rebuild_history()
        return True

    def _rebuild_history(self) -> None:
        consumed = self._online.consumed_offsets.get(self.partition)
        if consumed is None:
            return
        # resume() may be called on a warm model (or twice): reset
        # before refilling so history rows are never duplicated
        self.model.clear_history()
        start = self.log.start_offset(self.partition)
        limit = self.model.config.history_limit
        if limit is not None:
            # only the newest history_limit records survive the refill
            # anyway — don't read what _append_history would evict
            start = max(start, consumed - limit)
        offset = start
        while offset < consumed:
            batch, nxt = self.log.read(
                self.partition, offset,
                min(self.config.batch_records, consumed - offset))
            if nxt == offset:
                break
            self.model.preload_history(batch)
            offset = nxt

    @property
    def consumed_offset(self) -> int:
        """Next unconsumed log offset for this driver's partition:
        restored by ``resume``, advanced by each applied micro-batch,
        floored at the log's retention floor for a fresh model."""
        offsets = self._online.consumed_offsets
        if self.partition in offsets:
            return offsets[self.partition]
        # fresh model only — start_offset refreshes from disk (listdir +
        # per-segment stat), far too hot for the per-batch checkpoint
        # and telemetry paths that land here once the stamp exists
        return self.log.start_offset(self.partition)

    def checkpoint(self) -> str:
        """Write one atomic (factors, step, WAL offset) snapshot now."""
        t0 = time.perf_counter() if self._obs_on else 0.0
        path = save_online_state(self.manager, self._online,
                                 self._online.step)
        if self._obs_on:
            self._m_ckpt.observe(time.perf_counter() - t0)
        self.checkpoints_written += 1
        self._since_checkpoint = 0
        if self._events is not None:
            self._events.emit("stream.checkpoint",
                              partition=self.partition,
                              step=int(self._online.step),
                              offset=int(self.consumed_offset),
                              path=path)
        if self.config.truncate_log:
            # retention chases the CHECKPOINTED offset (what this very
            # snapshot guarantees is applied), never the live one — the
            # replay tail of any older surviving checkpoint may die, but
            # the latest one (the one resume() uses) always replays
            self.log.truncate_before(self.partition, self.consumed_offset)
        return path

    # -- ingest loop ---------------------------------------------------------

    def run(self, max_batches: int | None = None,
            follow: bool = False) -> int:
        """Tail the log from ``consumed_offset`` and apply micro-batches
        until caught up (``follow=False``), ``max_batches`` applied, or
        ``stop()``. Returns the number of batches applied this call.

        Each batch goes through ``AdaptiveMF.process`` (which may
        trigger/absorb retrains and refresh attached engines) or
        ``OnlineMF.partial_fit`` in pure-ingest mode, with its offset
        stamp; every ``checkpoint_every`` batches the atomic snapshot is
        written. A final checkpoint lands when the loop exits with
        unsnapshotted progress, so a clean catch-up run needs no replay
        at all on restart.
        """
        cfg = self.config
        if self._stop.is_set():
            # a stop delivered BEFORE the loop started (the parallel
            # runner's stop() racing a consumer thread that hasn't
            # entered run() yet) must win: clearing it unconditionally
            # erased the request and a follow-mode loop ran forever.
            # The pending stop is consumed — the run after this one
            # starts fresh.
            self._stop.clear()
            return 0
        tail = LogTailSource(
            self.log, self.partition, start_offset=self.consumed_offset,
            batch_records=cfg.batch_records, follow=follow,
            poll_interval_s=cfg.poll_interval_s)
        # WAL lookahead for a tiered user store: the feeder announces
        # each batch's user ids (on_enqueue) and the prefetcher stages
        # them into the device slot pool while earlier batches train —
        # the queue's whole lead over the consumer becomes prefetch
        # distance. Duck-typed on the store's prefetch seam: plain
        # tables have none, and the wiring collapses to exactly the
        # historical QueuedSource call.
        prefetcher = None
        if hasattr(self._online.users, "prefetch"):
            from large_scale_recommendation_tpu.store.prefetch import (
                StorePrefetcher,
            )
            prefetcher = StorePrefetcher(self._online.users).start()
        self._prefetcher = prefetcher
        self._source = QueuedSource(tail, capacity=cfg.queue_capacity,
                                    policy=cfg.queue_policy,
                                    on_enqueue=(prefetcher.submit_batch
                                                if prefetcher is not None
                                                else None))
        applied = 0
        seam = self._trace.seam
        try:
            batches = iter(self._source)
            while True:
                # seam "fit/online/source": waiting on the feeder's queue
                # and taking the next micro-batch off it (a profiler
                # capture charges the chip's idle time inside to it)
                with seam("fit/online/source"):
                    batch = next(batches, None)
                if batch is None:
                    break
                self._apply(batch)
                applied += 1
                if (max_batches is not None and applied >= max_batches) \
                        or self._stop.is_set():
                    self._source.stop()
                    break
        finally:
            # on ANY exit — including a mid-apply crash — wind the feeder
            # down and keep its counters readable; the final checkpoint
            # below is deliberately NOT in this block: a crash must not
            # checkpoint (the failed batch's offset may already be
            # stamped, and persisting it would turn at-least-once into
            # maybe-lost)
            self._source.stop()
            if prefetcher is not None:
                prefetcher.stop()
            self._last_stats = self._source.stats.snapshot()
            self._last_stats["dead_letter_buffered"] = len(
                self._source.dead_letters)
            if prefetcher is not None:
                self._last_stats["prefetch"] = prefetcher.snapshot()
        # a feeder fault must surface even when the consume loop exited
        # early (max_batches/stop) before draining to the end-of-stream
        # re-raise inside batches() — and it must land BEFORE the final
        # checkpoint, same as any other runtime fault
        self._source.finish()
        if self._since_checkpoint and self.config.checkpoint_every is not None:
            self.checkpoint()
        # a stop consumed by THIS run must not leak into the next one
        # (the entry check above would silently no-op it)
        self._stop.clear()
        return applied

    def _apply(self, batch: StreamBatch) -> None:
        if self._trace.enabled:
            # the batch's TraceContext (minted by the source from the
            # batch's durable offsets) is ACTIVATED around the apply:
            # every span opened inside — this ingest span, the nested
            # online/partial_fit spans, a retrain the batch triggers —
            # exports the record family's trace id, which is what the
            # pod assembler joins the cross-process chain on
            with self._trace.activate(batch.ctx), \
                    self._trace.span("stream/ingest_batch",
                                     partition=int(batch.partition),
                                     start_offset=int(batch.start_offset),
                                     end_offset=int(batch.end_offset)):
                self._apply_batch(batch)
        else:
            self._apply_batch(batch)

    def _apply_batch(self, batch: StreamBatch) -> None:
        offset = (batch.partition, batch.end_offset)
        ratings = batch.ratings
        if self._disttrace is not None:
            # apply START: the queue_wait → train_apply stage boundary
            self._disttrace.note_dequeue(batch.end_offset,
                                         partition=batch.partition)
        if self.inspector is not None:
            # observe-only: the gate makes rot visible, quarantine
            # stays the queue's job — the batch trains unmodified
            self.inspector.inspect_batch(batch)
        if self.evaluator is not None:
            # the holdout rows come OUT here — their weights zero, so
            # the model (and the dirty-id tracking below) never sees
            # them as real; the reservoir is out-of-sample forever
            ratings = self.evaluator.split_batch(ratings)
        if self._adaptive:
            self.model.process(ratings, offset=offset)
        else:
            self.model.partial_fit(
                ratings, offset=offset,
                emit_updates=self.config.emit_updates)
        if self._lineage is not None or self._disttrace is not None:
            # the ingest half of the freshness join: this offset landed
            # (APPLIED — the model's own stamp is the proof, the same
            # gate the checkpoint path uses below; a batch buffered
            # during a background retrain is not applied yet, and its
            # covering mark lands with the first post-swap batch whose
            # stamp advances past it) at this wall time. ONE clock read
            # shared by both planes, so the critical-path swap_lag
            # stage reconciles exactly against the lineage histogram.
            applied = self._online.consumed_offsets.get(
                batch.partition, 0)
            if applied >= batch.end_offset:
                t_applied = time.time()
                if self._lineage is not None:
                    self._lineage.note_ingest(applied,
                                              partition=batch.partition,
                                              t=t_applied)
                if self._disttrace is not None:
                    self._disttrace.note_applied(
                        applied, partition=batch.partition, t=t_applied)
        if self._engines:  # dirty-id tracking feeds delta refreshes
            ru, ri, _, rw = ratings.to_numpy()
            real = rw > 0
            du = np.unique(ru[real]).tolist()
            di = np.unique(ri[real]).tolist()
            with self._dirty_lock:
                self._dirty_users.update(du)
                self._dirty_items.update(di)
        self.batches_processed += 1
        self.records_processed += batch.n
        self._since_checkpoint += 1
        if self._obs_on:
            self._m_batches.inc()
            self._m_records.inc(batch.n)
            if self._source is not None and self._source.queue is not None:
                self._m_depth.set(self._source.stats.depth)
        if self.on_batch is not None:
            self.on_batch(batch)
        stamped = self._online.consumed_offsets.get(batch.partition, 0)
        if stamped < batch.end_offset:
            # buffered during a background retrain: the model's offset
            # stamp is frozen until the swap replays the buffer, so a
            # checkpoint now would just re-persist the pre-retrain
            # offset. Hold — _since_checkpoint keeps accumulating, and
            # the first post-swap batch (stamp advanced past it) writes
            # one checkpoint covering everything replayed.
            return
        if (self.config.checkpoint_every is not None
                and self._since_checkpoint >= self.config.checkpoint_every):
            self.checkpoint()

    def stop(self) -> None:
        """Ask a running ``run(follow=True)`` loop to wind down (it
        still checkpoints its progress on the way out)."""
        self._stop.set()
        if self._source is not None:
            self._source.stop()

    # -- serving -------------------------------------------------------------

    def serving_engine(self, k: int = 10, **kwargs):
        """A ``ServingEngine`` over the live model, wired for swap
        observation: every refresh (adaptive retrain swaps arrive
        automatically via the PR-1 versioned-catalog path; online models
        refresh via ``refresh_serving``) appends its catalog version to
        ``catalog_versions``."""
        if self._adaptive:
            engine = self.model.serving_engine(k=k, **kwargs)
        else:
            from large_scale_recommendation_tpu.serving.engine import (
                ServingEngine,
            )

            engine = ServingEngine(self.model.to_model(), k=k, **kwargs)
        engine.on_refresh = self.catalog_versions.append
        self.catalog_versions.append(engine.version)  # the bind itself
        self._engines.append(engine)
        self._note_swap(engine.version, self.consumed_offset,
                        source="engine_bind")
        return engine

    def _note_swap(self, version: int, watermark: int,
                   source: str) -> None:
        """One swap's causal stamps, each plane behind its own gate:
        the lineage record (enriched with the watermark only this
        driver knows), the critical-path swap mark (re-using the
        lineage record's own ``wall_time`` — the swap instant — so the
        ``swap_lag`` stage reconciles exactly against the freshness
        histogram), and a ``lineage/swap_watermark`` trace instant (the
        version↔watermark join the assembled record trace pivots on)."""
        if (self._lineage is None and self._disttrace is None
                and not self._trace.enabled):
            return
        step = int(self._online.step)
        t_swap = None
        if self._lineage is not None:
            rec = self._lineage.record_swap(
                version, wal_offset_watermark=watermark,
                partition=self.partition, train_step=step,
                source=source)
            t_swap = rec["wall_time"]
        if self._disttrace is not None:
            self._disttrace.note_swap(version, partition=self.partition,
                                      watermark=watermark, t=t_swap)
        if self._trace.enabled:
            self._trace.instant("lineage/swap_watermark",
                                version=int(version),
                                partition=int(self.partition),
                                watermark=int(watermark), source=source)

    def refresh_serving(self, delta: bool | None = None) -> None:
        """Push the live model's state into every attached engine — the
        manual analogue of the adaptive swap auto-refresh, for pure
        ``OnlineMF`` streams (and an ``AdaptiveMF``'s between-swap
        online increments) that want periodic serve visibility.

        ``delta=None`` (auto, the default) ships a DELTA whenever it
        can: the ids touched since the last refresh (tracked per
        applied WAL batch) map to engine rows and only those rows
        install — one scatter per table plus dirty-row requantization
        of the int8 fast path (``ServingEngine.apply_delta``), instead
        of re-sharding the whole catalog. Falls back to a full
        ``refresh`` whenever any engine's geometry no longer matches
        the live tables (vocab grew since its snapshot) — correctness
        never depends on the delta path being available. ``delta=False``
        forces the full rebuild; ``delta=True`` asserts deltas were
        possible (raises if not — the knob regression tests use).

        The retrain SWAP path (``AdaptiveMF._install``) stays a full
        refresh by construction: a from-scratch retrain rewrites every
        row, which is exactly the whole-table case."""
        if not self._engines:
            with self._dirty_lock:
                self._dirty_users.clear()
                self._dirty_items.clear()
            return
        online = self._online

        def geometry_matches(engine) -> bool:
            m = engine.model
            return (int(m.U.shape[0]) == online.users.num_rows
                    and int(m.V.shape[0]) == online.items.num_rows)

        can_delta = all(geometry_matches(e) for e in self._engines)
        if delta is True and not can_delta:
            raise ValueError(
                "delta refresh requested but an engine's geometry no "
                "longer matches the live tables (vocab grew) — use "
                "delta=None/False")
        # atomically TAKE the dirty sets (fresh empties replace them):
        # ids marked by a concurrently-applying batch after this point
        # land in the new sets and ship on the NEXT refresh — never
        # silently erased (the clear-after-snapshot race)
        with self._dirty_lock:
            dirty_users, self._dirty_users = self._dirty_users, set()
            dirty_items, self._dirty_items = self._dirty_items, set()
        if delta is not False and can_delta:
            du = (np.fromiter(dirty_users, np.int64, len(dirty_users))
                  if dirty_users else np.zeros(0, np.int64))
            di = (np.fromiter(dirty_items, np.int64, len(dirty_items))
                  if dirty_items else np.zeros(0, np.int64))
            u_rows, _ = online.users.rows_for(du)
            i_rows, _ = online.items.rows_for(di)
            # gather_rows (data/tables.py seam): a plain table's
            # pow2-padded device gather; a tiered store's merged host
            # gather (pool values win for hot rows) — engine deltas
            # always ship the LIVE values either way
            U_vals = online.users.gather_rows(u_rows)
            V_vals = online.items.gather_rows(i_rows)
            for engine in self._engines:
                engine.apply_delta(item_rows=i_rows, V_rows=V_vals,
                                   user_rows=u_rows, U_rows=U_vals)
        else:
            snapshot = self.model.to_model()
            for engine in self._engines:
                engine.refresh(snapshot)
        if (self._lineage is not None or self._disttrace is not None
                or self._trace.enabled):
            # the swap provenance this refresh created: each engine's
            # new version now covers everything this driver has applied
            # — the consumed offset IS the servable watermark
            watermark = self.consumed_offset
            for engine in self._engines:
                self._note_swap(engine.version, watermark,
                                source="stream_refresh")

    @staticmethod
    def _gather_rows(table_arr, rows: np.ndarray) -> np.ndarray:
        """One pow2-padded device gather of the dirty rows (the same
        bounded-shape-family idiom as ``BatchUpdates``' update gather)."""
        import jax.numpy as jnp

        from large_scale_recommendation_tpu.utils.shapes import pow2_pad

        n = len(rows)
        if n == 0:
            return np.zeros((0, int(table_arr.shape[1])), np.float32)
        idx = np.zeros(pow2_pad(n), np.int64)
        idx[:n] = rows
        return np.asarray(table_arr[jnp.asarray(idx)])[:n]

    # -- telemetry -----------------------------------------------------------

    def start_telemetry_export(self, interval_s: float = 5.0):
        """Publish ``telemetry()`` into the registry on a timed cadence
        (daemon thread). Without this, the lag/queue gauges only refresh
        when someone calls ``telemetry()`` by hand — a ``/metrics``
        scrape between calls would read stale stream lag. Idempotent:
        an already-running exporter is returned as-is. The exporter is
        independent of ``run()``'s lifecycle (telemetry of a *stopped*
        driver — frozen consumed offset vs a still-growing log — is
        exactly the lag signal a health check wants); stop it via
        ``stop_telemetry_export()``. Returns the ``PeriodicTask``."""
        from large_scale_recommendation_tpu.obs.health import ensure_periodic

        self._telemetry_task = ensure_periodic(
            self._telemetry_task, self.telemetry, interval_s,
            name=f"telemetry-p{self.partition}")
        return self._telemetry_task

    def stop_telemetry_export(self) -> None:
        task, self._telemetry_task = self._telemetry_task, None
        if task is not None:
            task.stop()

    def telemetry(self) -> dict:
        """One structured snapshot of the ingest tier: progress, lag
        against the log head, queue/drop/dead-letter counters from the
        current (or last) run, checkpoint count, and observed catalog
        versions."""
        queue = dict(self._last_stats)
        if self._source is not None and self._source.queue is not None:
            queue = self._source.stats.snapshot()
            queue["dead_letter_buffered"] = len(self._source.dead_letters)
        # lag for THIS driver's partition only — EventLog.lag would also
        # count every other partition's backlog (missing partitions are
        # charged from their floor), which is not this driver's lag
        end = self.log.end_offset(self.partition)
        if self._obs_on:
            # per-partition lag against the TRUE log head — refreshed
            # here (telemetry cadence), not per batch: end_offset stats
            # the disk, far too hot for the apply path
            self._m_lag.set(max(0, end - self.consumed_offset))
            from large_scale_recommendation_tpu.utils.metrics import (
                publish_fields,
            )

            publish_fields(queue, registry=self._obs,
                           prefix="streams_queue",
                           partition=str(self.partition))
        return {
            "partition": self.partition,
            "batches_processed": self.batches_processed,
            "records_processed": self.records_processed,
            "consumed_offset": self.consumed_offset,
            "log_end_offset": end,
            "lag_records": max(0, end - self.consumed_offset),
            "checkpoints_written": self.checkpoints_written,
            "catalog_versions": list(self.catalog_versions),
            "dirty_users": len(self._dirty_users),
            "dirty_items": len(self._dirty_items),
            "queue": queue,
        }
