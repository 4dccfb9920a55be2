"""``TieredFactorStore``: the user table beyond device memory.

Layout (docs/TIERING.md has the full diagram):

- **cold tier** — the whole table as host ``float32[capacity, rank]``
  (numpy; ``mmap_dir`` swaps the allocation for ``np.memmap`` so the
  cold tier can exceed RAM too). Rows are the same first-seen-order
  rows a plain ``GrowableFactorTable`` assigns — the id machinery IS
  the base class's, so checkpoints, ``rows_for`` and serving row maps
  are unchanged.
- **hot tier** — a FIXED device pool ``float32[slot_capacity, rank]``
  (``.array``; rank-sharded slices under the ``'model'`` axis ride
  through ``device_put`` exactly like a plain table's array). The pool
  never grows: one compile family per (slot_capacity, pad) pair no
  matter how far the cold tier scales.
- **maps** — ``_row_slot`` (cold row → slot, −1 cold) and
  ``_slot_row`` (slot → cold row, −1 free), plus per-slot dirty bits,
  pin refcounts and LRU ticks.

Training indexes SLOTS: ``acquire_rows(ids)`` registers the ids,
faults their rows hot (write-back LRU eviction of unpinned slots),
pins them against eviction and returns slot indices; the commit hooks
scatter trained values back into the live pool; ``release_rows``
unpins. Misses resolve on the HOST side of the jit boundary — by the
time a kernel traces, every index is a resident slot (the graftlint
``tier-boundary`` rule keeps it that way).

Bit-exactness with the untiered path (pinned by
``tests/test_store.py``): the id→slot map is injective within a
batch, so ``online_train`` sees the same collision structure; slot
values are exact f32 round-trips of cold rows; pad entries repeat a
REAL owned slot (idempotent identity writes); concurrent commits
scatter only their own pinned slots. Capacity therefore changes WHEN
rows move between tiers, never what any kernel computes.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import time

import numpy as np

import jax
import jax.numpy as jnp

from large_scale_recommendation_tpu.data.tables import GrowableFactorTable
from large_scale_recommendation_tpu.obs.contention import named_rlock
from large_scale_recommendation_tpu.obs.registry import get_registry
from large_scale_recommendation_tpu.obs.store import set_store
from large_scale_recommendation_tpu.obs.transfers import get_transfers
from large_scale_recommendation_tpu.utils.shapes import (
    next_pow2 as _next_pow2,
    pow2_pad as _pow2_pad,
)

# the pool update family — padded by callers (pow2 with repeated-own-
# slot pads: duplicate indices carry duplicate values, so scatter order
# cannot matter), compiled once per (pool_shape, pad) pair. NOT donated,
# same rationale as tables._install_rows: serving snapshots pool refs.
_scatter_slots = jax.jit(lambda pool, idx, vals: pool.at[idx].set(vals))
_commit_slots = jax.jit(lambda cur, src, idx: cur.at[idx].set(src[idx]))


@dataclasses.dataclass
class StoreStats:
    """Always-on host counters (the ``IngestStats`` precedent: cheap
    int/float fields, no gate — only *registry* instruments need one).
    ``hits``/``misses`` count the TRAINING acquire path only — and
    only REVISITED rows, so ``hit_rate`` answers "did prefetch keep
    the working set hot?". First-seen registrations count as
    ``installs`` instead: initialization is vocabulary growth the
    untiered path pays identically, not a prefetch failure.
    Serve-side traffic has its own pair."""

    hits: int = 0
    misses: int = 0
    installs: int = 0
    prefetched: int = 0
    evictions: int = 0
    writebacks: int = 0
    demand_fault_s: float = 0.0
    serve_hits: int = 0
    serve_misses: int = 0
    host_bytes: int = 0

    @property
    def hit_rate(self) -> float:
        seen = self.hits + self.misses
        return self.hits / seen if seen else 1.0

    def snapshot(self) -> dict:
        out = dataclasses.asdict(self)
        out["hit_rate"] = self.hit_rate
        return out


class TieredFactorStore(GrowableFactorTable):
    """Drop-in ``GrowableFactorTable`` whose device array is a fixed
    slot pool over a host-RAM cold tier.

    ``slot_capacity`` is the device budget in rows; every concurrently
    pinned working set (one micro-batch's unique rows × in-flight
    applies) must fit it — exceeding it raises with the accounting
    rather than silently thrashing. ``mmap_dir`` backs the cold tier
    with ``np.memmap`` files. Construction installs the store as the
    process's STORE obs plane (``obs.store.get_store`` — latest wins).
    """

    def __init__(self, initializer, capacity: int = 1024,
                 slot_capacity: int = 256, device_put=None,
                 mmap_dir: str | None = None):
        self.slot_capacity = max(_next_pow2(int(slot_capacity)), 8)
        self._mmap_dir = mmap_dir
        S = self.slot_capacity
        self._slot_row = np.full(S, -1, np.int64)
        self._slot_dirty = np.zeros(S, bool)
        self._slot_pin = np.zeros(S, np.int64)
        self._slot_tick = np.zeros(S, np.int64)
        self._tick = 0
        self.stats = StoreStats()
        # one reentrant lock over every map/tier mutation. Order with
        # the model: apply_lock → store lock (acquire/commit/snapshot
        # run under the model's apply_lock in concurrent mode); the
        # serving and prefetch threads take the store lock alone.
        self._lock = named_rlock("store.tiered")
        obs = get_registry()
        self._obs_on = obs.enabled
        self._m_hit_rate = obs.gauge("tier_hit_rate")
        self._m_wait = obs.counter("tier_prefetch_wait_s")
        self._m_evictions = obs.counter("tier_evictions_total")
        self._m_host_bytes = obs.gauge("tier_host_bytes")
        super().__init__(initializer, capacity=capacity,
                         device_put=device_put)
        self._publish_host_bytes()
        set_store(self)

    # -- storage hooks (base-class seams) ------------------------------------

    def _alloc_cold(self, cap: int) -> np.ndarray:
        if self._mmap_dir is None:
            return np.zeros((cap, self.rank), np.float32)
        os.makedirs(self._mmap_dir, exist_ok=True)
        # one file per capacity level: growth maps a fresh file and
        # copies (O(log n) times total, the geometric-doubling bound)
        path = os.path.join(self._mmap_dir, f"cold_{cap}x{self.rank}.f32")
        return np.memmap(path, dtype=np.float32, mode="w+",
                         shape=(cap, self.rank))

    def _make_array(self):
        self.cold = self._alloc_cold(self.capacity)
        self._row_slot = np.full(self.capacity, -1, np.int64)
        return self._device_put(
            jnp.zeros((self.slot_capacity, self.rank), jnp.float32))

    @property
    def array(self):
        """The device SLOT POOL (fixed shape) — what training kernels
        index after ``acquire_rows`` translated rows to slots."""
        return self._pool

    @array.setter
    def array(self, value):
        self._pool = value

    def array_copy(self):
        return jnp.copy(self._pool)

    @contextlib.contextmanager
    def updating(self):
        # the pool is shared with the prefetch and serving threads and
        # rebound under the store lock, so the update donates a copy of
        # it (the pool's bytes once, as a copying update would pay);
        # ``install_trained`` scatters the batch's slots into the pool
        # as it then stands
        yield jnp.copy(self._pool)

    @contextlib.contextmanager
    def borrowed(self):
        yield self.full_table()  # TABLE rows: the merged hot and cold view

    @property
    def device_bytes(self) -> int:
        return int(self._pool.nbytes)

    def _install(self, fresh, base: int) -> None:
        # initializer output for newly registered (+pad) rows lands in
        # the COLD tier; rows fault hot on first acquire. Called with
        # the store lock held (every path into ensure() takes it).
        f = np.asarray(fresh, np.float32)
        self.cold[base:base + len(f)] = f

    def _grow(self, need: int) -> None:
        new_cap = _next_pow2(need)
        cold = self._alloc_cold(new_cap)
        cold[: self.capacity] = self.cold[: self.capacity]
        self.cold = cold
        row_slot = np.full(new_cap, -1, np.int64)
        row_slot[: self.capacity] = self._row_slot
        self._row_slot = row_slot
        ids_buf = np.empty(new_cap, np.int64)
        ids_buf[: self._n] = self._ids_buf[: self._n]
        self._ids_buf = ids_buf
        self.capacity = new_cap
        self._publish_host_bytes()

    def ensure(self, ids: np.ndarray) -> np.ndarray:
        # the prefetch thread registers ids concurrently with the apply
        # path — the base machinery is not thread-safe, so every entry
        # serializes on the store lock (reentrant: acquire_rows nests)
        with self._lock:
            return super().ensure(ids)

    def rows_for(self, ids: np.ndarray):
        with self._lock:  # _sorted_cache mutates under concurrent ensure
            return super().rows_for(ids)

    # -- fault / eviction core (store lock held) ------------------------------

    def _publish_host_bytes(self) -> None:
        n = int(self.cold.nbytes + self._ids_buf.nbytes
                + self._row_slot.nbytes)
        self.stats.host_bytes = n
        if self._obs_on:
            self._m_host_bytes.set(n)

    def _gather_pool(self, slots: np.ndarray) -> np.ndarray:
        n = len(slots)
        idx = np.full(_pow2_pad(n), slots[0], np.int64)
        idx[:n] = slots
        # host sync is the point: write-back must land in the cold tier
        # before the slot is reused
        return np.asarray(self._pool[jnp.asarray(idx)])[:n]

    def _evict(self, victims: np.ndarray) -> None:
        dirty = self._slot_dirty[victims]
        if dirty.any():
            dv = victims[dirty]
            ledger = get_transfers()
            t0 = time.perf_counter() if ledger is not None else 0.0
            self.cold[self._slot_row[dv]] = self._gather_pool(dv)
            if ledger is not None:  # logical bytes: len(dv) == writebacks
                ledger.note_transfer("store.writeback", "d2h",
                                     len(dv) * self.rank * 4,
                                     time.perf_counter() - t0)
            self.stats.writebacks += int(dirty.sum())
        self._row_slot[self._slot_row[victims]] = -1
        self._slot_row[victims] = -1
        self._slot_dirty[victims] = False
        self.stats.evictions += len(victims)
        if self._obs_on:
            self._m_evictions.inc(len(victims))

    def _load_slots(self, slots: np.ndarray, rows: np.ndarray) -> None:
        n = len(slots)
        p = _pow2_pad(n)
        sidx = np.full(p, slots[0], np.int64)
        sidx[:n] = slots
        vals = np.zeros((p, self.rank), np.float32)
        vals[:n] = self.cold[rows]
        vals[n:] = vals[0]  # pad repeats slot[0] with its OWN value
        self._pool = self._device_put(
            _scatter_slots(self._pool, jnp.asarray(sidx),
                           jnp.asarray(vals)))
        self._slot_row[slots] = rows
        self._row_slot[rows] = slots
        self._slot_tick[slots] = self._tick
        self._tick += 1

    def _fault_in(self, uniq_rows: np.ndarray, pin: bool, dirty: bool,
                  best_effort: bool = False, demand: bool = True,
                  fresh: int = 0) -> int:
        """Make ``uniq_rows`` (unique cold rows) resident. Returns the
        number of rows faulted (0 = fully hot already). ``best_effort``
        (the prefetch path) loads what fits instead of raising when
        pinned demand exceeds the pool. ``fresh`` of the rows were
        first registered by this very call — they fault (no cold value
        is resident by definition) but count as installs, not misses."""
        slots = self._row_slot[uniq_rows]
        hot = slots >= 0
        hs = slots[hot]
        if hs.size:
            self._slot_tick[hs] = self._tick
            self._tick += 1
            if pin:
                self._slot_pin[hs] += 1
            if dirty:
                self._slot_dirty[hs] = True
        miss_rows = uniq_rows[~hot]
        if demand:
            self.stats.hits += int(hs.size)
            self.stats.misses += int(miss_rows.size) - fresh
            self.stats.installs += fresh
            if self._obs_on:
                self._m_hit_rate.set(self.stats.hit_rate)
        if miss_rows.size == 0:
            return 0
        free = np.nonzero(self._slot_row < 0)[0]
        need = len(miss_rows)
        if len(free) < need:
            shortfall = need - len(free)
            cand = np.nonzero((self._slot_row >= 0)
                              & (self._slot_pin == 0))[0]
            if len(cand) < shortfall:
                if best_effort:
                    take_n = len(free) + len(cand)
                    if take_n == 0:
                        return 0
                    miss_rows = miss_rows[:take_n]
                    need = take_n
                    shortfall = need - len(free)
                else:
                    if pin and hs.size:  # undo the hot-slot pins: a
                        # raising acquire must leak no refcounts
                        self._slot_pin[hs] -= 1
                    pinned = int((self._slot_pin > 0).sum())
                    raise RuntimeError(
                        f"tiered store overcommitted: need {need} slots "
                        f"for one working set but only {len(free)} free "
                        f"+ {len(cand)} evictable of {self.slot_capacity} "
                        f"({pinned} pinned) — raise slot_capacity or "
                        "shrink the micro-batch")
            if shortfall > 0:
                order = np.argsort(self._slot_tick[cand], kind="stable")
                self._evict(cand[order[:shortfall]])
                free = np.nonzero(self._slot_row < 0)[0]
        take = free[:need]
        ledger = get_transfers()
        t0 = time.perf_counter() if ledger is not None else 0.0
        self._load_slots(take, miss_rows)
        if ledger is not None:
            # logical bytes, never pow2-padded: need == misses+installs
            # on the demand path, == prefetched on the lookahead path,
            # so the per-site totals reconcile exactly with StoreStats
            ledger.note_transfer(
                "store.demand_fault" if demand else "store.prefetch",
                "h2d", need * self.rank * 4, time.perf_counter() - t0)
        if pin:
            self._slot_pin[take] += 1
        self._slot_dirty[take] = dirty
        if not demand:
            self.stats.prefetched += need
        return need

    # -- training seams --------------------------------------------------------

    def acquire_rows(self, ids: np.ndarray) -> np.ndarray:
        """Register ``ids``, fault their rows hot, PIN them, mark them
        dirty (training will write them), and return the device SLOT
        index per input id. The demand-fault wall (what async prefetch
        exists to hide) accrues to ``tier_prefetch_wait_s``."""
        ids = np.asarray(ids)
        with self._lock:
            n_before = self._n
            rows = super().ensure(ids)
            uniq = np.unique(rows)
            fresh = int((uniq >= n_before).sum())
            t0 = time.perf_counter()
            faulted = self._fault_in(uniq, pin=True, dirty=True,
                                     fresh=fresh)
            if faulted:
                wait = time.perf_counter() - t0
                self.stats.demand_fault_s += wait
                if self._obs_on:
                    self._m_wait.inc(wait)
            return self._row_slot[rows]

    def release_rows(self, rows: np.ndarray) -> None:
        """Unpin the slots ``acquire_rows`` returned (per-occurrence
        array accepted; one unpin per unique slot, mirroring the one
        pin per unique row)."""
        with self._lock:
            slots = np.unique(np.asarray(rows, np.int64))
            slots = slots[(slots >= 0) & (slots < self.slot_capacity)]
            self._slot_pin[slots] = np.maximum(
                self._slot_pin[slots] - 1, 0)

    def commit_rows(self, updated, idx) -> None:
        # scatter into the CURRENT pool binding under the store lock —
        # a whole-pool rebind would erase slots the prefetch thread
        # loaded between the trainer's snapshot and this commit
        with self._lock:
            self._pool = self._device_put(
                _commit_slots(self._pool, updated, jnp.asarray(idx)))

    def install_trained(self, updated, rows: np.ndarray) -> None:
        rows = np.unique(np.asarray(rows, np.int64))
        if rows.size == 0:
            return
        idx = np.full(_pow2_pad(len(rows)), rows[0], np.int64)
        idx[: len(rows)] = rows
        self.commit_rows(updated, idx)

    # -- prefetch --------------------------------------------------------------

    def prefetch(self, ids: np.ndarray) -> int:
        """Stage upcoming rows hot WITHOUT pinning or dirtying them —
        the async lookahead path (``StorePrefetcher`` feeds it from the
        WAL batches the feeder queue announces). Best-effort: a full
        pool of pinned slots loads what fits. Returns rows faulted.

        Unregistered ids are DROPPED, never registered: id→row
        assignment is first-seen order and belongs to the training
        path alone. A racing prefetcher that called ``ensure`` would
        permute the vocabulary relative to an untiered run (it sees
        batch N+1's ids while batch N trains), silently breaking the
        row-for-row bit-exactness contract — and a fresh id has no
        cold value to stage anyway, so skipping it costs nothing."""
        ids = np.asarray(ids)
        if ids.size == 0:
            return 0
        with self._lock:
            rows, found = super().rows_for(ids)
            rows = rows[found > 0]
            if rows.size == 0:
                return 0
            return self._fault_in(np.unique(rows), pin=False,
                                  dirty=False, best_effort=True,
                                  demand=False)

    def warm_rows(self, rows: np.ndarray) -> int:
        """Re-warm already-registered rows (checkpoint restore hands
        back the snapshot's resident set so a restart resumes with the
        hot tier it crashed with)."""
        rows = np.asarray(rows, np.int64)
        rows = rows[(rows >= 0) & (rows < self._n)]
        if rows.size == 0:
            return 0
        with self._lock:
            return self._fault_in(np.unique(rows), pin=False,
                                  dirty=False, best_effort=True,
                                  demand=False)

    def resident_rows(self) -> np.ndarray:
        """Cold rows currently hot (slot-index order) — the slot-map
        half of the checkpoint capture."""
        with self._lock:
            return self._slot_row[self._slot_row >= 0].copy()

    def dirty_rows(self) -> np.ndarray:
        with self._lock:
            sel = (self._slot_row >= 0) & self._slot_dirty
            return self._slot_row[sel].copy()

    # -- serving ---------------------------------------------------------------

    def serve_rows(self, rows: np.ndarray):
        """Device ``float32[len(rows), rank]`` of table rows for the
        serving gather: hot rows from the pool, cold rows straight from
        the host tier (counted as serve misses — their transfer wall
        lands inside the engine's flush and is therefore priced into
        the SLO tracker automatically). READ-ONLY: serving never admits
        rows to the pool, so it cannot thrash training's working set."""
        rows = np.asarray(rows, np.int64)
        n = len(rows)
        if n == 0:
            return jnp.zeros((0, self.rank), jnp.float32)
        with self._lock:
            slots = self._row_slot[rows]
            pool = self._pool  # immutable ref: consistent after release
            miss = slots < 0
            cold_vals = (np.array(self.cold[rows[miss]], np.float32)
                         if miss.any() else None)
            self.stats.serve_hits += int((~miss).sum())
            self.stats.serve_misses += int(miss.sum())
        p = _pow2_pad(n)
        sidx = np.zeros(p, np.int64)
        sidx[:n] = np.where(miss, 0, slots)
        # jnp.take (internally jitted) instead of eager pool[idx]: the
        # eager gather normalizes the index op-by-op, shipping a scalar
        # constant host->device per call, which an armed transfer guard
        # rightly flags
        out = jnp.take(pool, jnp.asarray(sidx), axis=0)
        if cold_vals is not None:
            ledger = get_transfers()
            t0 = time.perf_counter() if ledger is not None else 0.0
            midx = np.nonzero(miss)[0]
            m = len(midx)
            mp = _pow2_pad(m)
            mi = np.full(mp, midx[0], np.int64)
            mi[:m] = midx
            mv = np.zeros((mp, self.rank), np.float32)
            mv[:m] = cold_vals
            mv[m:] = cold_vals[0]
            out = _scatter_slots(out, jnp.asarray(mi), jnp.asarray(mv))
            if ledger is not None:  # logical bytes: m == serve_misses
                ledger.note_transfer("store.serve_cold", "h2d",
                                     m * self.rank * 4,
                                     time.perf_counter() - t0)
        return out[:n]

    # -- whole-table views (offline/eval + checkpoint) -------------------------

    def _merged_host(self, n: int) -> np.ndarray:
        """Cold[:n] with DIRTY resident slots overlaid (clean residents
        equal their cold rows by construction) — a genuine copy: the
        cold tier is mutable numpy, so the plain table's
        immutable-ref-can't-tear argument does not apply here."""
        out = np.array(self.cold[:n], np.float32, copy=True)
        sel = np.nonzero((self._slot_row >= 0) & self._slot_dirty)[0]
        if sel.size:
            rows = self._slot_row[sel]
            keep = rows < n
            if keep.any():
                out[rows[keep]] = self._gather_pool(sel[keep])
        return out

    def snapshot_rows(self, n: int):
        with self._lock:
            return self._merged_host(n)

    def load_rows(self, rows: np.ndarray, values: np.ndarray) -> None:
        rows = np.asarray(rows, np.int64)
        vals = np.asarray(values, np.float32)
        with self._lock:
            self.cold[rows] = vals
            slots = self._row_slot[rows]
            hot = slots >= 0
            if hot.any():
                hs = slots[hot]
                k = len(hs)
                p = _pow2_pad(k)
                si = np.full(p, hs[0], np.int64)
                si[:k] = hs
                sv = np.zeros((p, self.rank), np.float32)
                sv[:k] = vals[hot]
                sv[k:] = sv[0]
                self._pool = self._device_put(
                    _scatter_slots(self._pool, jnp.asarray(si),
                                   jnp.asarray(sv)))
                # restored slots now equal their cold rows again
                self._slot_dirty[hs] = False

    def full_table(self):
        with self._lock:
            return jnp.asarray(self._merged_host(self.capacity))

    def gather_rows(self, rows: np.ndarray) -> np.ndarray:
        rows = np.asarray(rows, np.int64)
        if rows.size == 0:
            return np.zeros((0, self.rank), np.float32)
        with self._lock:
            slots = self._row_slot[rows]
            out = np.array(self.cold[rows], np.float32)
            hot = np.nonzero(slots >= 0)[0]
            if hot.size:
                # pool values win for hot rows: dirty slots are ahead
                # of their cold copies
                out[hot] = self._gather_pool(slots[hot])
            return out

    def lookup(self, ids: np.ndarray) -> np.ndarray:
        rows, found = self.rows_for(ids)
        if not np.all(found > 0):
            missing = np.asarray(ids)[found == 0]
            raise KeyError(f"unregistered ids: {missing[:10].tolist()}")
        return self.gather_rows(rows)

    def as_dict(self) -> dict[int, np.ndarray]:
        with self._lock:
            host = self._merged_host(self._n)
            return {int(i): host[r]
                    for r, i in enumerate(
                        self._ids_buf[: self._n].tolist())}

    def factor_vectors(self, ids=None):
        from large_scale_recommendation_tpu.core.types import FactorVector

        if ids is None:
            ids = self._ids_buf[: self._n]
        ids = np.asarray(ids, dtype=np.int64)
        if ids.size == 0:
            return
        rows, found = self.rows_for(ids)
        if not np.all(found > 0):
            missing = ids[found == 0]
            raise KeyError(f"unregistered ids: {missing[:10].tolist()}")
        host = self.gather_rows(rows)
        for j, ident in enumerate(ids.tolist()):
            yield FactorVector(ident, host[j])

    # -- observability ---------------------------------------------------------

    def snapshot(self) -> dict:
        """The ``/storez`` body."""
        with self._lock:
            resident = int((self._slot_row >= 0).sum())
            return {
                "hot": {
                    "slot_capacity": int(self.slot_capacity),
                    "resident": resident,
                    "pinned": int((self._slot_pin > 0).sum()),
                    "dirty": int(self._slot_dirty.sum()),
                },
                "cold": {
                    "capacity": int(self.capacity),
                    "rows": int(self._n),
                    "host_bytes": int(self.stats.host_bytes),
                    "mmap": self._mmap_dir is not None,
                },
                "rank": int(self.rank),
                "stats": self.stats.snapshot(),
            }
