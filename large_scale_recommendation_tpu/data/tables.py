"""Growable device factor tables: dynamic vocabulary on static-shaped arrays.

The reference grows its factor maps implicitly everywhere with
``getOrElseUpdate(id, init)`` on JVM hash maps (reference:
ps/server/SimplePSLogic.scala:14, PSOfflineMF.scala:155,257,
FlinkOnlineMF.scala:92-93,129, OfflineSpark.scala:180-181). A device array
cannot grow — SURVEY §7 hard part (a). The TPU-native equivalent is:

- a dense ``float32[capacity, rank]`` device table,
- a host-side sorted id index (the only dynamic structure — fully
  vectorized binary search, no per-id Python anywhere),
- geometric capacity growth, so a stream of n distinct ids causes only
  O(log n) reallocations / recompilations of downstream jitted fns:
  doubling (powers of two) while a table is small, an eighth at a time
  and rounded to the sublane tile once it is large (``capacity_for``),
- new rows initialized from the pluggable ``FactorInitializer`` **by id**
  (so ``PseudoRandomFactorInitializer`` keeps its same-id-same-vector
  property across tables, devices and restarts),
- mutation in place: the live array is the table's own and never leaves
  it. An install, a load, a commit and a micro-batch update
  (``updating``) donate it, so each moves the rows it writes and nothing
  else (what lets 7.27 GB of rank-512 tables live on a 16 GB chip). A
  read that ends at once runs inside ``borrowed()``; whoever wants an
  array that outlives the next mutation asks for a copy by name
  (``array_copy()``, which ``.array`` is).
"""

from __future__ import annotations

import contextlib
import threading
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from large_scale_recommendation_tpu.core.initializers import FactorInitializer
from large_scale_recommendation_tpu.core.types import FactorVector
from large_scale_recommendation_tpu.utils.shapes import (
    next_pow2 as _next_pow2,
    pow2_pad as _pow2_pad,
)

# Up to this many bytes a table's capacity is a power of two (at most this
# much is ever wasted, and the compile family of everything jitted on the
# table's shape stays the closed set of powers of two). Past it a power of
# two can waste gigabytes (2,500,000 rows of rank 512 are 5.12 GB, the
# next power of two 8.59 GB), so capacity is the rows asked for, rounded
# to the float32 sublane tile, with an eighth of headroom when a table
# grows id by id. The same bound caps one install of fresh rows and one
# chunk of ``load_rows``, so a bulk registration or a restore of a large
# table stages at most this much beside the table.
STEP_BYTES = 64 << 20
TILE_ROWS = 8


def capacity_for(need: int, rank: int, current: int = 0) -> int:
    """Rows to allocate for ``need`` rows of ``rank`` float32, decided
    from the sizes alone: the next power of two while that table is at
    most ``STEP_BYTES``; otherwise ``need`` itself (or an eighth over
    ``current`` when that is more: growth by trickle stays O(log n)
    reallocations), rounded up to ``TILE_ROWS``."""
    pow2 = max(_next_pow2(need), TILE_ROWS)
    if pow2 * rank * 4 <= STEP_BYTES:
        return pow2
    want = max(need, current + (current >> 3))
    return -(-want // TILE_ROWS) * TILE_ROWS


def _step_rows(rank: int) -> int:
    """The most rows one install or one load chunk stages: the largest
    power of two whose rows fit ``STEP_BYTES`` (32,768 at rank 512)."""
    rows = max(TILE_ROWS, STEP_BYTES // (rank * 4))
    return 1 << (rows.bit_length() - 1)


@partial(jax.jit, donate_argnums=0)
def _install_rows(table: jax.Array, fresh: jax.Array,
                  base: jax.Array) -> jax.Array:
    """Write ``fresh`` into rows [base, base+len(fresh)).

    ``fresh`` is PADDED by the caller (pow2 with a capacity-scaled floor)
    so this compiles once per (capacity, pad) pair instead of once per
    distinct fresh-id count — the eager ``at[rows].set`` it replaces
    recompiled a new scatter for every micro-batch's unique-id count
    (measured: ~60% of online partial_fit wall was XLA compilation of
    these one-shot kernels). Rows beyond the real count receive
    initializer output for padding ids; they land in UNREGISTERED
    capacity rows (never read, and re-initialized properly if later
    registered), so the overwrite is harmless. The table is DONATED:
    the install writes its rows where they lie. The ingest API's
    documented polling pattern (``models/online.py`` partial_fit —
    snapshot ``table.array`` between micro-batches) holds copies, which
    no install can reach."""
    return jax.lax.dynamic_update_slice(table, fresh, (base, 0))


_load_rows = jax.jit(lambda table, rows, values: table.at[rows].set(values),
                     donate_argnums=0)


@partial(jax.jit, static_argnames="rows")
def _grown(table: jax.Array, rows: int) -> jax.Array:
    """``table`` with ``rows`` zero rows appended, as one program: the new
    table is the only allocation (a ``concatenate`` with a zeros array
    would hold that beside it)."""
    return jnp.pad(table, ((0, rows), (0, 0)))


# touched-rows commit for the concurrent-apply path (moved here from
# models/online.py so the tiered store can override the seam): ``idx``
# is pow2-padded with REPEATED OWN rows, so duplicate scatter entries
# carry duplicate values and order cannot matter. The live table is
# donated; the consumers compute on copies (``array_copy``)
_commit_rows = jax.jit(lambda cur, src, idx: cur.at[idx].set(src[idx]),
                       donate_argnums=0)


@partial(jax.jit, static_argnames="n")
def _first_rows(table: jax.Array, n: int) -> jax.Array:
    """Rows ``[0, n)`` as an array of their own (an eager ``table[:n]``
    with ``n`` the capacity IS the table)."""
    return jnp.copy(table[:n])


class GrowableFactorTable:
    """A factor matrix with ``getOrElseUpdate`` semantics on device.

    ≙ the PS server's ``HashMap[Int, P]`` shard with pull-side init
    (SimplePSLogic.scala:13-18) and the online operators' state maps
    (FlinkOnlineMF.scala:92-93,129). Row assignment is first-seen order,
    exactly as the sequential getOrElseUpdate would produce.
    """

    def __init__(
        self,
        initializer: FactorInitializer,
        capacity: int = 1024,
        device_put=None,
    ):
        self.initializer = initializer
        self.rank = initializer.rank
        self._sorted_cache: tuple[np.ndarray, np.ndarray] | None = None
        # (id -> row array or None, the ``_n`` it was built for): the
        # direct-address index of a dense id space (``_direct_index``)
        self._direct: tuple[np.ndarray | None, int] = (None, 0)
        self._device_put = device_put or (lambda x: x)
        self.capacity = capacity_for(capacity, self.rank)
        # registered ids in row order; row of _ids_buf[j] is j
        self._ids_buf = np.empty(self.capacity, np.int64)
        self._n = 0
        # held across every dispatch that reads or donates ``_array``: a
        # reader on another thread gets the array before an update takes
        # it, or the new one after, never a donated buffer
        self._guard = threading.RLock()
        self.array = self._make_array()  # the setter: a subclass's storage

    def _make_array(self):
        """Initial storage — subclass hook (HostFactorTable allocates on
        host instead of paying a device zeros round trip per table)."""
        return self._device_put(
            jnp.zeros((self.capacity, self.rank), jnp.float32))

    # -- the array: the table's own, read in place or copied by name --------

    def array_copy(self):
        """A copy of the live table, the caller's to keep: it stays valid
        whatever the table does next. Costs the table's bytes once, at
        the call."""
        with self._guard:
            return jnp.copy(self._array)

    @property
    def array(self):
        """``array_copy()``: the snapshot point of the polling pattern
        (``models/online.py``: read ``.array`` between micro-batches). A
        read that ends at once belongs inside ``borrowed()``."""
        return self.array_copy()

    @array.setter
    def array(self, value):
        """The table TAKES ``value``: the next mutation donates it, so the
        caller keeps no use of it."""
        with self._guard:
            self._array = value

    @contextlib.contextmanager
    def updating(self):
        """The live array for one update that ends in ``install_trained``,
        readers held off meanwhile. It is the update's to donate
        (``ops.sgd.online_train_inplace``)."""
        with self._guard:
            yield self._array

    @contextlib.contextmanager
    def borrowed(self):
        """The live array for a read that ends inside the block (a score,
        a gather, ``block_until_ready``): dispatch there and keep only the
        result. A reference kept past the block dies with the next
        mutation."""
        with self._guard:
            yield self._array

    def _rebind(self, fn, *args) -> None:
        """``_array = fn(_array, *args)``, ``fn`` donating the table."""
        with self._guard:
            self._array = self._device_put(fn(self._array, *args))

    @property
    def device_bytes(self) -> int:
        """Bytes the table holds on the device."""
        with self._guard:
            return int(self._array.nbytes)

    # -- vocabulary --------------------------------------------------------

    @property
    def num_rows(self) -> int:
        return self._n

    def __contains__(self, ident: int) -> bool:
        _, found = self.rows_for(np.asarray([ident]))
        return bool(found[0])

    def ensure(self, ids: np.ndarray) -> np.ndarray:
        """Register any unseen ids (initializing their rows) and return the
        row for every input id. ≙ ``getOrElseUpdate(id, init.nextFactor(id))``
        (SimplePSLogic.scala:14), batched.

        Fully vectorized (bulk binary search + np.unique): a per-id Python
        loop is fine at test scale but a bottleneck at ML-25M batch sizes
        (round-1 weak spot #6); 1M fresh ids must register in well under a
        second."""
        ids = np.asarray(ids).astype(np.int64)
        rows, found_f = self.rows_for(ids)
        known = found_f > 0
        if known.all():
            return rows
        new_mask = ~known
        # dense rows for the unseen ids, in first-seen order (matching the
        # sequential getOrElseUpdate semantics id-for-id)
        stream = ids[new_mask]
        uniq, first_idx, inv = np.unique(stream, return_index=True,
                                         return_inverse=True)
        order = np.argsort(first_idx, kind="stable")
        rank_of = np.empty(len(uniq), dtype=np.int64)
        rank_of[order] = np.arange(len(uniq))
        base = self._n
        rows[new_mask] = base + rank_of[inv]

        m = len(uniq)
        step = _step_rows(self.rank)
        if m > step:
            return self._ensure_bulk(rows, new_mask, uniq, order, rank_of,
                                     inv, step)
        # pow2-pad the install so downstream shapes repeat (see
        # _install_rows); the pad rows land in unregistered capacity.
        # The capacity-scaled FLOOR pins the steady-state install to ONE
        # shape: a long stream's fresh-id counts decay through every pow2
        # (8192, 4096, ... 8), and without the floor each size compiles
        # its own installer+initializer pair — measured as the dominant
        # cost of the online ingest loop even after warm-up. Small tables
        # (PS shards) keep a small floor so 1-id registrations stay cheap.
        # floor from the POST-grow capacity: a growth event must land on
        # the new capacity's steady-state install shape, not compile a
        # one-off for the stale smaller floor. The cap bounds wasted init
        # work on huge tables; 64K was 1K until round 5 — a 512K-vocab
        # online stream's fresh counts decay 67K→13K across its first
        # ten micro-batches, and every bucket crossed above the old floor
        # compiled a fresh ~0.5 s installer MID-STREAM (measured: the
        # whole online p99 tail, docs/PERF.md "Online latency tail").
        # Initializing 64K spare rows costs single-digit ms per batch.
        floor = self._install_floor(step)
        pad = _pow2_pad(m, floor)
        if base + pad > self.capacity:
            if base + m == self.capacity:
                # exact fill: one one-off install shape beats doubling a
                # table that is now FULL at this capacity (a bounded
                # vocab sized to a pow2 never grows for padding headroom
                # alone; any LATER fresh id grows for real need)
                pad = m
            else:
                # partial boundary install: GROW rather than clamp. The
                # pre-round-5 `pad = capacity - base` clamp handed every
                # install in the last floor-sized stretch of a capacity
                # level a UNIQUE shape — one fresh ~0.5 s compile per
                # install exactly where the floor was supposed to
                # prevent them. Growing ≤1/8 early costs some memory
                # headroom; the shape set stays closed. (At most two
                # rounds: the floor is capped, so the pad converges.)
                while base + pad > self.capacity:
                    self._grow(base + pad)
                    floor = self._install_floor(step)
                    pad = _pow2_pad(m, floor)
        self._register(uniq, order, rank_of, base)
        self._init_rows(base, m, pad)
        return rows

    def _install_floor(self, step: int) -> int:
        """The steady-state install: an eighth of the capacity, as a power
        of two (a capacity past ``STEP_BYTES`` is none itself), at most
        65,536 rows and at most one ``step``."""
        eighth = max(8, self.capacity >> 3)
        return min(65536, step, 1 << (eighth.bit_length() - 1))

    def _ensure_bulk(self, rows, new_mask, uniq, order, rank_of, inv,
                     step: int) -> np.ndarray:
        """More fresh ids in one call than one install may stage (a
        restore, a bulk registration): grow once to the rows needed, then
        install ``step`` rows at a time, so what is staged beside the
        table stays ``STEP_BYTES`` however many ids arrive (the pow2 pad
        of 2,500,000 ids of rank 512 would be 8.59 GB of initializer
        output). Same rows, same values as the one-install path."""
        base, m = self._n, len(uniq)
        rows[new_mask] = base + rank_of[inv]
        if base + m > self.capacity:
            self._grow(base + m)
        self._register(uniq, order, rank_of, base)
        for start in range(0, m, step):
            real = min(step, m - start)
            # whole steps where they fit (one install shape); the tail,
            # or a table filled to its last row, installs exactly
            pad = step if base + start + step <= self.capacity else real
            self._init_rows(base + start, real, pad)
        return rows

    def _register(self, uniq, order, rank_of, base: int) -> None:
        """Book ``uniq`` (value-sorted; ``order``: first-seen) as rows
        ``base + rank_of``."""
        m = len(uniq)
        self._ids_buf[base:base + m] = uniq[order]
        self._n = base + m
        table, n = self._direct
        if (table is not None and n == base and uniq[0] >= 0
                and uniq[-1] < table.size):
            table[uniq] = base + rank_of
            self._direct = (table, self._n)
        if self._sorted_cache is not None:
            # Merge the m new ids (already value-sorted in ``uniq``) into
            # the existing sorted index: O(n + m), not a full O(n log n)
            # re-sort — an online stream calls ensure() per micro-batch and
            # must not re-sort the whole table each time.
            s_ids, s_rows = self._sorted_cache
            pos = np.searchsorted(s_ids, uniq)
            self._sorted_cache = (
                np.insert(s_ids, pos, uniq),
                np.insert(s_rows, pos, base + rank_of),
            )

    def _init_rows(self, base: int, real: int, pad: int) -> None:
        """Initialize registered rows ``[base, base + real)`` by id, as
        one install of ``pad`` rows."""
        # pad with a REPEATED REAL id, not a fabricated 0: a
        # domain-sensitive FunctionFactorInitializer (pretrained lookups,
        # id validation) must only ever see ids the caller registered
        ids_pad = np.full(pad, self._ids_buf[base + real - 1], np.int64)
        ids_pad[:real] = self._ids_buf[base:base + real]
        fresh = self.initializer(jnp.asarray(ids_pad, dtype=jnp.int32))
        self._install(fresh, base)

    def _install(self, fresh, base: int) -> None:
        self._rebind(_install_rows, fresh, np.int32(base))

    def rows_for(self, ids: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Look up rows WITHOUT registering; unknown ids → row 0, mask 0
        (read-only form, for predict on a live model).

        One read an id where the registered ids are dense
        (``_direct_index``), else a vectorized binary search over a
        lazily-rebuilt sorted index — predict/eval call this on full
        evaluation sets (same rationale as ``IdIndex.rows_for``)."""
        ids = np.asarray(ids).astype(np.int64)
        direct = self._direct_index()
        if direct is not None:
            inside = (ids >= 0) & (ids < direct.size)
            rows = direct[np.where(inside, ids, 0)].astype(np.int64)
            found = inside & (rows >= 0)
            return np.where(found, rows, 0), found.astype(np.float32)
        sorted_ids, sorted_rows = self._sorted_index()
        if sorted_ids.size == 0:
            return np.zeros(len(ids), np.int64), np.zeros(len(ids), np.float32)
        pos = np.searchsorted(sorted_ids, ids)
        pos = np.clip(pos, 0, sorted_ids.size - 1)
        found = sorted_ids[pos] == ids
        rows = np.where(found, sorted_rows[pos], 0)
        return rows, found.astype(np.float32)

    def _direct_index(self) -> np.ndarray | None:
        """``row_of[id]`` (-1: not registered) where the registered ids are
        dense: non-negative and under twice their count. The table is the
        next power of two (room for the ids to come), so at most four
        int32 slots an id: the 16 B an id that the sorted index (two int64
        arrays) takes. One read an id in the place of a binary search: 65,536 lookups in arrival
        order into 2.5M ids take 0.3 ms against 30 (my chip runs, PR 35:
        ``fit/online/prepare`` 40.06 -> 3.96 ms a micro-batch, ``PERF.md``
        Findings). ``None`` for a sparse id space, which keeps the sorted
        index. Kept up to date by ``_register`` while fresh ids fit;
        rebuilt here (O(n)) when they did not."""
        table, n = self._direct
        if n == self._n:
            return table
        ids = self._ids_buf[:self._n]
        lo, hi = int(ids.min()), int(ids.max())
        table = None
        if lo >= 0 and hi < 2 * self._n:
            table = np.full(_next_pow2(hi + 1), -1, np.int32)
            table[ids] = np.arange(self._n, dtype=np.int32)
        self._direct = (table, self._n)
        return table

    def _sorted_index(self) -> tuple[np.ndarray, np.ndarray]:
        if self._sorted_cache is None or self._sorted_cache[0].size != self._n:
            all_ids = self._ids_buf[:self._n]
            order = np.argsort(all_ids).astype(np.int64)
            self._sorted_cache = (all_ids[order], order)
        return self._sorted_cache

    def id_array(self) -> np.ndarray:
        """Registered ids in row order (int64 copy) — the array form of
        ``ids()``; row j holds ``id_array()[j]``."""
        return self._ids_buf[:self._n].copy()

    def sorted_index(self) -> tuple[np.ndarray, np.ndarray]:
        """The (sorted_ids, sorted_rows) pair, from the incrementally
        maintained cache — snapshot consumers (``OnlineMF.to_model``)
        reuse it instead of re-sorting the vocabulary."""
        return self._sorted_index()

    def _grow(self, need: int) -> None:
        new_cap = capacity_for(need, self.rank, self.capacity)
        self._rebind(partial(_grown, rows=new_cap - self.capacity))
        ids_buf = np.empty(new_cap, np.int64)
        ids_buf[:self._n] = self._ids_buf[:self._n]
        self._ids_buf = ids_buf
        self.capacity = new_cap

    # -- access ------------------------------------------------------------

    def lookup(self, ids: np.ndarray) -> np.ndarray:
        """Factor vectors for ids (must be registered)."""
        rows, found = self.rows_for(ids)
        if not np.all(found > 0):
            missing = np.asarray(ids)[found == 0]
            raise KeyError(f"unregistered ids: {missing[:10].tolist()}")
        return np.asarray(self._take(jnp.asarray(rows)))

    def _take(self, idx):
        """``_array[idx]``, dispatched under the guard (a donating update
        on another thread must not take the buffer mid-read); the result
        is a new array."""
        with self._guard:
            return self._array[idx]

    def factor_vectors(self, ids=None):
        """Iterate ``FactorVector`` updates for ``ids`` (default: all).

        ≙ the updates-only output stream (``UpdateSeparatedHashMap.updates``,
        OfflineSpark.scala:33-67) / PS output ``(id, newValue)``
        (SimplePSLogic.scala:20-24).

        Only the requested rows are gathered off the device — per-batch
        updates-only output must not scale with table capacity."""
        if ids is None:
            ids = self._ids_buf[:self._n]
        ids = np.asarray(ids, dtype=np.int64)
        if ids.size == 0:
            return
        rows, found = self.rows_for(ids)
        if not np.all(found > 0):
            missing = ids[found == 0]
            raise KeyError(f"unregistered ids: {missing[:10].tolist()}")
        host = np.asarray(self._take(jnp.asarray(rows, dtype=jnp.int32)))
        for j, ident in enumerate(ids.tolist()):
            yield FactorVector(ident, host[j])

    def as_dict(self) -> dict[int, np.ndarray]:
        """Full model export as id → vector (host)."""
        with self._guard:
            host = np.asarray(self._array)
        return {int(i): host[r]
                for r, i in enumerate(self._ids_buf[:self._n].tolist())}

    def ids(self) -> list[int]:
        return self._ids_buf[:self._n].tolist()

    # -- tiering hooks -----------------------------------------------------
    # The seams ``store.tiered.TieredFactorStore`` overrides. On a plain
    # table every default is the existing behavior verbatim (acquire IS
    # ensure, release is free, snapshot is the zero-copy ref slice), so
    # the untiered paths stay byte-identical — the tiered bit-exactness
    # invariant is pinned against exactly these defaults.

    def acquire_rows(self, ids: np.ndarray) -> np.ndarray:
        """Register ``ids`` and return the rows TRAINING should index —
        table rows here; device SLOT indices on a tiered store (which
        also faults the rows hot and pins them until ``release_rows``)."""
        return self.ensure(ids)

    def release_rows(self, rows: np.ndarray) -> None:
        """Drop the eviction pins ``acquire_rows`` took (no-op here —
        a plain table has nothing to evict)."""

    def gather_rows(self, rows: np.ndarray) -> np.ndarray:
        """Host float32 values of ``rows`` — one pow2-padded device
        gather (the delta-shipping idiom ``StreamingDriver`` uses). A
        tiered store merges hot slots and cold rows instead."""
        n = len(rows)
        if n == 0:
            return np.zeros((0, self.rank), np.float32)
        idx = np.zeros(_pow2_pad(n), np.int64)
        idx[:n] = rows
        return np.asarray(self._take(jnp.asarray(idx)))[:n]

    def commit_rows(self, updated, idx) -> None:
        """Concurrent-apply commit: scatter ``updated``'s rows at
        ``idx`` (pow2-padded, repeated-own-row pads) into the live
        table, in place: the other consumers compute on copies. A tiered
        store takes its lock so a racing prefetch load is never erased by
        the rebind."""
        self._rebind(_commit_rows, updated, jnp.asarray(idx))

    def install_trained(self, updated, rows: np.ndarray) -> None:
        """Serial-path install of a trained table, inside ``updating``.
        Plain table: ``updated`` IS the new table (the array ``updating``
        yielded, donated to the update, with the batch's rows rewritten
        where they lie). A tiered store scatters only ``rows`` into the
        current pool instead."""
        with self._guard:
            self._array = updated

    def snapshot_rows(self, n: int):
        """The first ``n`` rows for a checkpoint capture, as an array of
        their own (the live one is donated to the next update); a tiered
        store copies under its lock (the cold tier is mutable numpy)."""
        with self._guard:
            return _first_rows(self._array, n=n)

    def load_rows(self, rows: np.ndarray, values: np.ndarray) -> None:
        """Write restored factor rows (checkpoint restore path), at
        most ``STEP_BYTES`` of them staged at a time."""
        rows = np.asarray(rows)
        step = _step_rows(self.rank)
        for a in range(0, len(rows), step):
            self._rebind(_load_rows, jnp.asarray(rows[a:a + step]),
                         jnp.asarray(values[a:a + step]))

    def full_table(self):
        """The whole table as one array — offline/eval consumers only
        (``predict``/``to_model``). A copy of a plain table
        (``array_copy``); a tiered store materializes the hot∪cold
        merge."""
        return self.array_copy()


class HostFactorTable(GrowableFactorTable):
    """Host-resident twin of ``GrowableFactorTable`` — numpy storage, same
    getOrElseUpdate semantics and id machinery.

    For BOOKKEEPING-ONLY consumers: the PS server shards do nothing but
    gather rows on pull and add deltas on push (SimplePSLogic.scala:13-24
    — a JVM hash map in the reference). No matmul ever touches the server
    table, so device residency bought nothing and cost two device round
    trips per request — ruinous for the online path's one-rating pulls
    (measured: ~10 eager dispatches per rating, docs/PERF.md). Worker
    COMPUTE tables stay on device; this is the parameter shard only.
    """

    def _make_array(self):
        return np.zeros((self.capacity, self.rank), np.float32)

    @property
    def array(self):
        """The live numpy storage itself: pushes mutate it in place."""
        return self._array

    @array.setter
    def array(self, value):
        self._array = value

    def array_copy(self):
        return self._array.copy()

    def snapshot_rows(self, n: int):
        return self._array[:n].copy()

    def as_dict(self) -> dict[int, np.ndarray]:
        """Copies, not views: numpy indexing into the live table would
        hand out aliases that later pushes mutate in place (the device
        base class copies implicitly on the device→host transfer)."""
        host = self.array
        return {int(i): host[r].copy()
                for r, i in enumerate(self._ids_buf[:self._n].tolist())}

    def _install(self, fresh, base: int) -> None:
        f = np.asarray(fresh, dtype=np.float32)
        self.array[base:base + len(f)] = f

    def _grow(self, need: int) -> None:
        new_cap = _next_pow2(need)
        arr = np.zeros((new_cap, self.rank), np.float32)
        arr[: self.capacity] = self.array
        self.array = arr
        ids_buf = np.empty(new_cap, np.int64)
        ids_buf[: self._n] = self._ids_buf[: self._n]
        self._ids_buf = ids_buf
        self.capacity = new_cap

    def gather_rows(self, rows: np.ndarray) -> np.ndarray:
        # host storage: plain numpy fancy-indexing, no device round trip
        return np.asarray(self.array[np.asarray(rows, np.int64)],
                          np.float32)

    def commit_rows(self, updated, idx) -> None:
        idx = np.asarray(idx, np.int64)
        self.array[idx] = np.asarray(updated, np.float32)[idx]

    def load_rows(self, rows: np.ndarray, values: np.ndarray) -> None:
        self.array[np.asarray(rows, np.int64)] = np.asarray(
            values, np.float32)
