"""On-device workload generation + DSGD blocking (the XLA data pipeline).

TPU-first counterpart of the host blocking pass (``data.blocking``).
Blocking is a pure data-layout transform, and on the chip it is built from
the operations that stream: multi-operand sorts (the payload moves with the
key), cumulative and segmented scans, and contiguous slice copies. An index
vector applied one element at a time (``x[order]``, ``take_along_axis``,
``.at[dest].set``) is not among them: measured on a TPU v5e, a gather or a
scatter over the 96.5M-entry layout costs 22-27 ns an ELEMENT whatever the
element's size (2.2-2.6 s a pass, where a streaming pass over the same 386
MB is a millisecond), and the pass once held eighteen of them (PERF.md,
PR 27). Keeping the whole pipeline on device means the host never
materializes the ``k × k × bmax`` stratum expansion at all:

- synthetic benchmarks (``synthetic_like_device``) move only scalars and a
  256-byte size vector across the host↔device link — the difference between
  kilobytes and the ~600 MB the host pipeline ships for the ML-25M-shaped
  north-star config (BASELINE.md), which matters at pod scale where
  per-host PCIe is shared;
- real datasets ship the raw COO triple (id, id, value) once, ~3× smaller
  than the padded stratum layout + collision scales, which are built on
  chip.

On the stratum ring (``mesh_block_problem``, what ``MeshDSGD.fit_device``
runs) the same pass is split over the chips: each chip counts, places in
the seeded shuffle and sorts only its own share of the entries, and one
``all_to_all`` hands every entry to the chip that owns its user block,
which lays out its own row of the device-major layout. The result is the
one-chip layout bit for bit; no chip holds more than its share of the
entries or of the layout, so the ring blocks shapes no one chip can.

Scope: dense, pre-compacted ids in ``[0, num_users) × [0, num_items)`` —
the contract of production feature pipelines and of the synthetic
generators. Arbitrary external ids go through the host path
(``data.blocking``), which also produces the reference-shaped ``IdIndex``.

Reference seams mirrored (same capabilities, device-resident):
- id → block/row assignment with balanced blocks and omega counts
  ≙ ``initFactorBlockAndIndices`` (DSGDforMF.scala:513-588, :537-541);
- stratum-major rating blocks, diagonal-rotation schedule pre-baked
  ≙ rating-block construction + ``nextRatingBlock``
  (DSGDforMF.scala:301-333, :562, :611-619);
- truncated-exponential skewed id draws ≙ ``nextExpDiscrete``
  (RandomGenerator.scala:36-50) — by exact inverse CDF on the truncated
  support instead of the reference's rejection recursion (loop-free, so it
  jits);
- planted-low-rank synthetic ratings ≙ ``core.generators
  .SyntheticMFGenerator`` (the oracle workload; no reference analogue —
  the reference has no tests or benchmarks, SURVEY §4/§6).

The layouts produced here satisfy the same invariants as the host pass
(disjoint strata, balanced blocks, weight-0 padding, per-minibatch
collision scales) but are not bit-identical to it — both are seeded and
deterministic, they just draw their permutations from different RNGs.
"""

from __future__ import annotations

import dataclasses
import functools
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.extend.random import threefry2x32_p

from large_scale_recommendation_tpu.obs.trace import get_tracer

# --------------------------------------------------------------------------
# Synthetic generation (device)
# --------------------------------------------------------------------------


def truncated_exp_ids(key: jax.Array, lam: float, n_ids: int,
                      size: int) -> jax.Array:
    """Skewed id draw: discretized exponential truncated to [0, n_ids).

    ≙ ``nextExpDiscrete`` (RandomGenerator.scala:36-50). The reference
    rejection-samples the overshoot tail; here the uniform is mapped through
    the exact truncated inverse CDF (u' = u·(1−e^{−λ})), which is loop-free
    and therefore jittable. Low ids are hot.
    """
    u = jax.random.uniform(key, (size,), dtype=jnp.float32)
    u = u * (1.0 - np.exp(-lam))
    v = jnp.floor(-jnp.log1p(-u) / lam * n_ids).astype(jnp.int32)
    return jnp.minimum(v, n_ids - 1)


@partial(jax.jit, static_argnames=("num_users", "num_items", "rank", "n",
                                   "noise", "skew_lam"))
def _planted_batch(key, factor_key, num_users: int, num_items: int,
                   rank: int, n: int, noise: float,
                   skew_lam: float | None):
    """One batch of planted-low-rank ratings, all on device.

    ``factor_key`` seeds the ground-truth factors (shared across batches of
    one workload); ``key`` seeds this batch's id/noise draws.
    """
    ku, kv = jax.random.split(factor_key)
    scale = 1.0 / np.sqrt(rank)
    Ut = scale * jax.random.normal(ku, (num_users, rank), jnp.float32)
    Vt = scale * jax.random.normal(kv, (num_items, rank), jnp.float32)
    k1, k2, k3 = jax.random.split(key, 3)
    if skew_lam is not None:
        u = truncated_exp_ids(k1, skew_lam, num_users, n)
        i = truncated_exp_ids(k2, skew_lam, num_items, n)
    else:
        u = jax.random.randint(k1, (n,), 0, num_users, jnp.int32)
        i = jax.random.randint(k2, (n,), 0, num_items, jnp.int32)
    r = _planted_scores(Ut, Vt, u, i)
    r = r + noise * jax.random.normal(k3, (n,), jnp.float32)
    return u, i, r


# ML-25M-shaped nnz at rank 128 would materialize two [23.7M, 128] f32
# gather temps (2 × 11.3 GB — measured on-chip OOM against v5e's 15.75 GB
# HBM, r5). Chunking the row-wise dot through lax.map keeps the transient
# footprint at 2 × [chunk, rank] regardless of nnz.
_SCORE_CHUNK = 1 << 20


def _planted_scores(Ut, Vt, u, i, chunk: int = _SCORE_CHUNK):
    """Row-wise ⟨Ut[u], Vt[i]⟩ in bounded-memory chunks."""
    n = u.shape[0]
    if n <= chunk:
        return jnp.einsum("nk,nk->n", Ut[u], Vt[i])
    nc = -(-n // chunk)
    pad = nc * chunk - n
    up = jnp.concatenate([u, jnp.zeros((pad,), u.dtype)]) if pad else u
    ip = jnp.concatenate([i, jnp.zeros((pad,), i.dtype)]) if pad else i
    r = jax.lax.map(
        lambda ui: jnp.einsum("nk,nk->n", Ut[ui[0]], Vt[ui[1]]),
        (up.reshape(nc, chunk), ip.reshape(nc, chunk)))
    return r.reshape(-1)[:n]


from large_scale_recommendation_tpu.data.movielens import _SHAPES  # noqa: E402


def synthetic_like_device(
    name: str,
    nnz: int | None = None,
    rank: int = 16,
    noise: float = 0.3,
    seed: int = 0,
    skew_lam: float | None = 2.0,
    num_users: int | None = None,
    num_items: int | None = None,
):
    """Device-resident ``synthetic_like``: planted-low-rank train/holdout
    batches with the named dataset's shape statistics.

    Returns ``((u, i, r), (hu, hi, hr), (num_users, num_items))`` — all six
    arrays live on device; nothing but the PRNG key crosses the link.
    Same 95/5 split-by-volume contract as ``data.movielens.synthetic_like``.

    ``num_users``/``num_items`` override the named shape — for reduced runs
    that must shrink the VOCAB along with nnz so obs/row stays in the
    recoverable regime (≥ ~100 per docs/PERF.md; below it the planted
    structure is unlearnable by any solver and RMSE curves are noise).
    """
    if name not in _SHAPES:
        raise KeyError(f"unknown dataset {name!r}; have {sorted(_SHAPES)}")
    nu, ni, n_default = _SHAPES[name]
    nu = int(num_users) if num_users is not None else nu
    ni = int(num_items) if num_items is not None else ni
    n = int(nnz if nnz is not None else n_default)
    n_train = int(n * 0.95)
    base = jax.random.PRNGKey(seed)
    fkey = jax.random.fold_in(base, 0)
    train = _planted_batch(jax.random.fold_in(base, 1), fkey, nu, ni,
                           rank, n_train, noise, skew_lam)
    hold = _planted_batch(jax.random.fold_in(base, 2), fkey, nu, ni,
                          rank, n - n_train, noise, skew_lam)
    return train, hold, (nu, ni)


# --------------------------------------------------------------------------
# Blocking (device)
# --------------------------------------------------------------------------


@dataclasses.dataclass
class DeviceBlockedProblem:
    """Stratum-major blocked problem, fully device-resident.

    Same layout contract as ``blocking.BlockedProblem`` flattened to the
    arrays the kernels consume (``ops.sgd.dsgd_train`` signature): entry
    ``[s, p, :]`` is rating block ``(p, (p+s) mod k)``.
    """

    su: jax.Array  # int32[k, k, bmax] global user rows
    si: jax.Array  # int32[k, k, bmax] global item rows
    sv: jax.Array  # float32[k, k, bmax]
    sw: jax.Array  # float32[k, k, bmax] 1=real 0=pad
    icu: jax.Array  # float32[k, k, bmax] 1/minibatch-occurrence (user side)
    icv: jax.Array  # float32[k, k, bmax] (item side)
    omega_u: jax.Array  # float32[num_user_rows] occurrence counts
    omega_v: jax.Array  # float32[num_item_rows]
    row_of_user: jax.Array  # int32[num_users] dense id → global row
    row_of_item: jax.Array  # int32[num_items]
    id_of_user_row: jax.Array  # int32[num_user_rows]; 0 on padding rows
    id_of_item_row: jax.Array  # int32[num_item_rows]
    num_blocks: int
    rows_per_block_u: int
    rows_per_block_v: int
    nnz: int
    max_pad_ratio: float
    # the minibatch size icu/icv were baked for: "mean"-collision training
    # MUST pass this same value as dsgd_train's ``minibatch`` (the scales
    # are 1/occurrence within THESE chunks; a different kernel minibatch
    # silently mis-scales colliding rows)
    minibatch: int

    def to_id_indices(self):
        """Reference-shaped ``blocking.IdIndex`` pair for this layout.

        Bridges the device pipeline into the standard ``MFModel`` surface
        (predict / empirical_risk / factors export). Pulls only the two
        id→row maps and omegas to host — a few hundred KB, once per fit.
        """
        from large_scale_recommendation_tpu.data.blocking import IdIndex

        def side(row_of, omega, rpb):
            rows = np.asarray(row_of).astype(np.int64)
            om = np.asarray(omega)
            # host-path semantics: only ids SEEN in training are known to
            # the index (unseen ids score 0 in predict, are dropped from
            # risk) — dense-vocab ids with zero occurrences stay unknown
            all_ids = np.arange(rows.shape[0], dtype=np.int64)
            present = om[rows] > 0
            ids = np.full(om.shape[0], -1, np.int64)
            ids[rows[present]] = all_ids[present]
            return IdIndex(
                ids=ids, num_blocks=self.num_blocks, rows_per_block=rpb,
                omega=om, sorted_ids=all_ids[present],
                sorted_rows=rows[present],
            )

        return (side(self.row_of_user, self.omega_u, self.rows_per_block_u),
                side(self.row_of_item, self.omega_v, self.rows_per_block_v))

    def holdout_rows(self, hu: jax.Array, hi: jax.Array):
        """Map holdout ids to rows with a seen-in-training mask.

        Host-path semantics (``IdIndex.rows_for``): ids absent from training
        are masked out of evaluation.
        """
        ur = self.row_of_user[hu]
        ir = self.row_of_item[hi]
        mask = ((self.omega_u[ur] > 0) & (self.omega_v[ir] > 0)).astype(
            jnp.float32)
        return ur, ir, mask


def validate_dense_ids(u, i, num_users: int, num_items: int,
                       ctx: str) -> None:
    """Fail fast on out-of-range ids, BEFORE any int32 cast — an int64 host
    array with a wild id would otherwise wrap around the cast and pass a
    post-cast range check as a plausible small id. Shared by every dense-id
    device entry point (device blocking, DSGD/ALS fit_device).

    Host arrays reduce on host in their NATIVE dtype (free, and immune to
    the int64→int32 wrap this check exists to catch); when BOTH sides are
    already device arrays, their four min/max reductions fuse into one
    jitted call, so the check costs exactly ONE device→host sync. A host
    array is never shipped to device here.

    The fused reduction specializes per input length — an accepted
    per-fit cost (ADVICE r4): both callers are once-per-fit entry points
    (``device_block_problem``, ``ALS.fit_device``), never per-batch, and
    bucketing cannot help a device-resident input (the pad op itself
    would specialize on the unpadded length). Per-batch id paths (online
    ingest, PS pulls) pass host arrays, which reduce on host for free."""
    if isinstance(u, jax.Array) and isinstance(i, jax.Array):
        ranges = np.asarray(_id_ranges(u, i))
        lo_u, hi_u, lo_i, hi_i = (int(x) for x in ranges)
    else:
        def rng(a):
            if isinstance(a, jax.Array):
                mm = np.asarray(_minmax(a))  # one sync for this side
                return int(mm[0]), int(mm[1])
            a = np.asarray(a)
            return int(a.min()), int(a.max())

        lo_u, hi_u = rng(u)
        lo_i, hi_i = rng(i)
    if lo_u < 0 or hi_u >= num_users or lo_i < 0 or hi_i >= num_items:
        raise ValueError(
            f"{ctx} needs dense ids in [0, num_users) × [0, num_items); "
            f"got user range [{lo_u}, {hi_u}] vs {num_users}, item range "
            f"[{lo_i}, {hi_i}] vs {num_items}. Arbitrary external ids go "
            "through the host path (data.blocking).")


@jax.jit
def _id_ranges(u, i):
    """min/max of both id vectors in one device array → one host readback."""
    return jnp.stack([u.min(), u.max(), i.min(), i.max()])


@jax.jit
def _minmax(a):
    return jnp.stack([a.min(), a.max()])


def rows_per_block(n_ids: int, num_blocks: int, row_multiple: int = 8) -> int:
    """The per-block row count for a dense vocab dealt over ``num_blocks``
    (padded up for TPU-friendly shard shapes) — shared by the single-device
    and the multi-host (``parallel.distributed``) blocking paths."""
    rpb = max(-(-n_ids // num_blocks), 1)
    return -(-rpb // row_multiple) * row_multiple


@partial(jax.jit, static_argnames=("num_users", "num_items"))
def _weighted_counts(u, i, w, num_users: int, num_items: int):
    """Exact per-id occurrence counts; a weight-0 entry is padding and
    counts as 0. int32 accumulation — float32 scatter-add would silently
    stall at 2^24 occurrences on hot ids."""
    real = (w > 0).astype(jnp.int32)
    cu = jnp.zeros(num_users, jnp.int32).at[u].add(real)
    cv = jnp.zeros(num_items, jnp.int32).at[i].add(real)
    return cu, cv


@partial(jax.jit, static_argnames=("k", "rpb", "num_rows"))
def _assign_rows(key, counts: jax.Array, k: int, rpb: int, num_rows: int):
    # counts: exact int occurrences — the serpentine deal needs their
    # ORDER; omegas inherit the values (cast to float).
    """Balanced block/row assignment for one side.

    ≙ ``build_id_index``'s serpentine deal (data/blocking.py): seeded random
    tiebreak, hottest ids dealt first in alternating direction so per-block
    nnz stays near-equal on power-law data (the load-balancing the
    reference's ``ExponentialRatingGen`` stresses, RandomGenerator.scala:20-26).
    """
    n_ids = counts.shape[0]
    # random permutation first, then a STABLE sort by descending count —
    # equal-count ties land in random order without needing 64-bit
    # composite keys (int64 is emulated on TPU and off by default in jax)
    perm = jax.random.permutation(key, n_ids)
    order = perm[jnp.argsort(-counts[perm], stable=True)]
    ar = jnp.arange(n_ids, dtype=jnp.int32)
    rnd, pos = ar // k, ar % k
    block = jnp.where(rnd % 2 == 0, pos, k - 1 - pos)
    rows_sorted = block * rpb + rnd
    row_of_id = jnp.zeros(n_ids, jnp.int32).at[order].set(
        rows_sorted, unique_indices=True)
    omega = jnp.zeros(num_rows, jnp.float32).at[row_of_id].set(
        counts.astype(jnp.float32), unique_indices=True)
    id_of_row = jnp.zeros(num_rows, jnp.int32).at[row_of_id].set(
        ar, unique_indices=True)
    return row_of_id, omega, id_of_row


def lane_view(table: jax.Array) -> jax.Array:
    """A 1-D per-row table as rows of 128 lanes: zero-padded to a whole
    row and reshaped to ``[ceil(h/128), 128]``, built once before the
    lookups that read it (``take_lane``)."""
    return jnp.pad(table, (0, -table.shape[0] % 128)).reshape(-1, 128)


def take_lane(view: jax.Array, rows: jax.Array) -> jax.Array:
    """``table[rows]`` from ``view = lane_view(table)``, rows ``>= 0``:
    the same bits, read a 128-lane row at a time.

    On the TPU a gather whose slice is one 4-byte element costs about
    7 ns an index, one whose slice is a 128-lane row about 1.5 ns (PERF.md,
    Findings, PR 39). So each index gathers its whole row and a select
    keeps its lane: a sum of one value and 127 zeros is that value. Not a
    one-hot ``dot``, which the TPU's default precision rounds to bfloat16.
    """
    lane = jax.lax.broadcasted_iota(rows.dtype, (1, 128), 1)
    picked = view[rows >> 7]  # row rows // 128, lane rows % 128
    return jnp.where(lane == (rows & 127)[:, None], picked, 0).sum(-1)


# the ids ``_lookup_rows`` reads at a time: each gathers its whole
# 128-lane row, so a chunk holds 128 words an id (32 MiB a table). All
# 95.5M entries of the fit at once would hold 49 GB: XLA does not fuse
# the row gather into the lane select, and the program does not compile
_LOOKUP_CHUNK = 1 << 16


def _lookup_rows(tables: tuple, ids: tuple) -> tuple:
    """``tuple(t[x] for t, x in zip(tables, ids))`` for 1-D tables and
    ids ``>= 0`` of one length, the same bits: ``take_lane`` from each
    table's lane view, built once before the loop, ``_LOOKUP_CHUNK`` ids
    at a time, written into the results in place. The last chunk ends at
    the last id and reads some of the one before it again: the same rows
    written to the same places."""
    n = ids[0].shape[0]
    c = min(n, _LOOKUP_CHUNK)
    views = tuple(lane_view(t) for t in tables)

    def chunk(j, outs):
        s = jnp.minimum(j * c, n - c)
        return tuple(
            jax.lax.dynamic_update_slice(
                out, take_lane(view, jax.lax.dynamic_slice(x, (s,), (c,))),
                (s,))
            for out, view, x in zip(outs, views, ids))

    return jax.lax.fori_loop(0, -(-n // c), chunk,
                             tuple(jnp.zeros_like(x, t.dtype)
                                   for t, x in zip(tables, ids)))


@partial(jax.jit, static_argnames=("k", "rpb_u", "rpb_v"))
def _bucket_entries(key, u, i, r, w, row_of_u, row_of_i,
                    k: int, rpb_u: int, rpb_v: int):
    """Map entries to (stratum, user-block) buckets and sort them bucket-
    contiguous with random within-bucket order (≙ the host pass's seeded
    shuffle + stable bucket sort, data/blocking.py ``block_ratings``).
    Weight-0 padding entries keep their slots (static shapes) but carry
    w=0 through to the layout — no-ops everywhere downstream."""
    # named scopes here and in _layout: HLO metadata only, so a device
    # trace can name the phases (the sorts, the offsets, the block copies)
    with jax.named_scope("bucket/assign"):
        urow, irow = _lookup_rows((row_of_u, row_of_i), (u, i))
        ublk = urow // rpb_u
        iblk = irow // rpb_v
        strat = (iblk - ublk) % k
        flat = (strat * k + ublk).astype(jnp.int32)
        # padding entries spread round-robin over ALL buckets: their ids
        # are 0 so they would otherwise pile into one bucket and inflate
        # bmax (and the whole k²·bmax layout) by the total pad count
        n = flat.shape[0]
        ar = jnp.arange(n, dtype=jnp.int32)
        flat = jnp.where(w > 0, flat, ar % (k * k))
    # seeded permutation + stable bucket sort: buckets become contiguous
    # runs with random within-bucket order (≙ the host pass's shuffle +
    # stable counting sort). ``perm[argsort(flat[perm], stable)]`` orders
    # the entries by (bucket, place in the shuffle), so ONE two-key sort
    # carries every column along: no index vector is ever applied one
    # element at a time (avoids 64-bit composite keys, see _assign_rows)
    with jax.named_scope("bucket/permutation"):
        perm = jax.random.permutation(key, n)
        _, rank = jax.lax.sort_key_val(perm, ar)  # rank[perm[t]] = t
    with jax.named_scope("bucket/sort"):
        flat_s, _, urow_s, irow_s, vals_s, w_s = jax.lax.sort(
            (flat, rank, urow, irow, jnp.asarray(r, jnp.float32),
             jnp.asarray(w, jnp.float32)), num_keys=2, is_stable=False)
    with jax.named_scope("bucket/sizes"):
        ends = jnp.searchsorted(
            flat_s, jnp.arange(1, k * k + 1, dtype=jnp.int32))
        sizes = jnp.diff(ends, prepend=0).astype(jnp.int32)
    return sizes, flat_s, urow_s, irow_s, vals_s, w_s


def _carry(flag: jax.Array, vals: tuple, reverse: bool = False) -> tuple:
    """Segmented carry along the last axis: each of ``vals`` (32-bit) read
    at the nearest flagged position at or before every position (at or
    after, ``reverse``) — ``take_along_axis(v, cummax(where(flag, j, -1)))``
    without the gather, the values moved and never computed with.

    A running max over keys ``position << c | c bits of the value``: the
    position leads, so the max is the nearest flagged position's key and
    the value's bits ride below it, ``c = 31 - bits(position)`` of them a
    pass (16 at minibatch 32768: two passes a value). ``lax.cummax`` is the
    scan the TPU streams (a reduce-window); the same carry written as a
    ``lax.associative_scan`` over 32768-wide rows held the TPU compiler
    for minutes and then crashed it (PR 27). The first position (the last,
    ``reverse``) must be flagged, as a run's start (end) always is."""
    axis = flag.ndim - 1
    m = flag.shape[axis]
    c = 31 - max((m - 1).bit_length(), 1)
    if c < 1:
        raise ValueError(f"_carry: rows of {m} leave no bit for a payload")
    low = jnp.uint32((1 << c) - 1)
    j = jax.lax.broadcasted_iota(jnp.int32, flag.shape, axis)
    pos = ((m - 1 - j) if reverse else j) << c
    out = []
    for v in vals:
        bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
        got = jnp.zeros(v.shape, jnp.uint32)
        for shift in range(0, 32, c):
            part = ((bits >> shift) & low).astype(jnp.int32)
            key = jax.lax.cummax(jnp.where(flag, pos | part, -1),
                                 axis=axis, reverse=reverse)
            got = got | ((key.astype(jnp.uint32) & low) << shift)
        out.append(jax.lax.bitcast_convert_type(got, v.dtype))
    return tuple(out)


def sorted_run_weights(rows: jax.Array, w: jax.Array) -> jax.Array:
    """For every position of ``rows`` (ascending along the last axis), the
    sum of ``w`` over its run of equal rows: a cumsum difference between
    the run's two ends, which ``_carry`` brings to every member without a
    gather. Weights that are whole numbers sum exactly (below 2**24)."""
    diff = rows[..., 1:] != rows[..., :-1]
    ones = jnp.ones_like(rows[..., :1], bool)
    new = jnp.concatenate([ones, diff], axis=-1)  # run starts
    last = jnp.concatenate([diff, ones], axis=-1)  # run ends
    cumw = jnp.cumsum(w, axis=-1)
    cumw_start, w_start = _carry(new, (cumw, w))
    cumw_end, = _carry(last, (cumw,), reverse=True)
    return cumw_end - cumw_start + w_start


def _inv_counts_2d(rows: jax.Array, w: jax.Array,
                   presorted: bool = False) -> jax.Array:
    """Per-entry 1/(weight-sum of its row within its minibatch).

    Device form of ``blocking.minibatch_inv_counts`` / the native
    ``minibatch_inv_counts_flat``: sort each minibatch by row with the
    weights and the positions as payload, find each run's weighted size as
    a cumsum difference between the run's two ends (``_carry`` brings the
    ends' values to every member), and un-sort by the positions. Padding
    (weight 0) contributes nothing; its own scale is irrelevant (its delta
    is zero regardless).

    ``presorted``: the caller guarantees each minibatch row-vector is
    already ascending (the ``minibatch_sort`` side in ``_layout``) — both
    sorts drop out (run detection is identical on sorted input, so the
    result is bit-equal).
    """
    if presorted:
        sr, sw = rows, w
    else:
        j = jax.lax.broadcasted_iota(jnp.int32, rows.shape, rows.ndim - 1)
        sr, sw, sj = jax.lax.sort((rows, w, j), dimension=-1, num_keys=1,
                                  is_stable=True)
    inv_sorted = 1.0 / jnp.maximum(sorted_run_weights(sr, sw), 1.0)
    if presorted:
        return inv_sorted
    # un-sort: the original positions rode along as payload
    return jax.lax.sort((sj, inv_sorted), dimension=-1, num_keys=1,
                        is_stable=False)[1]


@jax.jit
def _inv_counts_pair(su2, si2, sw2):
    return _inv_counts_2d(su2, sw2), _inv_counts_2d(si2, sw2)


def _layout_rows(urow_s, irow_s, vals_s, w_s, sizes, bmax: int, mb: int,
                 sort_side: str | None):
    """Copy bucket-sorted entries into one padded row of ``bmax`` slots a
    bucket (``sizes`` gives the buckets in the order the entries hold
    them) and compute the per-minibatch collision scales (both sides).

    The entries arrive bucket-sorted, so bucket ``b`` is one contiguous run
    of them and one contiguous row of the layout: one block copy a bucket,
    no per-element scatter. Returns six minibatch-major ``[buckets * bmax
    / mb, mb]`` arrays."""
    nb = sizes.shape[0]
    with jax.named_scope("layout/offsets"):
        starts = jnp.cumsum(sizes) - sizes
    with jax.named_scope("layout/copy"):
        real = jnp.arange(bmax, dtype=jnp.int32)
        # a run's window may reach past the last entry: pad, don't clamp
        src = tuple(jnp.pad(a, (0, bmax))
                    for a in (urow_s, irow_s, vals_s, w_s))

        def copy_bucket(b):
            return tuple(
                jnp.where(real < sizes[b],
                          jax.lax.dynamic_slice(a, (starts[b],), (bmax,)),
                          0)
                for a in src)

        # [buckets, bmax] rows of whole minibatches -> the minibatch-major
        # view
        su, si, sv, sw = (a.reshape(-1, mb) for a in jax.lax.map(
            copy_bucket, jnp.arange(nb, dtype=jnp.int32)))

    if sort_side is not None:
        # intra-minibatch locality sort (≙ blocking.block_ratings
        # minibatch_sort): membership unchanged, math identical up to
        # float reassociation. The key is one of the columns, so one
        # stable sort moves all four
        with jax.named_scope("layout/minibatch_sort"):
            if sort_side == "user":
                su, si, sv, sw = jax.lax.sort(
                    (su, si, sv, sw), dimension=-1, num_keys=1,
                    is_stable=True)
            else:
                si, su, sv, sw = jax.lax.sort(
                    (si, su, sv, sw), dimension=-1, num_keys=1,
                    is_stable=True)

    with jax.named_scope("layout/inv_counts_u"):
        icu = _inv_counts_2d(su, sw, presorted=sort_side == "user")
    with jax.named_scope("layout/inv_counts_v"):
        icv = _inv_counts_2d(si, sw, presorted=sort_side == "item")
    return su, si, sv, sw, icu, icv


@partial(jax.jit, static_argnames=("k", "bmax", "mb", "sort_side"))
def _layout(flat_s, urow_s, irow_s, vals_s, w_s, sizes,
            k: int, bmax: int, mb: int, sort_side: str | None):
    """The padded stratum-major ``[k, k, bmax]`` layout of the k² buckets
    (``flat_s`` is implied by ``sizes`` and unread)."""
    del flat_s
    shape = (k, k, bmax)
    return tuple(a.reshape(shape) for a in _layout_rows(
        urow_s, irow_s, vals_s, w_s, sizes, bmax, mb, sort_side))


def device_block_problem(
    u: jax.Array,
    i: jax.Array,
    r: jax.Array,
    num_users: int,
    num_items: int,
    num_blocks: int,
    minibatch_multiple: int = 1,
    seed: int = 0,
    row_multiple: int = 8,
    minibatch_sort: str | None = None,
    weights: jax.Array | None = None,
) -> DeviceBlockedProblem:
    """Full on-device blocking pass over dense-id COO arrays.

    The only host↔device traffic is the 256-byte bucket-size vector (read
    back to fix the padded block size ``bmax``, which must be a static shape
    for XLA). Everything else — balanced row assignment, omegas, the
    stratum-major layout, per-minibatch collision scales — happens on chip.

    ``weights`` (float32, optional) marks weight-0 entries as padding: they
    keep layout slots (static shapes) but contribute nothing to counts,
    omegas, collision scales or training — the same weight-0 contract as
    the host path's ``Ratings``. Callers that pad per-host shards to equal
    sizes (multi-host ingest) use exactly this.
    """
    if minibatch_sort not in (None, "user", "item"):
        raise ValueError(
            f"minibatch_sort must be None|'user'|'item', got {minibatch_sort!r}")
    k = num_blocks
    if np.shape(u)[0] == 0:  # no-copy for device arrays (shape attr)
        raise ValueError("device_block_problem: empty ratings input")
    seam = get_tracer().seam
    with seam("fit/blocking/bucket"):
        # pre-cast range check: an OOB int64 id would wrap through the
        # int32 cast into a wrong-but-plausible layout (e.g. raw 1-based
        # MovieLens ids). One tiny scalar sync, once per fit.
        validate_dense_ids(u, i, num_users, num_items,
                           "device_block_problem")
        u = jnp.asarray(u, jnp.int32)
        i = jnp.asarray(i, jnp.int32)
        w = (jnp.ones(u.shape[0], jnp.float32) if weights is None
             else jnp.asarray(weights, jnp.float32))
        base = jax.random.PRNGKey(seed)

        rpb_u, rpb_v = rows_per_block(num_users, k, row_multiple), \
            rows_per_block(num_items, k, row_multiple)
        counts_u, counts_v = _weighted_counts(u, i, w, num_users, num_items)
        row_of_u, omega_u, id_of_ur = _assign_rows(
            jax.random.fold_in(base, 10), counts_u, k, rpb_u, k * rpb_u)
        row_of_i, omega_v, id_of_ir = _assign_rows(
            jax.random.fold_in(base, 11), counts_v, k, rpb_v, k * rpb_v)

        sizes, flat_s, urow_s, irow_s, vals_s, w_s = _bucket_entries(
            jax.random.fold_in(base, 12), u, i, r, w, row_of_u, row_of_i,
            k, rpb_u, rpb_v)

        # the one tiny device→host sync: it also makes this seam's end
        # the device's true end of the bucket phase
        sizes_host = np.asarray(sizes)
        _count_lane_lookups(u.shape[0])
    with seam("fit/blocking/layout"):
        bmax = max(int(sizes_host.max()), 1)
        mbm = max(minibatch_multiple, 1)
        bmax = -(-bmax // mbm) * mbm

        su, si, sv, sw, icu, icv = _layout(
            flat_s, urow_s, irow_s, vals_s, w_s, sizes, k, bmax, mbm,
            minibatch_sort)
        nnz = (int(sizes_host.sum()) if weights is None
               else int(jnp.sum(w > 0)))
        return DeviceBlockedProblem(
            su=su, si=si, sv=sv, sw=sw, icu=icu, icv=icv,
            omega_u=omega_u, omega_v=omega_v,
            row_of_user=row_of_u, row_of_item=row_of_i,
            id_of_user_row=id_of_ur, id_of_item_row=id_of_ir,
            num_blocks=k, rows_per_block_u=rpb_u, rows_per_block_v=rpb_v,
            nnz=nnz, max_pad_ratio=(k * k * bmax) / max(nnz, 1),
            minibatch=mbm,
        )


# --------------------------------------------------------------------------
# Blocking over a mesh (the stratum ring): each chip blocks its own share
# --------------------------------------------------------------------------

# the words one all_to_all moves an entry, int32 each (floats bitcast):
# bucket, place in the shuffle, user row, item row, value, weight
_EXCHANGED = 6


@dataclasses.dataclass
class MeshBlockedProblem:
    """What ``mesh_block_problem`` hands the stratum ring: the device-major
    ``[k, k, bmax]`` arrays, row ``p`` on ring position ``p`` (cell
    ``[p, s]`` is rating block ``(p, (p+s) mod k)``) with rows LOCAL to
    the owning block; the omegas and the id maps replicated. The same
    numbers as ``device_block_problem`` followed by the transposes to
    device-major, bit for bit."""

    ru: jax.Array  # int32[k, k, bmax] local user rows, sharded on dim 0
    ri: jax.Array  # int32[k, k, bmax] local item rows
    rv: jax.Array  # float32[k, k, bmax]
    rw: jax.Array  # float32[k, k, bmax] 1=real 0=pad
    icu: jax.Array  # float32[k, k, bmax] collision scales (user side)
    icv: jax.Array  # float32[k, k, bmax] (item side)
    omega_u: jax.Array  # float32[num_user_rows], replicated
    omega_v: jax.Array
    row_of_user: jax.Array  # int32[num_users], replicated
    row_of_item: jax.Array
    id_of_user_row: jax.Array
    id_of_item_row: jax.Array
    num_blocks: int
    rows_per_block_u: int
    rows_per_block_v: int
    nnz: int
    max_pad_ratio: float
    minibatch: int
    exchange_bytes: int  # what one chip sends the others in the exchange

    to_id_indices = DeviceBlockedProblem.to_id_indices
    holdout_rows = DeviceBlockedProblem.holdout_rows


def _entry_shards(cols, n: int, q: int, sharding, k: int):
    """Each column (a host array or an array on one device, ``n`` long) as
    a global array of ``k * q`` entries sharded on dim 0: ring position
    ``p`` holds entries ``[p * q, (p + 1) * q)``, zero-filled past ``n``.
    A device array is sliced where it lies and each slice sent to its
    chip."""
    index = sharding.addressable_devices_indices_map((k * q,))
    out = []
    for x in cols:
        pieces = []
        for dev, (sl,) in index.items():
            lo = sl.start or 0
            hi = max(min(lo + q, n), lo)
            piece = x[lo:hi]
            if hi - lo < q:
                pad = (0, q - (hi - lo))
                piece = (jnp.pad(piece, pad) if isinstance(piece, jax.Array)
                         else np.pad(piece, pad))
            pieces.append(jax.device_put(piece, dev))
        out.append(jax.make_array_from_single_device_arrays(
            (k * q,), sharding, pieces))
    return out


def _sharded(part, fn, in_specs, out_specs):
    return jax.jit(jax.shard_map(fn, mesh=part.mesh, in_specs=in_specs,
                                 out_specs=out_specs))


@functools.lru_cache(maxsize=16)
def _mesh_ones(part, size: int):
    return jax.jit(partial(jnp.ones, (size,), jnp.float32),
                   out_shardings=part.sharding("ratings"))


def _gather_rows(row, axis: str, k: int):
    """``[k, len(row)]``, every chip's ``row`` at its ring position, the
    same on every chip (a sum of one-hot rows over the ring)."""
    me = jnp.arange(k)[:, None] == jax.lax.axis_index(axis)
    return jax.lax.psum(jnp.where(me, row[None, :], 0), axis)


@functools.lru_cache(maxsize=16)
def _mesh_counts(part, n: int, q: int, num_users: int, num_items: int):
    """Per chip: its share's occurrence counts, summed over the ring (exact
    integers, so the sum is one chip's count of every entry), its own
    per-user counts and its weight-0 entries by the chip they go to."""
    axis, k = part.data_axis, part.num_blocks
    shard, rep = part.spec("ratings"), part.spec()

    def _weighted_counts(u, i, w):
        gidx = (jax.lax.axis_index(axis) * q
                + jnp.arange(q, dtype=jnp.int32))
        present = gidx < n
        real = (present & (w > 0)).astype(jnp.int32)
        cu = jnp.zeros(num_users, jnp.int32).at[u].add(real)
        cv = jnp.zeros(num_items, jnp.int32).at[i].add(real)
        # a weight-0 entry goes to bucket gidx mod k² (_bucket_entries),
        # which chip gidx mod k owns
        pad = present & (w <= 0)
        pads = jnp.sum(((gidx % k)[:, None] == jnp.arange(k)[None, :])
                       & pad[:, None], axis=0, dtype=jnp.int32)
        return (jax.lax.psum(cu, axis), jax.lax.psum(cv, axis), cu[None],
                pads[None])

    return _sharded(part, _weighted_counts, (shard,) * 3,
                    (rep, rep, shard, shard))


@functools.lru_cache(maxsize=16)
def _mesh_send_counts(part, rpb_u: int):
    """``[k, k]``, replicated: how many entries chip ``src`` sends chip
    ``dst`` (the chip that owns their user block)."""
    axis, k = part.data_axis, part.num_blocks
    shard, rep = part.spec("ratings"), part.spec()

    def _send_counts(cu, pads, row_of_u):
        ublk = row_of_u // rpb_u
        to = jnp.sum(jnp.where(ublk[:, None] == jnp.arange(k)[None, :],
                               cu[0][:, None], 0), axis=0, dtype=jnp.int32)
        return _gather_rows(to + pads[0], axis, k)

    return _sharded(part, _send_counts, (shard, shard, rep), rep)


def _slots(words, first, count, c: int, fill):
    """``[k, len(words), c]``: for each chip ``d``, the ``count[d]``
    columns of ``words`` from ``first[d]`` on, then ``fill`` up to ``c``
    slots; what one ``all_to_all`` hands the chips."""
    stack = jnp.pad(words, ((0, 0), (0, c)))
    slot = jnp.arange(c, dtype=jnp.int32)

    def send(d):
        win = jax.lax.dynamic_slice(stack, (0, first[d]), (words.shape[0], c))
        return jnp.where(slot < count[d], win, fill)

    return jax.lax.map(send, jnp.arange(first.shape[0], dtype=jnp.int32))


def shuffle_rounds(n: int) -> int:
    """The stable sorts by fresh 32-bit keys that ``jax.random.permutation``
    runs over ``n`` entries: ``3 ln n / ln(2^32 - 1)``, rounded up (JAX's
    ``random._shuffle``)."""
    return int(np.ceil(3 * np.log(max(1, n))
                       / np.log(np.iinfo(np.uint32).max)))


def even_slots(q: int, k: int) -> int:
    """Slots for an even share of ``q`` entries over ``k`` chips: a fixed
    1% over ``q / k``, in 512s, so that one shape serves every draw of the
    same size and compiles once. A shuffle round's count from a chip to a
    chip is binomial about ``q / k``: at 95.5M entries on four chips the
    1% is 28 standard deviations."""
    even = -(-int(q * 1.01) // k)
    return -(-even // 512) * 512


def _shuffle_places(key, n: int, q: int, s: int, axis: str, k: int):
    """Each chip's ``q`` entries' places in ``jax.random.permutation(key,
    n)``, bit for bit, with no array of every entry on any chip; and the
    largest count a chip sent a chip (the rounds' slots held every entry
    if it is at most ``s``).

    ``permutation`` is ``shuffle_rounds(n)`` stable sorts of the entries
    by fresh 32-bit keys, the key of place ``t`` drawn from ``t`` alone.
    In each round a chip holds a run of consecutive places, draws their
    keys, and sends each entry to the chip of its key's range (chip ``d``
    takes ``[d w, (d + 1) w)``, ``w = ceil(2^32 / k)``) in ``s`` slots a
    chip through one ``all_to_all``, in order of place; what a chip
    receives, in chip order, sorted stably by key is its run of the
    round's sorted order. Then every entry's place goes to the chip that
    holds the entry (entry ``e`` on chip ``e // q``) through one more
    ``all_to_all``, which sorts what it receives by entry.

    A sender's sort is by one word, its chip above its place, which is
    unique: no stable sort's index and no key ride along, the keys are
    drawn again in the sorted order (round 1 sorts that word alone)."""
    p = jax.lax.axis_index(axis)
    u32 = jnp.uint32
    width = -(-(1 << 32) // k)
    shift = 32 - k.bit_length()  # a place's bits under its chip (k: none)
    ar_k = jnp.arange(k, dtype=jnp.int32)

    def draw(sub, places):
        b1, b2 = threefry2x32_p.bind(sub[0], sub[1], jnp.zeros_like(places),
                                     places)
        return b1 ^ b2

    def chip_counts(dest):
        # what this chip sends each chip; all chips' (rows: the sender)
        mine = jnp.sum(dest[None, :] == ar_k[:, None], axis=1,
                       dtype=jnp.int32)
        return mine, _gather_rows(mine, axis, k)

    def exchange(words, mine, fill):
        out = _slots(words, jnp.cumsum(mine) - mine, mine, s, fill)
        got = jax.lax.all_to_all(out, axis, 0, 0, tiled=True)
        return jnp.transpose(got, (1, 0, 2)).reshape(words.shape[0], k * s)

    # round 1 shuffles arange(n): chip p holds places [p q, p q + q), and
    # each entry is its place (vals None)
    held = jnp.clip(n - p * q, 0, q)
    start = (p * q).astype(u32)
    size, vals = q, None
    need = jnp.int32(0)
    for _ in range(shuffle_rounds(n)):
        if size > 1 << shift:
            raise ValueError(f"_shuffle_places: {size} places a chip leave "
                             f"no room for {k} chips in 32 bits")
        key, sub = jax.random.split(key)
        # the key is the same on every chip, the places are not
        sub = jax.lax.pcast(sub, axis, to="varying")
        j = jnp.arange(size, dtype=u32)
        dest = ((draw(sub, start + j) // u32(width)).astype(jnp.int32)
                if k > 1 else jnp.zeros(size, jnp.int32))
        dest = jnp.where(j < held.astype(u32), dest, k)
        mine, counts = chip_counts(dest)
        need = jnp.maximum(need, counts.max())
        order = (dest.astype(u32) << shift) | j
        if vals is None:
            order = jax.lax.sort(order, is_stable=False)
        else:
            order, vals = jax.lax.sort((order, vals), num_keys=1,
                                       is_stable=False)
        j = order & u32((1 << shift) - 1)
        vals = start + j if vals is None else vals
        # the key less its range's start (below w): an empty slot sorts
        # last; stable, so equal keys stay in order of place
        low = draw(sub, start + j)
        low = low % u32(width) if k > 1 else low
        got = exchange(jnp.stack([low, vals]), mine,
                       jnp.array([[0xFFFFFFFF], [0]], u32))
        _, vals = jax.lax.sort(tuple(got), num_keys=1, is_stable=True)
        holds = counts.sum(axis=0)
        held = holds[p]
        start = (jnp.cumsum(holds)[p] - held).astype(u32)
        size = k * s
    # the inversion: entry e's place goes to the chip that holds e
    j = jnp.arange(size, dtype=u32)
    ent = start + j if vals is None else vals
    ent = jnp.where(j < held.astype(u32), ent, u32(k * q))
    mine, counts = chip_counts((ent // u32(q)).astype(jnp.int32))
    need = jnp.maximum(need, counts.max())
    ent, place = jax.lax.sort((ent, start + j), num_keys=1, is_stable=False)
    got = exchange(jnp.stack([ent, place]), mine,
                   jnp.array([[k * q], [0]], u32))
    _, place = jax.lax.sort(tuple(got), num_keys=1, is_stable=False)
    place = jnp.pad(place, (0, max(q - k * s, 0)))[:q]
    return place.astype(jnp.int32), need


@functools.lru_cache(maxsize=16)
def _mesh_bucket(part, n: int, q: int, c: int, s: int, rpb_u: int,
                 rpb_v: int):
    """Per chip: its entries' buckets, their places in the seeded shuffle
    (``_shuffle_places``: the chips sort it in shares, ``s`` slots a pair
    of chips a round), one ``all_to_all`` that hands every entry to the
    chip of its user block (``c`` slots from each chip to each), and a
    sort of what it received by (bucket, place). Buckets and places are
    unique together, so each chip's buckets come out in the order
    ``_bucket_entries`` gives them on one chip. Beside the sizes, the
    largest count a chip sent a chip in the shuffle: above ``s``, the
    shuffle lost entries."""
    axis, k = part.data_axis, part.num_blocks
    shard, rep = part.spec("ratings"), part.spec()
    # an empty slot sorts after every bucket and is never laid out
    fill = jnp.array([k * k] + [0] * (_EXCHANGED - 1), jnp.int32)[:, None]
    as_word = partial(jax.lax.bitcast_convert_type, new_dtype=jnp.int32)
    as_float = partial(jax.lax.bitcast_convert_type, new_dtype=jnp.float32)

    def _bucket_entries(key, u, i, r, w, row_of_u, row_of_i):
        p = jax.lax.axis_index(axis)
        gidx = p * q + jnp.arange(q, dtype=jnp.int32)
        with jax.named_scope("bucket/assign"):
            # the one-chip program's keys; the round-robin of weight-0
            # entries by their place in the whole input
            urow, irow = _lookup_rows((row_of_u, row_of_i), (u, i))
            ublk = urow // rpb_u
            iblk = irow // rpb_v
            flat = (((iblk - ublk) % k) * k + ublk).astype(jnp.int32)
            flat = jnp.where(w > 0, flat, gidx % (k * k))
            # the zero-filled tail past n is no entry: it goes nowhere
            dest = jnp.where(gidx < n, flat % k, k)
        with jax.named_scope("bucket/permutation"):
            rank, need = _shuffle_places(key, n, q, s, axis, k)
        with jax.named_scope("bucket/send"):
            dest_s, *words = jax.lax.sort(
                (dest, flat, rank, urow, irow, as_word(r), as_word(w)),
                num_keys=1, is_stable=False)
            ar = jnp.arange(k + 1, dtype=jnp.int32)
            bounds = jnp.searchsorted(dest_s, ar)
            out = _slots(jnp.stack(words), bounds[:-1], jnp.diff(bounds), c,
                         fill)  # [k, words, c]
        with jax.named_scope("bucket/exchange"):
            got = jax.lax.all_to_all(out, axis, 0, 0, tiled=True)
        with jax.named_scope("bucket/sort"):
            got = jnp.transpose(got, (1, 0, 2)).reshape(_EXCHANGED, k * c)
            flat_s, _, urow_s, irow_s, v_s, w_s = jax.lax.sort(
                tuple(got), num_keys=2, is_stable=False)
        with jax.named_scope("bucket/sizes"):
            mine = ar[:k] * k + p  # buckets (s, p), s = 0..k-1
            sizes = (jnp.searchsorted(flat_s, mine + 1)
                     - jnp.searchsorted(flat_s, mine)).astype(jnp.int32)
        return (_gather_rows(sizes, axis, k), need, urow_s, irow_s,
                as_float(v_s), as_float(w_s))

    return _sharded(part, _bucket_entries,
                    (rep,) + (shard,) * 4 + (rep, rep),
                    (rep, rep) + (shard,) * 4)


@functools.lru_cache(maxsize=16)
def _mesh_layout(part, bmax: int, mb: int, sort_side: str | None,
                 rpb_u: int, rpb_v: int):
    """Per chip: its k buckets laid out as row ``p`` of the device-major
    layout, rows made local to the chip's blocks."""
    axis = part.data_axis
    shard, rep = part.spec("ratings"), part.spec()

    def _layout(urow_s, irow_s, vals_s, w_s, sizes):
        su, si, sv, sw, icu, icv = (a.reshape(1, -1, bmax) for a in (
            _layout_rows(urow_s, irow_s, vals_s, w_s,
                         sizes[jax.lax.axis_index(axis)], bmax, mb,
                         sort_side)))
        return su % rpb_u, si % rpb_v, sv, sw, icu, icv

    return _sharded(part, _layout, (shard,) * 4 + (rep,), (shard,) * 6)


def exchange_slots(send: np.ndarray, q: int, k: int) -> int:
    """Slots each chip sends each chip in the exchange: ``even_slots``,
    or the largest count where a pair exceeds that (skewed or
    pre-partitioned input)."""
    return max(int(send.max()), even_slots(q, k))


def mesh_block_problem(
    u,
    i,
    r,
    num_users: int,
    num_items: int,
    partitioner,
    minibatch_multiple: int = 1,
    seed: int = 0,
    row_multiple: int = 8,
    minibatch_sort: str | None = None,
    weights=None,
    _shuffle_slots: int | None = None,
) -> MeshBlockedProblem:
    """``device_block_problem`` for the stratum ring, as programs over the
    partitioner's mesh, with the same result bit for bit: no chip ever
    holds more than its share of the entries and of the layout.

    Each ring position takes a contiguous share of the COO entries (given
    on the host or on one device, they are sliced and sent; given as
    arrays already sharded by the partitioner's ``ratings`` rule, they
    stay). Each chip counts its share and the counts are summed over the
    ring (``_weighted_counts``); the balanced row assignment runs
    replicated (``_assign_rows``, the sides' ids only); each chip finds
    its entries' buckets and places in the seeded shuffle and one
    ``all_to_all`` hands every entry to the chip of its user block, which
    sorts what it received (``_bucket_entries``); and each chip lays out
    its own row of the device-major layout (``_layout``). The host reads
    two ``[k, k]`` count matrices (the exchange's slot count and ``bmax``
    are static shapes) and nothing else.

    The chips sort the shuffle in shares (``_shuffle_places``: a round of
    key-range routing by ``all_to_all`` for each of ``permutation``'s
    sorts, and one more that hands each chip its entries' places), in
    ``even_slots`` slots a pair of chips a round. Where a round's count
    outgrows them the bucket program runs again with room for it
    (``_shuffle_slots`` sets the first slots, for tests), counted by
    ``blocking_shuffle_retries_total`` on the live registry.
    """
    if minibatch_sort not in (None, "user", "item"):
        raise ValueError(
            f"minibatch_sort must be None|'user'|'item', got {minibatch_sort!r}")
    part = partitioner
    k = part.num_blocks
    n = int(np.shape(u)[0])
    if n == 0:
        raise ValueError("mesh_block_problem: empty ratings input")
    shard = part.sharding("ratings")
    seam = get_tracer().seam
    with seam("fit/blocking/bucket"):
        validate_dense_ids(u, i, num_users, num_items, "mesh_block_problem")
        cols = (u, i, r) + (() if weights is None else (weights,))
        placed = n % k == 0 and all(
            isinstance(x, jax.Array) and x.sharding == shard for x in cols)
        q = n // k if placed else -(-n // k)
        if placed:
            u, i, r = (jnp.asarray(x, dt) for x, dt in
                       zip(cols[:3], (jnp.int32, jnp.int32, jnp.float32)))
            w = None if weights is None else jnp.asarray(cols[3],
                                                         jnp.float32)
        else:
            as_np = not isinstance(u, jax.Array)
            cast = (lambda x, dt: np.asarray(x, dt)) if as_np else (
                lambda x, dt: jnp.asarray(x, dt))
            host = [cast(u, np.int32), cast(i, np.int32),
                    cast(r, np.float32)]
            if weights is not None:
                host.append(cast(weights, np.float32))
            sent = _entry_shards(host, n, q, shard, k)
            u, i, r = sent[:3]
            w = sent[3] if weights is not None else None
        if w is None:
            w = _mesh_ones(part, k * q)()
        base = jax.random.PRNGKey(seed)
        rpb_u = rows_per_block(num_users, k, row_multiple)
        rpb_v = rows_per_block(num_items, k, row_multiple)

        counts_u, counts_v, cu_own, pads = _mesh_counts(
            part, n, q, num_users, num_items)(u, i, w)
        row_of_u, omega_u, id_of_ur = _assign_rows(
            jax.random.fold_in(base, 10), counts_u, k, rpb_u, k * rpb_u)
        row_of_i, omega_v, id_of_ir = _assign_rows(
            jax.random.fold_in(base, 11), counts_v, k, rpb_v, k * rpb_v)
        send = np.asarray(_mesh_send_counts(part, rpb_u)(
            cu_own, pads, row_of_u))
        c = exchange_slots(send, q, k)
        del cu_own, pads
        s = even_slots(q, k) if _shuffle_slots is None else _shuffle_slots
        retries = 0
        while True:
            sizes, need, urow_s, irow_s, vals_s, w_s = _mesh_bucket(
                part, n, q, c, s, rpb_u, rpb_v)(
                jax.random.fold_in(base, 12), u, i, r, w, row_of_u,
                row_of_i)
            # the bucket phase's one read-back; it also ends this seam
            # where the device ends the phase
            sizes_host, need = jax.device_get((sizes, need))
            if need <= s:
                break
            # a round's count outgrew its slots and entries were lost: run
            # again with room for it, at least twice the slots (a count
            # read after a loss may be short), never more than n
            retries += 1
            s = min(max(int(need), 2 * s), n)
        nnz = n if weights is None else int(np.asarray(
            _count_real(counts_u)))
    with seam("fit/blocking/layout"):
        bmax = max(int(sizes_host.max()), 1)
        mbm = max(minibatch_multiple, 1)
        bmax = -(-bmax // mbm) * mbm
        ru, ri, rv, rw, icu, icv = _mesh_layout(
            part, bmax, mbm, minibatch_sort, rpb_u, rpb_v)(
            urow_s, irow_s, vals_s, w_s, sizes)
    exchange_bytes = (k - 1) * c * _EXCHANGED * 4
    _publish_exchange(exchange_bytes, sizes_host.sum(axis=1), retries)
    _count_lane_lookups(k * q * (retries + 1))
    return MeshBlockedProblem(
        ru=ru, ri=ri, rv=rv, rw=rw, icu=icu, icv=icv,
        omega_u=omega_u, omega_v=omega_v,
        row_of_user=row_of_u, row_of_item=row_of_i,
        id_of_user_row=id_of_ur, id_of_item_row=id_of_ir,
        num_blocks=k, rows_per_block_u=rpb_u, rows_per_block_v=rpb_v,
        nnz=nnz, max_pad_ratio=(k * k * bmax) / max(nnz, 1),
        minibatch=mbm, exchange_bytes=exchange_bytes,
    )


@jax.jit
def _count_real(counts):
    return jnp.sum(counts)


def _publish_exchange(sent_bytes: int, held, retries: int) -> None:
    """The exchange on the live registry (``obs.enable()``; nothing
    otherwise): ``blocking_exchange_bytes_total{chip}``, what each chip
    sent the others, ``blocking_shard_entries{chip}``, the entries
    (weight-0 ones among them) each holds after it: the balance of the
    user blocks; and ``blocking_shuffle_retries_total``, the bucket
    program's reruns for a shuffle round that outgrew its slots."""
    from large_scale_recommendation_tpu.obs.registry import get_registry

    obs = get_registry()
    if not obs.enabled:
        return
    obs.counter("blocking_shuffle_retries_total").inc(retries)
    for chip, entries in enumerate(held):
        obs.counter("blocking_exchange_bytes_total",
                    chip=str(chip)).inc(sent_bytes)
        obs.gauge("blocking_shard_entries", chip=str(chip)).set(int(entries))


def _count_lane_lookups(entries: int) -> None:
    """``blocking_lane_lookups_total`` on the live registry
    (``obs.enable()``; nothing otherwise): the id→row lookups
    ``_lookup_rows`` made, a user's and an item's for each entry a
    bucket program looked up (a chip's zero-filled tail and a shuffle
    retry's second pass among them)."""
    from large_scale_recommendation_tpu.obs.registry import get_registry

    obs = get_registry()
    if obs.enabled:
        obs.counter("blocking_lane_lookups_total").inc(2 * int(entries))


def recompute_inv_counts(problem: DeviceBlockedProblem, minibatch: int):
    """Collision scales for a DIFFERENT kernel minibatch on the same layout.

    Valid for any ``minibatch`` dividing the padded block size — lets a
    caller A/B kernel minibatch sizes (bench autotune) from ONE blocking
    pass instead of rebuilding the layout per candidate. Returns
    ``(icu, icv)`` shaped like the problem's.
    """
    k, bmax = problem.num_blocks, problem.su.shape[-1]
    if bmax % minibatch != 0:
        raise ValueError(
            f"minibatch {minibatch} does not divide padded block size "
            f"{bmax}; rebuild the problem with this minibatch_multiple")
    shape = (k, k, bmax)
    icu, icv = _inv_counts_pair(
        problem.su.reshape(-1, minibatch),
        problem.si.reshape(-1, minibatch),
        problem.sw.reshape(-1, minibatch),
    )
    return icu.reshape(shape), icv.reshape(shape)


def init_factors_device(problem: DeviceBlockedProblem, rank: int,
                        scale: float) -> tuple[jax.Array, jax.Array]:
    """Per-id deterministic factor init for the device problem.

    Same semantics as ``PseudoRandomFactorInitializer`` (row = scale ·
    uniform(fold_in(key0, id))) applied through ``id_of_*_row``, so a given
    id gets the same vector as on the host path's table for that id.
    Padding rows carry id 0's vector — they are never touched by training
    (no ratings reference them).
    """
    from large_scale_recommendation_tpu.core.initializers import (
        _keyed_uniform_rows_padded,
    )

    key = jax.random.PRNGKey(0)
    s = jnp.float32(scale)
    U = _keyed_uniform_rows_padded(key, problem.id_of_user_row, rank, s)
    V = _keyed_uniform_rows_padded(key, problem.id_of_item_row, rank, s)
    return U, V
