"""Jitted SGD kernels: the DSGD hot inner loop, batched for the MXU/VPU.

TPU-native replacement for the reference's sequential per-rating inner loop
(reference: DSGDforMF.scala:392-418 ``updateLocalFactors`` — netlib ``ddot``
+ scalar zip/map per rating; OfflineSpark.scala:179-187). Instead of one
rating at a time, ratings stream through in minibatches:

    gather u = U[rows], v = V[rows]          (vectorized gather)
    e = r − Σ u∘v                            (one fused einsum)
    ΔU, ΔV from the pluggable updater        (core.updaters — same seam as
                                              the reference FactorUpdater)
    scatter-add ΔU into U, ΔV into V         (duplicate rows in a minibatch
                                              accumulate — minibatch-SGD
                                              semantics, SURVEY §7 (b))

The minibatch loop is a ``lax.scan`` so the whole stratum sweep is one XLA
computation with no host round-trips; batch size 1 recovers the reference's
exact sequential semantics for parity testing.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from large_scale_recommendation_tpu.data.device_blocking import (
    lane_view,
    sorted_run_weights,
    take_lane,
)


def dsgd_bytes_per_sweep(nnz: int, rank: int, *, factor_bytes: int = 4,
                         model_size: int = 1, loss: str = "squared") -> int:
    """Bytes of HBM traffic one full DSGD sweep moves PER DEVICE.

    The roofline model behind the ``train_hbm_gbs`` obs gauge. Every
    rating pays ~4 row transactions (read+write of a u row and a v row)
    of ``rank × factor_bytes`` plus ~16 B of COO stream. This is the
    historical bench model (4·rank·4 + 16 at f32). Under ``loss="bpr"``
    a rating is a triple and pays ~6 (the negative's row read and
    written too); its stream (u, i, weight, user scale) is 16 B as well.

    ``model_size`` is the size of the ``'model'`` mesh axis: rank-sharded
    tables hold ``rank/model_size`` columns per device, so the factor-row
    term divides by it (the COO stream is replicated across the model
    axis and does NOT divide). The extra wire traffic the reduction
    collectives move is a SEPARATE term — see
    ``dsgd_collective_bytes_per_sweep`` — so the roofline can show HBM
    and interconnect as distinct costs.
    """
    if model_size < 1 or rank % model_size:
        raise ValueError(
            f"model_size {model_size} must be ≥1 and divide rank {rank}")
    rows = 6 if loss == "bpr" else 4
    return int(nnz * (rows * (rank // model_size) * factor_bytes + 16))


def dsgd_collective_bytes_per_sweep(nnz: int, rank: int,
                                    model_size: int = 1) -> int:
    """Interconnect bytes one DSGD sweep moves per device for the
    rank-reduction collectives, ring all-reduce model.

    The rank-sharded kernel ``psum``s ONE f32 prediction per rating over
    the ``'model'`` axis (the ``u·v`` dot); a ring all-reduce of m
    participants moves ``2·(m−1)/m`` bytes per reduced byte per device
    (reduce-scatter + all-gather). model_size=1 ⇒ 0 — the replicated
    path pays no collective. Kept SEPARATE from
    ``dsgd_bytes_per_sweep`` so ``/rooflinez`` prices HBM and wire as
    their own terms (``rank`` is accepted for signature symmetry and
    future per-element generalizations; the pred reduction is
    rank-independent)."""
    del rank
    if model_size <= 1:
        return 0
    return int(nnz * 4 * 2 * (model_size - 1) / model_size)


def dsgd_flops_per_sweep(nnz: int, rank: int, loss: str = "squared") -> int:
    """FLOPs one full DSGD sweep computes: ~6·rank per rating visit
    (2·rank for the prediction dot, ~4·rank for the error broadcast and
    the two factor deltas); under ``loss="bpr"`` ~10·rank per triple
    (the difference ``v_i − v_j``, the dot with ``u``, and the three
    deltas at 2·rank each). The FLOP twin of ``dsgd_bytes_per_sweep`` —
    the ONE hand model behind the ``/rooflinez`` model column."""
    return int(nnz * (10 if loss == "bpr" else 6) * rank)


def sgd_minibatch_update(
    U: jax.Array,
    V: jax.Array,
    u_rows: jax.Array,
    i_rows: jax.Array,
    values: jax.Array,
    weights: jax.Array,
    omega_u: jax.Array | None,
    omega_v: jax.Array | None,
    updater: Any,
    t: jax.Array | int,
    collision: str = "mean",
    inv_cu: jax.Array | None = None,
    inv_cv: jax.Array | None = None,
    pred_axis: str | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One minibatch: gather → delta → scatter-add.

    ≙ one group of iterations of the per-rating loop at
    DSGDforMF.scala:398-417. Row collisions inside a minibatch (the same
    user/item hit by several ratings — SURVEY §7 hard part (b)):

    - ``collision="mean"`` (default): each row's accumulated delta is divided
      by its occurrence count, bounding the effective step at the base
      learning rate. Without this, dense workloads (many ratings per row per
      minibatch) make the summed stale-point deltas an effective step of
      lr × dup_count and training diverges to NaN.
    - ``collision="sum"``: raw additive accumulation (plain minibatch SGD) —
      closest to sequential semantics when collisions are rare.

    ``inv_cu``/``inv_cv`` are optional PRECOMPUTED per-entry 1/occurrence
    scales (``data.blocking.minibatch_inv_counts``). When given with
    ``collision="mean"`` they replace the runtime counters — the counts are
    a pure function of the static blocked layout, and the runtime form
    costs two full-table zero+scatter+gather rounds per step.

    With ``minibatch=1`` both modes recover the reference's exact sequential
    per-rating semantics.

    ``omega_u``/``omega_v`` are the per-row ω as lane views
    (``lane_view``, which ``sgd_block_sweep`` builds once a block), or
    ``None``.

    ``pred_axis`` names the mesh axis U/V are rank-sharded over (the
    ``'model'`` axis inside a shard_map): each device then holds only
    ``rank/m`` columns, the local einsum is a PARTIAL dot, and the full
    prediction is its ``psum`` over that axis — handed to the updater as
    ``pred=`` so the error term uses the full-rank dot while every other
    operation (deltas, collision scaling, scatter-add) stays purely
    row-space and therefore correct on the rank slice unchanged.
    """
    if collision not in ("mean", "sum"):
        raise ValueError(
            f"collision must be 'mean' or 'sum', got {collision!r}"
        )
    # named scopes: HLO metadata only, so a device trace can name the
    # phases of the sweep (the residual is scoped inside the updater,
    # core.updaters._errors); no arithmetic moves. A scope opened inside
    # another nests under it: sgd/gather/omega, sgd/update/collision_counts
    with jax.named_scope("sgd/gather"):
        u = U[u_rows]
        v = V[i_rows]
        with jax.named_scope("omega"):
            ou = None if omega_u is None else take_lane(omega_u, u_rows)
            ov = None if omega_v is None else take_lane(omega_v, i_rows)
    with jax.named_scope("sgd/update"):
        pred = None
        if pred_axis is not None:
            pred = jax.lax.psum(jnp.einsum("bk,bk->b", u, v), pred_axis)
        du, dv = updater.delta(
            values,
            u,
            v,
            weights=weights,
            omega_u=ou,
            omega_v=ov,
            t=t,
            **({} if pred is None else {"pred": pred}),
        )
        if collision == "mean":
            if inv_cu is not None:
                du = du * inv_cu[:, None]
                dv = dv * inv_cv[:, None]
            else:
                with jax.named_scope("collision_counts"):
                    cu = jnp.zeros(U.shape[0], U.dtype).at[u_rows].add(
                        weights)
                    cv = jnp.zeros(V.shape[0], V.dtype).at[i_rows].add(
                        weights)
                    du = du / jnp.maximum(cu[u_rows], 1.0)[:, None]
                    dv = dv / jnp.maximum(cv[i_rows], 1.0)[:, None]
    with jax.named_scope("sgd/scatter_u"):
        U = U.at[u_rows].add(du)
    with jax.named_scope("sgd/scatter_v"):
        V = V.at[i_rows].add(dv)
    return U, V


def bpr_minibatch_update(
    U: jax.Array,
    V: jax.Array,
    u_rows: jax.Array,
    i_rows: jax.Array,
    weights: jax.Array,
    n_real: jax.Array,
    key: jax.Array,
    updater: Any,
    t: jax.Array | int,
    collision: str = "mean",
    inv_cu: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One minibatch of BPR (Rendle et al., UAI 2009): every entry
    ``(u, i)`` is a positive, and a negative ``j`` is drawn for it
    uniformly from the item rows ``[0, n_real)`` of ``V`` (the visited
    block's rows seen in training) with ``key``. For a triple with weight
    ``w``, ``x = u·(v_i − v_j)`` and ``g = σ(−x)``:

        Δu   = w·η(g(v_i − v_j) − λu)
        Δv_i = w·η(g·u − λv_i)
        Δv_j = w·η(−g·u − λv_j)

    so a padding entry (``w = 0``) changes nothing. ``η`` is the
    updater's schedule at ``t``; one ``λ`` (the updater's ``lambda_``)
    for all three, as the ``implicit`` library's BPR has it, where the
    paper gives each its own. No ω: the per-occurrence weighting is the
    squared loss's (``sgd_minibatch_update``).

    ``collision="mean"``: the user side divides by the precomputed
    ``inv_cu`` (or its runtime count); the item side by the weighted
    occurrences of a row as positive and as negative in this minibatch,
    counted here in row order (``_add_item_side``), since the negatives
    are drawn anew every sweep."""
    if collision not in ("mean", "sum"):
        raise ValueError(
            f"collision must be 'mean' or 'sum', got {collision!r}")
    with jax.named_scope("sgd/negatives"):
        j_rows = jax.random.randint(key, u_rows.shape, 0, n_real,
                                    dtype=jnp.int32)
    with jax.named_scope("sgd/gather"):
        u = U[u_rows]
        vi = V[i_rows]
        vj = V[j_rows]
    with jax.named_scope("sgd/update"):
        lr = updater.schedule(jnp.float32(updater.learning_rate), t)
        lam = jnp.float32(updater.lambda_)
        d = vi - vj
        g = jax.nn.sigmoid(-jnp.einsum("bk,bk->b", u, d))[:, None]
        lw = (lr * weights)[:, None]
        du = lw * (g * d - lam * u)
        dv = jnp.concatenate([lw * (g * u - lam * vi),
                              lw * (-g * u - lam * vj)])
        if collision == "mean":
            if inv_cu is not None:
                du = du * inv_cu[:, None]
            else:
                with jax.named_scope("collision_counts"):
                    cu = jnp.zeros(U.shape[0], U.dtype).at[u_rows].add(
                        weights)
                    du = du / jnp.maximum(cu[u_rows], 1.0)[:, None]
    with jax.named_scope("sgd/scatter_u"):
        U = U.at[u_rows].add(du)
    return U, _add_item_side(V, i_rows, j_rows, weights, dv, collision)


def _add_item_side(V: jax.Array, i_rows: jax.Array, j_rows: jax.Array,
                   weights: jax.Array, dv: jax.Array,
                   collision: str) -> jax.Array:
    """``V.at[concat([i_rows, j_rows])].add(dv)`` applied in row order;
    under ``"mean"`` each delta divided by the weight sum of its row's
    occurrences in the minibatch, as positive and as negative (at least 1).

    One stable sort of the rows with the weights and the positions gives
    the counts as the weight sums of the sorted runs and the order in
    which the deltas are gathered into a scatter told its rows are sorted.
    Stable, so a row's addends keep the order in which an unsorted scatter
    adds them. On the TPU a scatter of rows in random order cost four
    times a sorted one (PERF.md, Findings)."""
    with jax.named_scope("sgd/negatives"):
        v_rows = jnp.concatenate([i_rows, j_rows])
        rows, w, perm = jax.lax.sort(
            (v_rows, jnp.concatenate([weights, weights]),
             jax.lax.iota(jnp.int32, v_rows.shape[0])),
            num_keys=1, is_stable=True)
        if collision == "mean":
            counts = sorted_run_weights(rows, w)
    with jax.named_scope("sgd/scatter_v"):
        dv = dv[perm]
        if collision == "mean":
            dv = dv / jnp.maximum(counts, 1.0)[:, None]
        return V.at[rows].add(dv, indices_are_sorted=True)


def _minibatch_scan(step, U: jax.Array, V: jax.Array, minibatch: int,
                    streams: tuple, indexed: bool = False,
                    ) -> tuple[jax.Array, jax.Array]:
    """``lax.scan`` of ``U, V = step(U, V, *chunk)`` over the minibatches
    of ``streams`` (1-D, of one length divisible by ``minibatch``; a
    ``None`` stream reaches ``step`` as ``None``). ``indexed`` hands each
    minibatch's index to ``step`` first."""
    e = streams[0].shape[0]
    assert e % minibatch == 0, f"block nnz {e} not divisible by minibatch {minibatch}"
    n_chunks = e // minibatch
    index = (jnp.arange(n_chunks, dtype=jnp.int32),) if indexed else ()
    xs = index + tuple(None if a is None else a.reshape(n_chunks, minibatch)
                       for a in streams)

    def body(carry, x):
        return step(*carry, *x), None

    (U, V), _ = jax.lax.scan(body, (U, V), xs)
    return U, V


def bpr_block_sweep(
    U: jax.Array,
    V: jax.Array,
    u_rows: jax.Array,  # int32[e] (e divisible by minibatch)
    i_rows: jax.Array,
    weights: jax.Array,
    n_real: jax.Array,
    key: jax.Array,
    updater: Any,
    t: jax.Array | int,
    minibatch: int,
    collision: str = "mean",
    inv_cu: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """``sgd_block_sweep`` for BPR: minibatch ``m`` of the block draws its
    negatives with ``fold_in(key, m)``."""

    def step(U, V, m, ur, ir, w, icu):
        return bpr_minibatch_update(
            U, V, ur, ir, w, n_real, jax.random.fold_in(key, m), updater, t,
            collision, icu)

    return _minibatch_scan(step, U, V, minibatch,
                           (u_rows, i_rows, weights, inv_cu), indexed=True)


def sgd_block_sweep(
    U: jax.Array,
    V: jax.Array,
    u_rows: jax.Array,  # int32[e] (e divisible by minibatch)
    i_rows: jax.Array,
    values: jax.Array,
    weights: jax.Array,
    omega_u: jax.Array | None,
    omega_v: jax.Array | None,
    updater: Any,
    t: jax.Array | int,
    minibatch: int,
    collision: str = "mean",
    inv_cu: jax.Array | None = None,
    inv_cv: jax.Array | None = None,
    pred_axis: str | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Sweep one rating block (or one whole stratum flattened) in minibatch
    chunks via ``lax.scan``. ``pred_axis`` — see ``sgd_minibatch_update``.

    ≙ ``updateLocalFactors`` visiting every rating of the block once
    (DSGDforMF.scala:392-418). Chunk order is the deterministic blocked order
    (the reference shuffles per visit unless seeded, DSGDforMF.scala:392-393;
    we are deterministic-by-default, the seeded behavior).
    """
    # built here, before the scan: a view built in the step is sunk into
    # the loop by XLA, a pad a minibatch (tests/test_tpu_compile.py)
    omega_u = None if omega_u is None else lane_view(omega_u)
    omega_v = None if omega_v is None else lane_view(omega_v)

    def step(U, V, ur, ir, vals, w, icu, icv):
        return sgd_minibatch_update(
            U, V, ur, ir, vals, w, omega_u, omega_v, updater, t, collision,
            icu, icv, pred_axis,
        )

    return _minibatch_scan(
        step, U, V, minibatch,
        (u_rows, i_rows, values, weights, inv_cu, inv_cv))


@partial(
    jax.jit,
    static_argnames=("updater", "minibatch", "num_blocks", "iterations",
                     "collision", "loss"),
)
def dsgd_train(
    U: jax.Array,
    V: jax.Array,
    su: jax.Array,  # int32[k, k, b] stratum-major user rows
    si: jax.Array,
    sv: jax.Array,
    sw: jax.Array,
    omega_u: jax.Array,
    omega_v: jax.Array,
    inv_cu: jax.Array | None = None,  # [k, k, b] precomputed collision
    inv_cv: jax.Array | None = None,  # scales (blocking.minibatch_inv_counts)
    n_real: jax.Array | None = None,  # int32[k] (loss="bpr")
    neg_key: jax.Array | None = None,  # the negatives' PRNG key (loss="bpr")
    *,
    updater: Any,
    minibatch: int,
    num_blocks: int,
    iterations: int,
    collision: str = "mean",
    t0: jax.Array | int = 0,
    loss: str = "squared",
) -> tuple[jax.Array, jax.Array]:
    """Full single-device DSGD training loop as ONE jitted computation.

    ``t0`` is the number of iterations already completed — segmented runs
    (checkpoint boundaries, utils.checkpoint) pass it so the η/√t schedule
    continues instead of restarting.

    ``loss`` is ``"squared"`` or ``"bpr"`` (``DSGDConfig`` checks the
    name). ``loss="bpr"`` runs the same blocking, schedule and block
    visits with the pairwise step of ``bpr_minibatch_update`` in the
    squared loss's place: ``sv`` and the omegas are not read, ``inv_cv``
    is not used (the item side counts its negatives as it draws them),
    and each visit draws its negatives from the real rows of the visited
    item block, ``[0, n_real[q])`` of block ``q``
    (``data.blocking.seen_rows_per_block``), so that the blocks of a
    stratum stay row-disjoint. Minibatch ``m`` of
    block ``p`` in stratum ``s`` at sweep ``t`` (global, ``t0`` counted)
    draws with ``neg_key`` folded with ``t``, ``s``, ``p`` and ``m``: a
    fresh draw each sweep, the same in any segmentation.

    ≙ the reference's cluster-wide bulk iteration
    ``union(userBlocks, itemBlocks).iterate(iterations * k)``
    (DSGDforMF.scala:337-344) driving ``updateFactors`` each superstep
    (:364-497). Superstep step_idx visits stratum ``step_idx mod k`` (the
    diagonal-rotation schedule is pre-baked into the stratum-major layout by
    ``data.blocking``); the effective iteration for LR decay is
    ``step_idx // k + 1`` (≙ superstep/numBlocks then +1,
    DSGDforMF.scala:383-386,476).

    On one device a sweep is ``k²`` block visits under one ``lax.scan``,
    strata ``s = 0..k-1`` and within a stratum blocks ``p = 0..k-1`` (the
    mesh ring's order, block for block). Block ``p``'s users are the
    contiguous rows
    ``[p·rpb_u, (p+1)·rpb_u)`` and its items in stratum ``s`` the rows of
    item block ``(p+s) mod k``, so a visit slices those two row ranges (and
    their omegas), runs ``sgd_block_sweep`` against them with block-local
    rows, and writes them back: the scatter-adds land in a table a ``k``-th
    as high as the whole one, which the TPU keeps in on-chip memory across
    the minibatch scan where the whole U does not fit (a ninth of the
    time a rating, PERF.md Findings, PR 32). Both blocking paths pad
    every block to whole minibatches and every table to ``k`` equal row
    blocks; a hand-built layout that breaks either raises ``ValueError``
    at trace time.

    bf16 factor storage (ISSUE 6, the ALX recipe): ``U``/``V`` may arrive
    as ``bfloat16`` tables — the whole sweep then runs on ONE f32 upcast
    of each table (gradient accumulation and duplicate-row scatter
    semantics stay exact f32) and the result is rounded back to the
    storage dtype on exit, all inside this jitted computation. The
    tables at rest (HBM between segments, checkpoints, host↔device
    transfers) are half-width. The upcast stays one per call, not one
    per block visit: rounding a block back after every visit would round
    each row ``k`` times a sweep, another arithmetic than this
    configuration's.
    """
    if loss == "bpr" and (n_real is None or neg_key is None):
        raise ValueError("loss='bpr' needs n_real and neg_key")
    store_dtype = U.dtype
    if store_dtype != jnp.float32:
        U = U.astype(jnp.float32)
        V = V.astype(jnp.float32)
    k = num_blocks
    b = su.shape[-1]
    (rows_u, rank), rows_v = U.shape, V.shape[0]
    if rows_u % k or rows_v % k:
        raise ValueError(
            f"table rows ({rows_u}, {rows_v}) must be divisible by "
            f"num_blocks={k} — use the data.blocking / "
            "data.device_blocking layouts")
    if b % minibatch:
        # a minibatch that spans two blocks would touch two row ranges
        raise ValueError(
            f"block size {b} must be a multiple of minibatch={minibatch} "
            "— block with minibatch_multiple=minibatch")
    rpb_u, rpb_v = rows_u // k, rows_v // k
    # global rows -> block-local, once per call. Weight-0 padding entries
    # carry global row 0, outside block p > 0: ``%`` lands them on local
    # row 0, where their exactly-zero deltas change nothing
    su_l = su % rpb_u
    si_l = si % rpb_v

    def visit(carry, v_idx):
        U, V = carry
        s = (v_idx // k) % k
        p = v_idx % k
        q = (p + s) % k
        t = v_idx // (k * k) + 1 + jnp.asarray(t0, jnp.int32)
        U_blk = jax.lax.dynamic_slice(U, (p * rpb_u, 0), (rpb_u, rank))
        V_blk = jax.lax.dynamic_slice(V, (q * rpb_v, 0), (rpb_v, rank))
        if loss == "bpr":
            key = neg_key
            for x in (t, s, p):
                key = jax.random.fold_in(key, x)
            U_blk, V_blk = bpr_block_sweep(
                U_blk, V_blk, su_l[s, p], si_l[s, p], sw[s, p], n_real[q],
                key, updater, t, minibatch, collision,
                None if inv_cu is None else inv_cu[s, p],
            )
        else:
            ou_blk = jax.lax.dynamic_slice(omega_u, (p * rpb_u,), (rpb_u,))
            ov_blk = jax.lax.dynamic_slice(omega_v, (q * rpb_v,), (rpb_v,))
            U_blk, V_blk = sgd_block_sweep(
                U_blk, V_blk,
                su_l[s, p], si_l[s, p], sv[s, p], sw[s, p],
                ou_blk, ov_blk,
                updater, t, minibatch, collision,
                None if inv_cu is None else inv_cu[s, p],
                None if inv_cv is None else inv_cv[s, p],
            )
        U = jax.lax.dynamic_update_slice(U, U_blk, (p * rpb_u, 0))
        V = jax.lax.dynamic_update_slice(V, V_blk, (q * rpb_v, 0))
        return (U, V), None

    (U, V), _ = jax.lax.scan(
        visit, (U, V), jnp.arange(iterations * k * k, dtype=jnp.int32)
    )
    if store_dtype != jnp.float32:
        U = U.astype(store_dtype)
        V = V.astype(store_dtype)
    return U, V


@partial(jax.jit, static_argnames=("updater", "minibatch", "iterations",
                                   "collision"))
def online_train(
    U: jax.Array,
    V: jax.Array,
    u_rows: jax.Array,  # int32[e], e divisible by minibatch
    i_rows: jax.Array,
    values: jax.Array,
    weights: jax.Array,
    *,
    updater: Any,
    minibatch: int,
    iterations: int = 1,
    collision: str = "mean",
    t0: jax.Array | int = 0,
) -> tuple[jax.Array, jax.Array]:
    """Online micro-batch update: sweep one micro-batch ``iterations`` times.

    ``t0`` lets callers that invoke this repeatedly (streaming drivers, PS
    epoch loops) advance a decaying learning-rate schedule across calls —
    async-PS convergence leans on η/√t decay exactly like the reference DSGD
    default (DSGDforMF.scala:118).

    ≙ the online inner loops — one ``nextFactors`` application per arriving
    rating (FlinkOnlineMF.scala:125-136; OnlineSpark.scala:76-78 runs exactly
    a 1-iteration DSGD over the micro-batch) — batched into minibatch chunks
    via ``lax.scan``. No omegas: the online paths use the plain ``SGDUpdater``
    rule (unregularized, FactorUpdater.scala:35-53); regularized updaters
    receive omega=None and fall back to plain λ. Sweep ``s`` (0-based) runs at
    schedule step ``t = t0 + s + 1`` (the same t convention as
    ``dsgd_train``), so decaying schedules advance per sweep within a call
    and across calls via ``t0``.
    """
    e = u_rows.shape[0]
    assert e % minibatch == 0, (
        f"batch size {e} not divisible by minibatch {minibatch}; pad with "
        f"weight-0 entries first"
    )

    def sweep(carry, t):
        U, V = carry
        U, V = sgd_block_sweep(
            U, V, u_rows, i_rows, values, weights, None, None,
            updater, t, minibatch, collision,
        )
        return (U, V), None

    (U, V), _ = jax.lax.scan(
        sweep, (U, V),
        jnp.asarray(t0, jnp.int32) + jnp.arange(1, iterations + 1,
                                                dtype=jnp.int32),
    )
    return U, V


# The same body with ``U`` and ``V`` DONATED: the outputs alias the inputs,
# the minibatch scan scatters into the tables where they lie, and a call
# moves the touched rows alone. ``online_train`` leaves its inputs alive,
# so XLA copies each whole table into its output once a call (7.27 GB for
# 1 MB of ratings at 2.5M + 1M rows of rank 512) and a caller's older
# reference stays valid; after this one the arrays passed in are dead.
# Same arithmetic, bit for bit (tests/test_online_reference.py).
# ``models/online.py`` runs this one alone: the live tables never leave
# ``data.tables.GrowableFactorTable``, whose ``updating`` yields them.
online_train_inplace = jax.jit(
    online_train.__wrapped__,
    static_argnames=("updater", "minibatch", "iterations", "collision"),
    donate_argnums=(0, 1))


def pad_minibatches(
    u_rows,
    i_rows,
    values,
    minibatch: int,
    buffers: dict | None = None,
):
    """Pad COO arrays to a power-of-2 number of ``minibatch``-sized chunks
    with weight-0 no-op entries — the divisibility contract of
    ``online_train``/``sgd_block_sweep``, shared by every micro-batch caller
    (streaming OnlineMF, the PS epoch loops, the PS online+batch combo).

    The pow2 bucket bounds the jitted kernel to O(log n) compiled shape
    variants on variable-size batches. ``buffers`` (optional dict keyed by
    padded length) reuses the four numpy staging arrays across calls —
    ONLY safe when the caller guarantees the previous dispatch that
    consumed them has completed: ``jnp.asarray`` zero-copy ALIASES
    aligned numpy buffers on the CPU backend, so refilling a reused
    buffer races an in-flight async kernel's read of it (measured as
    factor divergence under concurrent consumers, ISSUE 13 — the
    streaming ``partial_fit`` paths therefore allocate fresh). This
    hazard is mechanically enforced: graftlint rule ``buffer-aliasing``
    (tools/graftlint, docs/STATIC_ANALYSIS.md) flags any caller that
    passes ``buffers=`` and feeds the results to ``jnp.asarray``/
    ``jnp.frombuffer`` — as of ISSUE 15 no production caller does
    (``ps/mf.py``, ``ps/adaptive.py``, and both ``models/online.py``
    paths all allocate fresh staging per batch).
    Returns ``(ur, ir, vals, w)`` int32/int32/float32/float32 of the padded
    length.
    """
    import numpy as np

    from large_scale_recommendation_tpu.utils.shapes import next_pow2

    n = len(u_rows)
    n_mb = max(1, -(-n // minibatch))
    padded = next_pow2(n_mb) * minibatch  # pow2 minibatch-count buckets
    if buffers is not None:
        if padded not in buffers:
            buffers[padded] = (
                np.zeros(padded, np.int32), np.zeros(padded, np.int32),
                np.zeros(padded, np.float32), np.zeros(padded, np.float32),
            )
        ur, ir, vals_out, w = buffers[padded]
        ur[n:] = 0
        ir[n:] = 0
        vals_out[n:] = 0.0
        w[n:] = 0.0
    else:
        ur = np.zeros(padded, np.int32)
        ir = np.zeros(padded, np.int32)
        vals_out = np.zeros(padded, np.float32)
        w = np.zeros(padded, np.float32)
    ur[:n], ir[:n], vals_out[:n], w[:n] = u_rows, i_rows, values, 1.0
    return ur, ir, vals_out, w


def predict_rows(U: jax.Array, V: jax.Array, u_rows: jax.Array,
                 i_rows: jax.Array) -> jax.Array:
    """Batched score: r̂ = u·v. ≙ ``blas.ddot`` in predict
    (MatrixFactorization.scala:258-265), as one einsum. Gathered rows
    are upcast so bf16-stored tables score with f32 dot products."""
    return jnp.einsum("bk,bk->b", U[u_rows].astype(jnp.float32),
                      V[i_rows].astype(jnp.float32))


@jax.jit
def empirical_risk_rows(
    U: jax.Array,
    V: jax.Array,
    u_rows: jax.Array,
    i_rows: jax.Array,
    values: jax.Array,
    mask: jax.Array,
    lambda_: jax.Array,
) -> jax.Array:
    """Empirical risk, reference semantics: per labeled point
    residual² + λ(‖u‖² + ‖v‖²), summed
    (MatrixFactorization.scala:133-192 — the norms are added once per
    *rating occurrence*, not once per factor)."""
    u = U[u_rows].astype(jnp.float32)
    v = V[i_rows].astype(jnp.float32)
    res = values - jnp.einsum("bk,bk->b", u, v)
    per_point = res * res + lambda_ * (
        jnp.sum(u * u, axis=-1) + jnp.sum(v * v, axis=-1)
    )
    return jnp.sum(per_point * mask)


@jax.jit
def sse_rows(
    U: jax.Array,
    V: jax.Array,
    u_rows: jax.Array,
    i_rows: jax.Array,
    values: jax.Array,
    mask: jax.Array,
) -> jax.Array:
    """Masked sum of squared residuals (RMSE numerator)."""
    res = values - predict_rows(U, V, u_rows, i_rows)
    return jnp.sum(res * res * mask)
