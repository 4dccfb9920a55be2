"""Mesh-sharded top-K serving: recommend over an item-sharded catalog.

Pod-scale serving twin of ``utils.metrics.top_k_recommend`` (which is
itself ≙ MLlib ``MatrixFactorizationModel.recommendProducts`` — a
DRIVER-side loop in MLlib; the reference has no distributed serving at
all). Here the catalog side V is row-sharded over the device mesh and
each query chunk runs

    per shard:  scores [chunk, rows_per_shard] = U_chunk @ V_shardᵀ
                (one MXU matmul per shard, in parallel)
                + in-range exclusion scatter-min + local top-k
    collective: all_gather of the [chunk, k] candidate (value, row)
                pairs — k·n_dev candidates per query, a few KB riding
                ICI instead of the full score row
    merge:      top-k over the gathered candidates (exact: the global
                top-k is a subset of the per-shard top-ks)

Exact-equivalence contract: the merged result equals the single-device
``lax.top_k`` over the full catalog wherever scores are tie-free
(float ties can order differently across shard boundaries — same
caveat as any distributed top-k; pinned by tests against the
single-device path on tie-free workloads).

Catalogs are VERSIONED: ``shard_catalog`` stamps each build with a token
derived from the identity of the factor array (``catalog_version``), so
serving caches — ``MFModel._serving_catalogs``, the engine's bound
executables (``serving.engine``) — can detect a retrain swap with one
integer compare and refresh in O(1) instead of silently serving stale
factors. An opt-in bf16 catalog (``dtype="bfloat16"``) halves the HBM
footprint and the per-shard matmul/all_gather traffic; scores are still
accumulated in f32 (``preferred_element_type``) and the merge is f32
end-to-end.

Catalog shardings, the per-shard offset (``axis_index``) and the
candidate all_gather all resolve through the unified
``parallel.partitioner.Partitioner`` rules table (catalog = logical
``('items', 'rank')``, query chunks = replicated ``('queries',)``) —
this module constructs no ``NamedSharding`` of its own.
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
import time
import weakref
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh  # noqa: F401 — annotation surface

from large_scale_recommendation_tpu.obs.trace import get_tracer
from large_scale_recommendation_tpu.parallel.mesh import shard_map
from large_scale_recommendation_tpu.parallel.partitioner import (
    as_partitioner,
)
from large_scale_recommendation_tpu.utils.metrics import DEAD_SLOT_OFFSET


# --------------------------------------------------------------------------
# Catalog versioning
# --------------------------------------------------------------------------

_version_counter = itertools.count(1)
_versions_by_id: dict[int, int] = {}
_versions_lock = threading.Lock()  # serving + retrain threads both stamp


def catalog_version(V) -> int:
    """A token identifying THIS factor-array object.

    Stable while the array lives (repeated calls return the same token);
    a new array — the product of any retrain/swap, since jax arrays are
    immutable — gets a fresh token. Serving caches compare tokens to
    decide staleness, which turns "did the model change under me?" into
    one integer compare. Id reuse after garbage collection is handled by
    a weakref finalizer that retires the entry with the array."""
    key = id(V)
    with _versions_lock:
        tok = _versions_by_id.get(key)
        if tok is None:
            tok = next(_version_counter)
            try:
                weakref.finalize(V, _versions_by_id.pop, key, None)
            except TypeError:
                return tok  # not weakref-able: never memoized
            _versions_by_id[key] = tok
    return tok


# --------------------------------------------------------------------------
# Sharded catalog
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedCatalog:
    """A catalog prepared for mesh serving: the padded factor table and
    phantom/pad mask resident ON the mesh. Build once per (V, mesh,
    item_mask) via ``shard_catalog`` and reuse across requests — the
    per-call work then is one tiny query-chunk transfer + the candidate
    merge, not a full-catalog reshard. ``version`` is the
    ``catalog_version`` token of the source array at build time; caches
    holding this catalog compare it against the live model's token."""

    V_sh: jax.Array  # [n_dev·rpb, r] block-sharded, f32 or bf16
    w_sh: jax.Array  # [n_dev·rpb] -inf on mesh-pad rows, offset on masked
    n_rows: int  # real catalog height
    rows_per_shard: int
    mesh: Mesh
    version: int = 0
    dtype: str = "float32"

    def apply_delta(self, rows, values,
                    version: int | None = None) -> "ShardedCatalog":
        """Install ONLY the given catalog rows (the delta-swap half of
        the streaming ingest→serve handoff): scatter ``values`` (full
        precision; cast to the catalog dtype here, same as a build)
        into the sharded table and restamp the version. One device-side
        scatter — no host device_put of the full table, no mask/pad
        recompute, and the result is BIT-EQUIVALENT to rebuilding from
        the patched source table (test-pinned; the scatter output keeps
        the block sharding, re-pinned explicitly so the scoring step's
        executables see the exact same layout). Geometry must be
        unchanged — vocab growth is a full-rebuild event, callers
        (``ServingEngine.apply_delta``) fall back on shape mismatch.

        ``version`` defaults to a fresh ``catalog_version`` token of
        the new sharded array — pass the patched source table's token
        when you have one, so engine and quantized catalogs agree."""
        rows = np.asarray(rows)
        if len(rows) == 0:
            return dataclasses.replace(
                self, version=(catalog_version(self.V_sh)
                               if version is None else version))
        part = as_partitioner(self.mesh)
        vals = jnp.asarray(values).astype(self.V_sh.dtype)
        V_new = self.V_sh.at[jnp.asarray(rows)].set(vals)
        V_new = part.shard(V_new, "items", "rank")
        return dataclasses.replace(
            self, V_sh=V_new,
            version=(catalog_version(V_new) if version is None
                     else version))


def shard_catalog(V, mesh=None, item_mask=None,
                  dtype=None) -> ShardedCatalog:
    """Pad ``V`` to a mesh-divisible height and place it block-sharded.

    ``mesh`` may be a raw ``Mesh`` (legacy), a ``Partitioner``, or None
    (the default global partitioner); the catalog rows are the logical
    ``('items', 'rank')`` axes of the unified rules table.

    ``dtype`` (default f32) accepts ``"bfloat16"``/``jnp.bfloat16`` to
    store the catalog half-width: the per-shard matmul then reads bf16
    from HBM and the query chunks ride the ICI at half the bytes, while
    scores accumulate in f32 (see ``_mesh_topk_step``)."""
    part = as_partitioner(mesh)
    mesh = part.mesh
    cat_dtype = jnp.dtype(dtype or jnp.float32)
    part.require_rank_divisible(int(V.shape[1]), "shard_catalog")
    n_dev = part.num_blocks
    n_rows = int(V.shape[0])
    rpb = -(-n_rows // n_dev)
    item_w = np.zeros(n_dev * rpb, np.float32)
    if item_mask is not None:
        item_w[:n_rows][~np.asarray(item_mask)] = DEAD_SLOT_OFFSET
    # mesh-padding rows score -inf (below even excluded/masked slots):
    # they can still surface when k exceeds the real candidate supply,
    # so their indices are clamped to row 0 after the merge — the
    # single-device contract (rows are always valid table indices, dead
    # slots identified by score) must hold on the mesh path too
    item_w[n_rows:] = -np.inf
    version = catalog_version(V)
    V_dev = jnp.asarray(V)
    if V_dev.dtype != cat_dtype:  # cast BEFORE padding: the full-size
        V_dev = V_dev.astype(cat_dtype)  # intermediate is half-width
    V_pad = jnp.concatenate(
        [V_dev,
         jnp.zeros((n_dev * rpb - n_rows, V.shape[1]), cat_dtype)]
    ) if n_dev * rpb != n_rows else V_dev
    return ShardedCatalog(
        V_sh=part.shard(V_pad, "items", "rank"),
        w_sh=part.shard(item_w, "items"),
        n_rows=n_rows, rows_per_shard=rpb, mesh=mesh,
        version=version, dtype=cat_dtype.name)


# --------------------------------------------------------------------------
# Jitted scoring step (weak-keyed per-mesh executable cache)
# --------------------------------------------------------------------------

# The per-mesh executable cache {(k_local, k_out, rows_per_shard,
# donate): jitted step} rides ON the mesh object itself: the jitted
# steps close over the mesh, so any module-global container (the old
# lru_cache(32), ADVICE r5 — or even a WeakKeyDictionary, whose values
# would keep their keys reachable) roots the executables for the
# process lifetime. As a mesh attribute the cache is reachable ONLY
# through the mesh, so compiled executables are released exactly when
# the mesh is. (Current jax interns Mesh objects process-wide — equal
# meshes are the same object — which gives cross-callsite reuse for
# free but also makes the mesh itself immortal, so the per-mesh dict is
# additionally LRU-bounded: a long-lived service sweeping many distinct
# k values must not accumulate executables forever.)
_STEP_CACHE_ATTR = "_lsrt_topk_step_cache"
_STEP_CACHE_CAP = 32  # the bound the replaced lru_cache(32) provided
# one lock for all meshes' caches: the interned mesh is shared across
# every engine/model in the process (the replaced lru_cache was
# internally locked too, so unlocked mutation would be a regression)
_STEP_CACHE_LOCK = threading.Lock()


def _mesh_topk_step(mesh: Mesh, k_local: int, k_out: int,
                    rows_per_shard: int, donate: bool = False):
    """Jitted sharded scoring + local top-k + candidate merge.

    ``k_local`` candidates per shard (≤ rows_per_shard), ``k_out``
    merged results (≤ n_dev·k_local). The returned jitted function is
    dtype-polymorphic: a bf16 catalog simply traces a bf16 variant, with
    the score matmul pinned to f32 accumulation either way. With
    ``donate=True`` the per-call buffers (query chunk + exclusion
    triple) are donated — they are freshly built each call, so the
    device can reuse their pages for the outputs (not legal on CPU,
    where jax ignores donation with a warning, so callers gate it)."""
    key = (k_local, k_out, rows_per_shard, donate)
    with _STEP_CACHE_LOCK:
        per_mesh = getattr(mesh, _STEP_CACHE_ATTR, None)
        if per_mesh is None:
            per_mesh = {}
            setattr(mesh, _STEP_CACHE_ATTR, per_mesh)
        cached = per_mesh.pop(key, None)
        if cached is not None:
            per_mesh[key] = cached  # re-insert: dict order is LRU order
            return cached

    part = as_partitioner(mesh)
    axis = part.data_axis
    cat_spec = part.spec("items", "rank")
    # rank-sharded catalogs: each model-axis participant holds a column
    # slice of V; the score matmul becomes a PARTIAL contraction psummed
    # over 'model' before the per-shard top-k (the ISSUE 16 reduction
    # collective). model_parallel == 1 traces the exact historical kernel.
    model_axis = part.model_axis if part.model_parallel > 1 else None

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(part.spec("queries"), cat_spec, part.spec("items"),
                  part.spec(), part.spec(), part.spec()),
        out_specs=(part.spec("queries"), part.spec("queries")),
        # outputs are replicated BY the trailing all_gather+top_k merge;
        # the static VMA checker can't see through the axis_index-derived
        # shard offsets to infer it (the mesh==single parity tests pin
        # the actual equivalence)
        check_vma=False,
    )
    def step(U_chunk, V_l, item_w_l, excl_rows, excl_cols, excl_w):
        # locals arrive with the sharded axes already sliced away:
        # V_l [rpb, r/m], item_w_l [rpb]; U_chunk is replicated full-width
        if model_axis is not None:
            r_loc = V_l.shape[1]
            U_c = jax.lax.dynamic_slice_in_dim(
                U_chunk, jax.lax.axis_index(model_axis) * r_loc, r_loc, 1)
            scores = jax.lax.psum(
                jnp.dot(U_c, V_l.T, preferred_element_type=jnp.float32),
                model_axis)
        else:
            scores = jnp.dot(U_chunk, V_l.T,
                             preferred_element_type=jnp.float32)
        scores = scores + item_w_l[None, :]
        # exclusions carry GLOBAL item rows; this shard applies the ones
        # in its range (out-of-range → clamped index, +inf weight: no-op)
        base = jax.lax.axis_index(axis) * rows_per_shard
        local = excl_cols - base
        in_range = (local >= 0) & (local < rows_per_shard)
        local = jnp.clip(local, 0, rows_per_shard - 1)
        w = jnp.where(in_range, excl_w, jnp.inf)
        scores = scores.at[excl_rows, local].min(w)
        v_loc, r_loc = jax.lax.top_k(scores, k_local)
        r_glob = r_loc + base
        # candidates ride the ICI: [chunk, n_dev·k_local] after the gather
        v_all = jax.lax.all_gather(v_loc, axis, axis=1, tiled=True)
        r_all = jax.lax.all_gather(r_glob, axis, axis=1, tiled=True)
        v_top, pos = jax.lax.top_k(v_all, k_out)
        return v_top, jnp.take_along_axis(r_all, pos, axis=1)

    jitted = jax.jit(step, donate_argnums=(0, 3, 4, 5) if donate else ())
    with _STEP_CACHE_LOCK:
        existing = per_mesh.get(key)
        if existing is not None:  # a racing builder won: use its step
            return existing
        per_mesh[key] = jitted
        while len(per_mesh) > _STEP_CACHE_CAP:  # evict least-recent
            # graftlint: disable=lock-gap  (not stale state: per_mesh
            # is the cache CONTAINER, and the re-acquisition re-reads
            # it first — a racing builder's entry wins, never reverted)
            per_mesh.pop(next(iter(per_mesh)))
    return jitted


def mesh_supports_donation(mesh: Mesh) -> bool:
    """Buffer donation is a device-memory feature; XLA:CPU ignores it
    (with a warning per call), so the pipelined callers gate on this."""
    return all(d.platform != "cpu" for d in mesh.devices.flat)


def run_pipelined_topk(user_rows, *, k: int, k_out: int, n_rows: int,
                       slice_size: int, bucket_fn, score_chunk,
                       on_batch=None, seam=None):
    """The chunk-loop machinery shared by ``mesh_top_k_recommend`` and
    the serving engine: walk ``user_rows`` in ``slice_size`` slices,
    pad each to ``bucket_fn(len(slice))`` rows, score via
    ``score_chunk(cu_padded, c) -> (v_top, r_top)`` (an async device
    dispatch), and drain results ONE chunk behind the dispatch — so
    host-side work for chunk i+1 (exclusion building inside
    ``score_chunk``) overlaps device scoring of chunk i. Ends with the
    pad-row clamp: surfaced mesh-padding rows (index ≥ ``n_rows``)
    become row 0 / -inf, keeping the single-device contract (rows are
    always valid table indices, dead slots identified by score). ONE
    copy of the pipeline + clamp so the per-call path and the engine
    cannot drift. ``on_batch(bucket, c)`` observes each dispatched bucket
    and how many of its rows are real; each drain (the wait for the
    device and the copy back) is the seam
    ``serving/pipeline/drain``, opened through ``seam`` (default: the
    installed tracer's ``seam`` — ``obs.trace.SEAMS``).
    """
    seam = seam or get_tracer().seam
    n = len(user_rows)
    out_rows = np.zeros((n, k), np.int32)
    out_scores = np.full((n, k), -np.inf, np.float32)
    if n == 0:
        return out_rows, out_scores
    pending = None  # (c0, c, v_top, r_top) — one chunk in flight

    def drain(p):
        # pull first, clamp the pad rows host-side: slicing the device
        # array (pr[:pc]) dispatches dynamic_slice eagerly, which ships
        # its scalar start indices host->device and trips an armed
        # transfer guard
        p0, pc, pv, pr = p
        with seam("serving/pipeline/drain"):
            out_rows[p0:p0 + pc, :k_out] = np.asarray(pr)[:pc]
            out_scores[p0:p0 + pc, :k_out] = np.asarray(pv)[:pc]

    for c0 in range(0, n, slice_size):
        cu = user_rows[c0:c0 + slice_size]
        c = len(cu)
        bucket = bucket_fn(c)
        if c < bucket:
            cu = np.concatenate([cu, np.zeros(bucket - c, cu.dtype)])
        v_top, r_top = score_chunk(cu, c)
        if on_batch is not None:
            on_batch(bucket, c)
        if pending is not None:
            drain(pending)
        pending = (c0, c, v_top, r_top)
    drain(pending)
    pad_hits = out_rows >= n_rows  # surfaced mesh-padding rows
    out_rows[pad_hits] = 0
    out_scores[pad_hits] = -np.inf
    return out_rows, out_scores


def mesh_top_k_recommend(U, V, user_rows, k: int = 10,
                         train_u=None, train_i=None, chunk: int = 2048,
                         item_mask=None, mesh: Mesh | None = None,
                         catalog: ShardedCatalog | None = None):
    """Row-space mesh serving — same contract as
    ``utils.metrics.top_k_recommend`` (inputs are row indices, returns
    ``(top_rows int32 [n, k], top_scores f32 [n, k])``), with the
    catalog sharded over ``mesh`` and scored in parallel.

    Pass a prebuilt ``catalog`` (``shard_catalog``) to amortize the
    full-catalog reshard across requests — a serving loop should; with
    only ``V``/``mesh``/``item_mask`` the catalog is built per call
    (``V`` may then be padded to a mesh-divisible height internally).

    The chunk loop runs two deep: while the device scores chunk i, the
    host builds chunk i+1's exclusion triple and drains chunk i-1's
    results — jax dispatch is async, so the host-side exclusion work
    overlaps device scoring instead of serializing with it.
    """
    from large_scale_recommendation_tpu.utils.metrics import (
        _exclusion_builder,
    )
    from large_scale_recommendation_tpu.utils.shapes import pow2_pad

    # version-keyed outcome attribution (obs.budget): the bare mesh
    # serving path has no engine flush to note for it, so the call
    # itself lands its wall in the cohort of the catalog version that
    # scored it. One `is not None` test when the plane is off — no
    # clock reads on the null path. The request plane (obs.requests)
    # mirrors the seam: the call is noted as a one-request flush whose
    # stage ledger marks the stages the engine's seams do (the drain
    # seam closes into it; the residual lands in topk_merge — the pad
    # clamp runs after the final drain).
    from large_scale_recommendation_tpu.obs.budget import get_budget
    from large_scale_recommendation_tpu.obs.requests import get_requests

    budget = get_budget()
    rt = get_requests()
    t_serve = (time.perf_counter()
               if budget is not None or rt is not None else 0.0)
    led = rt.ledger(t_serve) if rt is not None else None

    if catalog is None:
        catalog = shard_catalog(V, mesh, item_mask)
    mesh = catalog.mesh
    n_dev = as_partitioner(mesh).num_blocks
    n_rows, rpb = catalog.n_rows, catalog.rows_per_shard
    V_sh, w_sh = catalog.V_sh, catalog.w_sh
    user_rows = np.asarray(user_rows)
    n = len(user_rows)
    if n == 0:
        return (np.zeros((0, k), np.int32), np.zeros((0, k), np.float32))

    k_local = min(k, rpb)  # per-shard top_k bound
    k_out = min(k, n_dev * k_local)  # merged width
    build_excl = _exclusion_builder(train_u, train_i, int(U.shape[0]))
    step = _mesh_topk_step(mesh, k_local, k_out, rpb,
                           donate=mesh_supports_donation(mesh))
    U_dev = jnp.asarray(U)  # row gathers stay on device per chunk
    cat_dtype = jnp.dtype(catalog.dtype)

    def score_chunk(cu, c):
        excl_rows, excl_cols, excl_w = build_excl(cu, c)
        if led is not None:
            led.mark("batch_form")  # exclusion build
        U_chunk = U_dev[jnp.asarray(cu)]
        if U_chunk.dtype != cat_dtype:
            U_chunk = U_chunk.astype(cat_dtype)
        if led is not None:
            led.mark("gather")
        out = step(U_chunk, V_sh, w_sh,
                   jnp.asarray(excl_rows), jnp.asarray(excl_cols),
                   jnp.asarray(excl_w))
        if led is not None:
            led.mark("score_stage1")  # one fused dispatch: stage 1
        return out

    chunk = min(chunk, pow2_pad(n))
    out = run_pipelined_topk(
        user_rows, k=k, k_out=k_out, n_rows=n_rows, slice_size=chunk,
        bucket_fn=lambda c: chunk, score_chunk=score_chunk,
        seam=(None if led is None
              else partial(get_tracer().seam, sink=led.on_seam)))
    if budget is not None or led is not None:
        t_end = time.perf_counter()  # ONE read shared by both planes
        if budget is not None:
            budget.note_result(catalog.version, t_end - t_serve)
        if rt is not None and led is not None:
            rt.note_flush(led, t_end, (t_serve,),
                          version=catalog.version, rows=(n,),
                          residual_stage="topk_merge")
    return out
