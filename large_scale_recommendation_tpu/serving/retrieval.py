"""Approximate retrieval fast path: int8 score-then-rescore top-K.

``parallel.serving`` scores the **full catalog exactly** for every
request bucket — per-request cost grows linearly with items, which is
exactly where "millions of users" dies at the serving tier (ROADMAP
item 3; FLAME, arxiv 2509.22681, frames the milestone as sustaining
heavy *mixed* traffic within latency SLOs, not batch throughput). This
module is the two-stage alternative, the serving half of the ALX
quantized-storage/f32-accumulate recipe the training tier already runs
(PR 6's bf16 factors):

- **stage 1 (cheap, approximate)** — score an int8-quantized catalog
  (per-row symmetric scale: ``q = round(V / scale)``, ``scale =
  max|row| / 127``) with an int8×int8→int32 matmul and keep the top
  ``k · overfetch`` candidates. Optionally the catalog is organized
  into a k-means-clustered MIPS index (IVF layout: rows grouped into
  per-cluster slabs, queries routed to their top-``n_probe`` clusters
  by centroid inner product) so stage 1 touches ``n_probe / n_clusters``
  of the catalog instead of all of it — the per-request cost stops
  scaling with the catalog.
- **stage 2 (exact)** — gather the candidates' full-precision rows and
  rescore them in f32 (one ``[bucket, kc, rank]`` einsum), apply the
  train-seen exclusions exactly, and return the top-k. Every returned
  score is the EXACT f32 score of that item — approximation only
  affects which ~``k·overfetch`` items were considered, measured as
  recall@k against the exact path (``recall_at_k``; target ≥ 0.95 at
  overfetch 4, test-pinned).

A ``stage1_only`` mode skips the rescore and returns the dequantized
approximate scores — the *degraded* operating point the admission
controller (``serving.admission``) falls back to under SLO burn.

Exclusion semantics match the exact path: the flat stage-1 kernel
scatter-mins the same ``(rows, cols, w)`` triple ``_exclusion_builder``
produces; stage 2 re-applies exclusions as a sorted-key membership test
over the candidate set (an excluded candidate's score is forced to
``DEAD_SLOT_OFFSET``, below ``DEAD_SLOT_THRESHOLD`` — the shared
dead-slot sentinel contract). Masked (phantom) rows carry the same
additive ``item_w`` offset as the exact catalogs.

Everything here is single-HOST; within the host the catalog is either a
plain replicated device array (int8 makes a 1M×128 catalog ~128 MB —
far below one chip's HBM) or, given a ``Partitioner`` with
``model_parallel > 1``, RANK-SHARDED: the int8 codes (flat ``q``,
clustered ``slab_q``/``ovf_q``) and the f32 rescore table live as
column slices over the ``'model'`` mesh axis, so catalog bytes per
device scale down with the model size (ISSUE 16). The stage kernels
stay unchanged — GSPMD partitions the jitted contractions over the
sharded rank dimension and inserts the all-reduce the partial dots
need (int32 partial sums reduce EXACTLY; the f32 stage-2 rescore and
the clustered f32 einsum carry only reduction-reordering error).
Per-row scales are computed on FULL rows before sharding, so the int8
codes are identical at every model size.
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from large_scale_recommendation_tpu.obs.registry import get_registry
from large_scale_recommendation_tpu.obs.trace import get_tracer
from large_scale_recommendation_tpu.parallel.serving import catalog_version
from large_scale_recommendation_tpu.utils.metrics import DEAD_SLOT_OFFSET
from large_scale_recommendation_tpu.utils.shapes import pow2_pad


@dataclasses.dataclass(frozen=True)
class RetrievalConfig:
    """Fast-path knobs.

    ``overfetch`` sets the stage-1 candidate budget (``k · overfetch``,
    clamped to the catalog); 4 is the recall≥0.95 operating point the
    tests pin. ``n_clusters=None`` scores the whole int8 catalog flat
    (bandwidth win only — right up to ~100k items); an integer opts
    into the clustered MIPS index (compute win: stage 1 touches
    ``n_probe`` clusters per query). ``spill`` pads each cluster slab
    to ``pow2_pad(max cluster size)`` — k-means imbalance costs memory,
    never correctness (every row is in exactly one slab).
    ``max_bucket`` caps the fast path's micro-batch slice: the clustered
    gather materializes ``[bucket, slab, rank]`` per probe, so the
    bucket — not the catalog — bounds stage-1 memory."""

    overfetch: int = 4
    n_clusters: int | None = None
    n_probe: int = 8
    kmeans_iters: int = 5
    kmeans_sample: int = 65536
    slab_slack: float = 2.0
    spill_choices: int = 4
    max_bucket: int = 256
    seed: int = 0

    def __post_init__(self):
        if self.overfetch < 1:
            raise ValueError(f"overfetch must be >= 1, got {self.overfetch}")
        if self.n_clusters is not None and self.n_clusters < 2:
            raise ValueError(f"n_clusters must be >= 2, "
                             f"got {self.n_clusters}")
        if self.n_probe < 1:
            raise ValueError(f"n_probe must be >= 1, got {self.n_probe}")
        if self.slab_slack < 1.0:
            raise ValueError(f"slab_slack must be >= 1, "
                             f"got {self.slab_slack}")
        if self.spill_choices < 1:
            raise ValueError(f"spill_choices must be >= 1, "
                             f"got {self.spill_choices}")


# --------------------------------------------------------------------------
# int8 per-row quantization (the ALX storage recipe, serving half)
# --------------------------------------------------------------------------


@jax.jit
def _quantize_rows(X):
    """Per-row symmetric int8: ``scale = max|row| / 127`` (all-zero rows
    get scale 1 so dequantization is exact), ``q = round(X / scale)``.
    Round-trip error is ≤ ``scale / 2`` per element — test-pinned."""
    X = X.astype(jnp.float32)
    amax = jnp.max(jnp.abs(X), axis=1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(X / scale[:, None]), -127, 127).astype(jnp.int8)
    return q, scale


def quantize_rows(X) -> tuple[jax.Array, jax.Array]:
    """Public form of the per-row int8 quantizer: ``(q int8 [n, r],
    scale f32 [n])`` with ``dequant = q * scale[:, None]``."""
    return _quantize_rows(jnp.asarray(X))


def dequantize_rows(q, scale) -> jax.Array:
    return q.astype(jnp.float32) * scale[:, None]


# --------------------------------------------------------------------------
# k-means MIPS index build (host-side; assignment via chunked matmuls)
# --------------------------------------------------------------------------


def _augment(V: np.ndarray) -> np.ndarray:
    """MIPS→NN reduction (Bachrach et al. 2014): append
    ``sqrt(max_norm² − ‖v‖²)`` so Euclidean k-means groups items by the
    direction+norm structure inner-product search actually cares about
    (raw Euclidean clustering under-weights the norm component)."""
    norms2 = np.sum(V * V, axis=1)
    pad = np.sqrt(np.maximum(norms2.max() - norms2, 0.0))
    return np.concatenate([V, pad[:, None]], axis=1).astype(np.float32)


def _assign(X: np.ndarray, centroids: np.ndarray, top: int = 1,
            chunk: int = 16384) -> np.ndarray:
    """Per row, the ``top`` nearest centroids by Euclidean distance
    (argmin ‖x − c‖² = argmax (x·c − ‖c‖²/2)), chunked matmuls so a
    1M-row assignment never materializes [n, C] at once. Returns
    ``[n]`` for ``top=1``, else ``[n, top]`` best-first."""
    half = jnp.asarray(0.5 * np.sum(centroids * centroids, axis=1))
    C_dev = jnp.asarray(centroids.T)
    top = min(top, len(centroids))
    out = np.empty((len(X), top), np.int32)
    for c0 in range(0, len(X), chunk):
        sl = jnp.asarray(X[c0:c0 + chunk])
        scores = jnp.dot(sl, C_dev) - half[None, :]
        _, idx = jax.lax.top_k(scores, top)
        out[c0:c0 + len(idx)] = np.asarray(idx)
    return out[:, 0] if top == 1 else out


def _capacity_assign(choices: np.ndarray, cap: int, n_clusters: int
                     ) -> tuple[np.ndarray, np.ndarray]:
    """Greedy capacity-capped assignment: every row tries its ranked
    cluster choices in order; a cluster accepts rows only up to ``cap``.
    Rows exhausting their choices land in the OVERFLOW set (scored on
    every probe downstream, so spilling costs compute, never recall).
    Capacity capping is what makes the probed volume ``n_probe · cap``
    a real bound — uncapped k-means slabs pad to the LARGEST cluster,
    and one hot cluster then inflates every probe (measured: a 7×
    imbalance turned the fast path 4× SLOWER than exact). Vectorized
    per choice rank: rows are ranked within each cluster's applicant
    pool and accepted while capacity remains."""
    n, n_choices = choices.shape
    assign = np.full(n, -1, np.int32)
    used = np.zeros(n_clusters, np.int64)
    remaining = np.arange(n)
    for level in range(n_choices):
        if not len(remaining):
            break
        c = choices[remaining, level]
        order = np.argsort(c, kind="stable")
        cs = c[order]
        starts = np.searchsorted(cs, np.arange(n_clusters))
        rank = np.arange(len(cs)) - starts[cs]
        ok = rank < (cap - used[cs])
        accepted = order[ok]
        assign[remaining[accepted]] = cs[ok]
        used += np.bincount(cs[ok], minlength=n_clusters)
        remaining = remaining[order[~ok]]
    return assign, remaining


def kmeans_fit(V: np.ndarray, n_clusters: int, iters: int = 5,
               sample: int = 65536, seed: int = 0, cap: int | None = None,
               spill_choices: int = 4
               ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Fit centroids on a subsample (Lloyd iterations), then
    capacity-capped-assign EVERY row — the standard IVF build split:
    fitting is O(sample·C) per iteration, the one full pass is
    assignment only. Returns ``(assignment int32 [n] (−1 = overflow),
    overflow row indices, routing centroids f32 [C, rank])`` — routing
    centroids are the mean RAW member vectors (queries route by inner
    product against them). Clustering runs in MIPS-augmented space
    (``_augment``) so direction AND norm structure separate."""
    n, r = V.shape
    rng = np.random.default_rng(seed)
    aug = _augment(np.asarray(V, np.float32))
    fit_idx = (rng.choice(n, size=sample, replace=False)
               if n > sample else np.arange(n))
    X = aug[fit_idx]
    centroids = X[rng.choice(len(X), size=n_clusters, replace=False)]
    for _ in range(max(1, iters)):
        a = _assign(X, centroids)
        counts = np.bincount(a, minlength=n_clusters)
        sums = np.zeros_like(centroids)
        np.add.at(sums, a, X)
        nonempty = counts > 0
        centroids[nonempty] = (sums[nonempty]
                               / counts[nonempty][:, None])
        # dead centroids: reseed from random points so every slab can
        # fill (an empty cluster wastes a probe slot forever otherwise)
        n_dead = int((~nonempty).sum())
        if n_dead:
            centroids[~nonempty] = X[rng.choice(len(X), size=n_dead)]
    if cap is None:
        cap = n  # uncapped: single-choice argmax, no overflow
    choices = _assign(aug, centroids, top=max(1, spill_choices))
    if choices.ndim == 1:
        choices = choices[:, None]
    assignment, overflow = _capacity_assign(choices, cap, n_clusters)
    route = np.zeros((n_clusters, r), np.float32)
    placed = assignment >= 0
    counts = np.bincount(assignment[placed], minlength=n_clusters)
    np.add.at(route, assignment[placed], np.asarray(V, np.float32)[placed])
    route[counts > 0] /= counts[counts > 0][:, None]
    return assignment, overflow, route


# --------------------------------------------------------------------------
# Quantized catalog (flat or clustered slabs) + delta re-quantization
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class QuantizedCatalog:
    """The stage-1 scoring structure: an int8 catalog with per-row
    scales, either flat (``q``/``scale``) or grouped into clustered
    slabs (``slab_q [C, m, r]`` etc.; ``pos_of_row`` maps a global row
    to its flat slab position so a delta can re-quantize ONLY dirty
    rows in place). ``item_w`` is the additive phantom/mask offset the
    exact catalogs carry too; slab pad slots hold ``-inf`` weight and
    row id ``n_rows`` (clamped downstream, same as mesh padding).

    ``version`` is the ``catalog_version`` token of the source factor
    array — the same token the engine's exact catalog carries, so one
    integer compare answers "are these two builds of the same swap?".
    """

    n_rows: int
    rank: int
    version: int
    item_w: jax.Array  # [n] 0 real / DEAD_SLOT_OFFSET masked
    # flat layout (None in clustered mode)
    q: jax.Array | None = None  # int8 [n, r]
    scale: jax.Array | None = None  # f32 [n]
    # clustered layout (None in flat mode). Slabs are CAPACITY-CAPPED
    # (``slab_slack × n/C`` rows, pow2-padded); rows spilling every
    # ranked choice live in the overflow block, scored on EVERY probe.
    centroids: jax.Array | None = None  # f32 [C, r] (routing)
    slab_q: jax.Array | None = None  # int8 [C, m, r]
    slab_scale: jax.Array | None = None  # f32 [C, m]
    slab_w: jax.Array | None = None  # f32 [C, m] (item_w; -inf pads)
    slab_rows: jax.Array | None = None  # int32 [C, m] (n_rows pads)
    ovf_q: jax.Array | None = None  # int8 [O, r]
    ovf_scale: jax.Array | None = None  # f32 [O]
    ovf_w: jax.Array | None = None  # f32 [O] (-inf pads)
    ovf_rows: jax.Array | None = None  # int32 [O] (n_rows pads)
    pos_of_row: np.ndarray | None = None  # int64 [n]: c·m+slot | C·m+j
    stats: dict = dataclasses.field(default_factory=dict)
    # rank-sharded builds carry their Partitioner so delta patches can
    # re-pin layouts; None = single-device replicated (the historical
    # layout, byte-identical arrays)
    partitioner: object | None = None

    # every array field that counts toward the catalog footprint
    _ARRAY_FIELDS = ("q", "scale", "centroids", "slab_q", "slab_scale",
                     "slab_w", "slab_rows", "ovf_q", "ovf_scale", "ovf_w",
                     "ovf_rows", "item_w")

    @property
    def clustered(self) -> bool:
        return self.slab_q is not None

    def nbytes(self) -> int:
        total = 0
        for f in self._ARRAY_FIELDS:
            arr = getattr(self, f)
            if arr is not None:
                total += arr.size * arr.dtype.itemsize
        return int(total)

    def nbytes_per_device(self) -> int:
        """Catalog bytes RESIDENT PER DEVICE — the number the ISSUE 16
        footprint acceptance reads. Rank-sharded builds hold only a
        column slice of the int8 codes per device (replicated scales/
        routing metadata count at full size on every device); the
        replicated build returns ``nbytes()``. Measured from the actual
        addressable shards, not modeled, so layout drift shows up."""
        per_dev: dict = {}
        for f in self._ARRAY_FIELDS:
            arr = getattr(self, f)
            if arr is None:
                continue
            shards = getattr(arr, "addressable_shards", None)
            if shards:
                for s in shards:
                    per_dev[s.device] = (per_dev.get(s.device, 0)
                                         + int(s.data.size
                                               * s.data.dtype.itemsize))
            else:
                per_dev[None] = (per_dev.get(None, 0)
                                 + int(arr.size * arr.dtype.itemsize))
        if not per_dev:
            return 0
        # single-device arrays (key None / one device) plus the max over
        # mesh devices: the bound a capacity plan must honor
        return int(max(per_dev.values()))

    def apply_delta(self, rows, values, version: int) -> "QuantizedCatalog":
        """Re-quantize ONLY the given rows (new full-precision
        ``values``) and scatter them into the layout. Per-row
        quantization is deterministic, so the flat result is
        BIT-EQUIVALENT to a full rebuild from the patched table
        (test-pinned). Clustered mode keeps each row's cluster
        assignment — re-clustering is a full-rebuild concern; routing
        quality degrades only as rows drift far from their centroid."""
        rows = np.asarray(rows)
        if len(rows) == 0:
            return dataclasses.replace(self, version=version)
        q_new, s_new = _quantize_rows(jnp.asarray(values))
        part = self.partitioner
        if part is not None:
            # rank-sharded layout: the fresh codes are quantized on FULL
            # rows (identical codes at any model size), replicated onto
            # the mesh, and each scatter below re-pins to the original
            # sharding — so only the owning shard's column slice of the
            # dirty rows actually changes on each device
            q_new = part.shard(q_new)
            s_new = part.shard(s_new)

        def repin(name, new):
            # scatter outputs must keep the exact build-time layout so
            # the stage kernels' compiled executables see the same
            # shardings (replicated builds: no-op)
            if part is None:
                return new
            return jax.device_put(new, getattr(self, name).sharding)

        patch: dict = {"version": version}
        if self.q is not None:
            idx = jnp.asarray(rows)
            patch["q"] = repin("q", self.q.at[idx].set(q_new))
            patch["scale"] = repin("scale", self.scale.at[idx].set(s_new))
        if self.clustered:
            C, m, r = self.slab_q.shape
            pos = self.pos_of_row[rows]
            in_slab = pos < C * m
            if in_slab.any():
                sp = jnp.asarray(pos[in_slab])
                qs, ss = q_new[jnp.asarray(in_slab)], s_new[
                    jnp.asarray(in_slab)]
                patch["slab_q"] = repin("slab_q", self.slab_q.reshape(
                    C * m, r).at[sp].set(qs).reshape(C, m, r))
                patch["slab_scale"] = repin(
                    "slab_scale", self.slab_scale.reshape(
                        C * m).at[sp].set(ss).reshape(C, m))
            in_ovf = ~in_slab
            if in_ovf.any():
                op = jnp.asarray(pos[in_ovf] - C * m)
                patch["ovf_q"] = repin("ovf_q", self.ovf_q.at[op].set(
                    q_new[jnp.asarray(in_ovf)]))
                patch["ovf_scale"] = repin(
                    "ovf_scale", self.ovf_scale.at[op].set(
                        s_new[jnp.asarray(in_ovf)]))
        return dataclasses.replace(self, **patch)


def _rank_shard_partitioner(partitioner):
    """The builder's gate: a Partitioner with ``model_parallel > 1``
    opts the catalog into the rank-sharded layout; anything else (None,
    or a model=1 mesh) keeps the historical single-device arrays —
    byte-identical, nothing placed on a mesh."""
    if partitioner is None or partitioner.model_parallel <= 1:
        return None
    return partitioner


def _shard_quantized(cat: QuantizedCatalog, part) -> QuantizedCatalog:
    """Place a built catalog rank-sharded: int8 code tables (and only
    them — scales, routing centroids, weights and row maps replicate;
    they are O(n), not O(n·r)) split by COLUMN over the ``'model'``
    axis. Codes were quantized on full rows before this, so the shards
    concatenate back to the exact replicated catalog."""
    patch: dict = {"partitioner": part}
    if cat.q is not None:
        patch["q"] = part.shard(cat.q, None, "rank")
        patch["scale"] = part.shard(cat.scale)
    patch["item_w"] = part.shard(cat.item_w)
    if cat.clustered:
        patch["centroids"] = part.shard(cat.centroids)
        patch["slab_q"] = part.shard(cat.slab_q, None, None, "rank")
        patch["slab_scale"] = part.shard(cat.slab_scale)
        patch["slab_w"] = part.shard(cat.slab_w)
        patch["slab_rows"] = part.shard(cat.slab_rows)
        patch["ovf_q"] = part.shard(cat.ovf_q, None, "rank")
        patch["ovf_scale"] = part.shard(cat.ovf_scale)
        patch["ovf_w"] = part.shard(cat.ovf_w)
        patch["ovf_rows"] = part.shard(cat.ovf_rows)
    out = dataclasses.replace(cat, **patch)
    cat.stats.update(rank_sharded=int(part.model_parallel),
                     bytes_per_device=out.nbytes_per_device())
    return out


def build_quantized_catalog(V, item_mask=None,
                            config: RetrievalConfig | None = None,
                            version: int | None = None,
                            partitioner=None,
                            ) -> QuantizedCatalog:
    """Quantize ``V`` and (optionally) build the clustered MIPS layout.
    ``item_mask`` follows the ``shard_catalog`` contract (True = real
    item; masked rows score ``DEAD_SLOT_OFFSET`` additively).
    ``partitioner`` with ``model_parallel > 1`` rank-shards the int8
    code tables over the ``'model'`` mesh axis (see module docstring);
    otherwise the historical replicated layout is returned unchanged."""
    cfg = config or RetrievalConfig()
    part = _rank_shard_partitioner(partitioner)
    if part is not None:
        part.require_rank_divisible(int(np.shape(V)[1]),
                                    "build_quantized_catalog")
    t0 = time.perf_counter()
    version = catalog_version(V) if version is None else version
    V_host = np.asarray(V, np.float32)
    n, r = V_host.shape
    item_w = np.zeros(n, np.float32)
    if item_mask is not None:
        item_w[~np.asarray(item_mask)] = DEAD_SLOT_OFFSET
    q_dev, s_dev = _quantize_rows(jnp.asarray(V_host))
    stats = {"n_rows": n, "rank": r, "mode": "flat"}
    if cfg.n_clusters is None:
        cat = QuantizedCatalog(
            n_rows=n, rank=r, version=version,
            item_w=jnp.asarray(item_w), q=q_dev, scale=s_dev, stats=stats)
        if part is not None:
            cat = _shard_quantized(cat, part)
        stats["build_s"] = round(time.perf_counter() - t0, 3)
        stats["bytes"] = cat.nbytes()
        return cat

    C = min(cfg.n_clusters, n)
    # capacity-capped slabs: m = pow2(slack · mean cluster) bounds the
    # probed volume at n_probe·m rows REGARDLESS of k-means imbalance
    m = pow2_pad(max(1, int(np.ceil(cfg.slab_slack * n / C))))
    assignment, overflow, route = kmeans_fit(
        V_host, C, iters=cfg.kmeans_iters, sample=cfg.kmeans_sample,
        seed=cfg.seed, cap=m, spill_choices=cfg.spill_choices)
    placed = assignment >= 0
    counts = np.bincount(assignment[placed], minlength=C)
    # slab fill, vectorized: placed rows sorted by cluster; each row's
    # slot is its rank within the cluster (< m by the capacity cap)
    placed_rows = np.nonzero(placed)[0]
    order = placed_rows[np.argsort(assignment[placed_rows],
                                   kind="stable")]
    starts = np.zeros(C + 1, np.int64)
    np.cumsum(counts, out=starts[1:])
    slot = (np.arange(len(order), dtype=np.int64)
            - starts[assignment[order]])
    pos_of_row = np.empty(n, np.int64)
    pos_of_row[order] = assignment[order].astype(np.int64) * m + slot
    O = pow2_pad(max(len(overflow), 1), 8)
    pos_of_row[overflow] = C * m + np.arange(len(overflow))
    q_host = np.asarray(q_dev)
    s_host = np.asarray(s_dev)
    slab_q = np.zeros((C * m + O, r), np.int8)
    slab_scale = np.zeros(C * m + O, np.float32)
    slab_w = np.full(C * m + O, -np.inf, np.float32)  # pads: -inf
    slab_rows = np.full(C * m + O, n, np.int32)  # pads: clamped later
    slab_q[pos_of_row] = q_host
    slab_scale[pos_of_row] = s_host
    slab_w[pos_of_row] = item_w
    slab_rows[pos_of_row] = np.arange(n, dtype=np.int32)
    stats.update(mode="clustered", n_clusters=int(C), slab_size=int(m),
                 capacity_cap=int(m), overflow_rows=int(len(overflow)),
                 max_cluster=int(counts.max()),
                 mean_cluster=float(counts.mean()),
                 empty_clusters=int((counts == 0).sum()),
                 n_probe=int(min(cfg.n_probe, C)))
    cat = QuantizedCatalog(
        n_rows=n, rank=r, version=version, item_w=jnp.asarray(item_w),
        centroids=jnp.asarray(route),
        slab_q=jnp.asarray(slab_q[:C * m].reshape(C, m, r)),
        slab_scale=jnp.asarray(slab_scale[:C * m].reshape(C, m)),
        slab_w=jnp.asarray(slab_w[:C * m].reshape(C, m)),
        slab_rows=jnp.asarray(slab_rows[:C * m].reshape(C, m)),
        ovf_q=jnp.asarray(slab_q[C * m:]),
        ovf_scale=jnp.asarray(slab_scale[C * m:]),
        ovf_w=jnp.asarray(slab_w[C * m:]),
        ovf_rows=jnp.asarray(slab_rows[C * m:]),
        pos_of_row=pos_of_row, stats=stats)
    if part is not None:
        cat = _shard_quantized(cat, part)
    stats["build_s"] = round(time.perf_counter() - t0, 3)
    stats["bytes"] = cat.nbytes()
    return cat


# --------------------------------------------------------------------------
# Jitted stages
# --------------------------------------------------------------------------


_LANES = 128  # columns of one TPU tile: the least group, one lane row
_SUBLANES = 8  # float32 rows of one TPU tile


def select_groups(n: int, k: int) -> tuple[int, int] | None:
    """The shape rule of ``exact_top_k``: ``(S, G)``, ``G`` contiguous
    groups of ``S`` scores a row, or ``None`` where the row goes to
    ``lax.top_k`` whole.

    The two levels read ``G`` group maxima and ``k * S`` gathered
    scores where the whole-row top-k reads ``n``; ``G + k * S`` is
    least at ``S = sqrt(n / k)``, rounded up here to a power-of-two
    number of lane rows. Below ``G = 4 * k`` (a catalog of some 20K
    rows at ``k = 40``) the detour's two small top-ks and its gather
    cost what the one whole-row top-k costs (0.5 ms each on a v5e:
    PERF.md, Findings, PR 30), so the row stays whole."""
    S = _LANES
    while S * S * k < n:
        S *= 2
    G = -(-n // S)
    return (S, G) if G >= 4 * k else None


def exact_top_k(scores, k: int):
    """``lax.top_k(scores, k)`` of ``f32[b, n]`` rows, values and
    positions, ties included, without a top-k over ``n`` scores a row
    where ``select_groups`` says the detour pays:

    1. the row is ``G`` contiguous groups of ``S`` scores (the tail
       padded with ``-inf``); each group's maximum, ``[b, G]``;
    2. ``lax.top_k`` of the maxima, ``k`` groups a row, their ids sorted
       ascending;
    3. those groups gathered as ``b * k`` whole rows of ``S`` floats
       (never per element) and ``lax.top_k`` of the ``k * S`` scores;
    4. positions mapped back, ``gid * S + offset``.

    Exact: ``lax.top_k`` orders by (value descending, position
    ascending). An element outside the ``k`` groups that lead by
    (maximum descending, id ascending) has ``k`` elements ahead of it in
    that order, one in each leading group — a greater maximum, or an
    equal one in an earlier, hence lower-positioned, group — so it is
    not among the top ``k``; and since the gathered groups are in id
    order, position order inside the candidates is position order in
    the row, so step 3 breaks ties as the whole-row top-k would. A
    ``-inf`` pad is the row's last position and is chosen only after
    every real score.

    Both reads of the ``[b, n]`` matrix go through one view, ``[b / 8,
    G, 8, S]``: the order in which a TPU holds ``f32[b, n]`` (tiles of 8
    rows by 128 columns, row-tile major), so that view costs nothing
    there, where ``scores.reshape(b, G, S)`` is a copy of the whole
    matrix; the maxima reduce over its last axis and the gather takes
    whole rows of its ``[b * G, S]`` flattening. A ``b`` that 8 does not
    divide takes the same steps over ``[b, G, 1, S]``."""
    b, n = scores.shape
    groups = select_groups(n, k)
    if groups is None:
        with jax.named_scope("stage1/top_k"):
            return jax.lax.top_k(scores, k)
    S, G = groups
    if G * S > n:
        scores = jnp.pad(scores, ((0, 0), (0, G * S - n)),
                         constant_values=-jnp.inf)
    r = _SUBLANES if b % _SUBLANES == 0 else 1
    tiles = scores.reshape(b // r, r, G, S).transpose(0, 2, 1, 3)
    with jax.named_scope("stage1/group_max"):
        gmax = tiles.max(axis=-1).transpose(0, 2, 1).reshape(b, G)
    with jax.named_scope("stage1/group_top_k"):
        _, gid = jax.lax.top_k(gmax, k)
        gid = jnp.sort(gid, axis=1)
    with jax.named_scope("stage1/gather"):
        row = jnp.arange(b, dtype=jnp.int32)[:, None]
        at = ((row // r) * G + gid) * r + row % r  # [b, k]
        cand = tiles.reshape(b * G, S)[at].reshape(b, k * S)
    with jax.named_scope("stage1/top_k"):
        v, pos = jax.lax.top_k(cand, k)
        return v, jnp.take_along_axis(gid, pos // S, axis=1) * S + pos % S


@partial(jax.jit, static_argnames=("kc",))
def _stage1_flat(qU, u_scale, Q, scale, item_w,
                 excl_rows, excl_cols, excl_w, *, kc):
    """Flat int8 stage 1: one int8×int8→int32 matmul over the whole
    quantized catalog, dequantized by the outer product of scales, the
    exact path's additive mask offset and scatter-min exclusions
    applied, top-``kc`` candidates out: the ``kc`` that ``lax.top_k``
    over the whole row would give, chosen by ``exact_top_k`` (on a wide
    catalog through group maxima, so that no top-k runs over a million
    scores a row)."""
    # named scopes: HLO metadata only (the device trace can then name
    # the phases the fusion numbers hide); no arithmetic moves
    with jax.named_scope("stage1/int8_dot"):
        scores = jax.lax.dot_general(
            qU, Q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32).astype(jnp.float32)
    with jax.named_scope("stage1/dequant_mask"):
        scores = scores * (u_scale[:, None] * scale[None, :])
        scores = scores + item_w[None, :]
        scores = scores.at[excl_rows, excl_cols].min(excl_w)
    return exact_top_k(scores, kc)


@partial(jax.jit, static_argnames=("kc", "n_probe"))
def _stage1_clustered(U_chunk, centroids,
                      slab_q, slab_scale, slab_w, slab_rows,
                      ovf_q, ovf_scale, ovf_w, ovf_rows,
                      *, kc, n_probe):
    """Clustered stage 1 (IVF): route each query to its top-``n_probe``
    clusters by centroid inner product, score ONLY those slabs plus the
    (small) overflow block every query scores. The probe loop is a
    ``lax.map`` so peak memory is one ``[bucket, slab, rank]`` gather,
    not ``n_probe`` of them; the gathered int8 slab upcasts to f32
    before the einsum (measured fastest on XLA:CPU — the int8-einsum
    path is a slow scalar loop, and f32-at-rest slabs would double the
    gather bytes). Queries stay RAW f32: the slab operand is f32 by
    then anyway, so quantizing queries here would add round-trip error
    for zero compute saved (the flat path quantizes them because its
    int8×int8 dot actually consumes them). Exclusions are NOT applied
    here (slab positions vary per query); stage 2's membership test
    owns them — overfetch absorbs the candidate slots excluded items
    waste."""
    routing = jnp.dot(U_chunk, centroids.T)  # [b, C] f32
    _, cid = jax.lax.top_k(routing, n_probe)  # [b, p]

    def one_probe(pi):
        c = cid[:, pi]  # [b]
        g = slab_q[c].astype(jnp.float32)  # [b, m, r]
        sc = jnp.einsum("br,bmr->bm", U_chunk, g)
        sc = sc * slab_scale[c] + slab_w[c]
        return sc, slab_rows[c]

    scores, rows = jax.lax.map(one_probe, jnp.arange(n_probe))
    b = U_chunk.shape[0]
    scores = jnp.moveaxis(scores, 0, 1).reshape(b, -1)  # [b, p·m]
    rows = jnp.moveaxis(rows, 0, 1).reshape(b, -1)
    # overflow block: rows that spilled every capped slab — scored by
    # every query (a plain [b, O] matmul; O is a few % of the catalog
    # at most, and the cap is what keeps the slabs honest)
    ov = jnp.dot(U_chunk, ovf_q.astype(jnp.float32).T)
    ov = ov * ovf_scale[None, :] + ovf_w[None, :]
    scores = jnp.concatenate([scores, ov], axis=1)
    rows = jnp.concatenate(
        [rows, jnp.broadcast_to(ovf_rows[None, :], ov.shape)], axis=1)
    v, pos = jax.lax.top_k(scores, kc)
    return v, jnp.take_along_axis(rows, pos, axis=1)


@partial(jax.jit, static_argnames=("k", "exact"))
def _stage2(U_chunk, V, item_w, cand_v, cand_rows,
            excl_rows, excl_cols, excl_w, *, k, exact):
    """Candidate finalization. ``exact=True`` gathers the candidates'
    full-precision rows and rescores in f32 (every surfaced score is
    then the true score of that item); ``exact=False`` is the degraded
    stage-1-only mode — approximate scores pass through. Either way the
    train-seen exclusions apply EXACTLY via a sorted-key membership
    test (the scatter-min triple can't address a candidate list), and
    excluded candidates drop to ``DEAD_SLOT_OFFSET`` — the shared
    dead-slot sentinel."""
    n = V.shape[0]
    safe_rows = jnp.minimum(cand_rows, n - 1)  # slab pads carry n
    if exact:
        with jax.named_scope("stage2/gather"):
            Vc = V[safe_rows]  # [b, kc, r]
        with jax.named_scope("stage2/rescore"):
            sc = jnp.einsum("br,bkr->bk", U_chunk, Vc)
            sc = sc + item_w[safe_rows]
            # pads (row == n) must stay dead even though row n-1 is real
            sc = jnp.where(cand_rows >= n, -jnp.inf, sc)
    else:
        sc = cand_v
    # membership: real exclusion entries carry w = DEAD_SLOT_OFFSET,
    # pads +inf — encode (query, item) as one sortable uint32 key
    # (x64 is disabled repo-wide; the bucket·(n+1) < 2³² capacity this
    # implies is guarded loudly in TwoStageRetriever.topk)
    stride = jnp.uint32(n + 1)
    real = excl_w < 0
    keys = jnp.where(
        real,
        excl_rows.astype(jnp.uint32) * stride
        + excl_cols.astype(jnp.uint32),
        jnp.uint32(2**32 - 1))
    keys = jnp.sort(keys)
    b = cand_rows.shape[0]
    cand_keys = (jnp.arange(b, dtype=jnp.uint32)[:, None] * stride
                 + cand_rows.astype(jnp.uint32))
    pos = jnp.clip(jnp.searchsorted(keys, cand_keys), 0, keys.shape[0] - 1)
    hit = keys[pos] == cand_keys
    sc = jnp.where(hit, DEAD_SLOT_OFFSET, sc)
    with jax.named_scope("stage2/top_k"):
        v, p = jax.lax.top_k(sc, k)
        return v, jnp.take_along_axis(cand_rows, p, axis=1)


# --------------------------------------------------------------------------
# Retriever: the engine-facing surface
# --------------------------------------------------------------------------


class TwoStageRetriever:
    """One catalog build's fast path: quantized stage-1 structure +
    full-precision rescore table, with per-chunk ``topk`` the engine's
    micro-batch loop calls. Rebuilt by ``ServingEngine._refresh`` on a
    full swap; patched in place by ``apply_delta`` on a delta swap.

    The flat stage 1 hands stage 2 the ``k · overfetch`` best int8
    scores of the whole row, exactly those ``lax.top_k`` over the row
    would: ``exact_top_k`` finds them through the maxima of contiguous
    groups on a catalog wide enough for that to pay (``select_groups``,
    read off the catalog's height and the budget; no option), and the
    counter ``serving_stage1_select_total{path}`` says which way each
    call went."""

    def __init__(self, V, item_mask=None,
                 config: RetrievalConfig | None = None,
                 version: int | None = None, partitioner=None):
        self.config = config or RetrievalConfig()
        self.partitioner = _rank_shard_partitioner(partitioner)
        self.V = jnp.asarray(V, jnp.float32)  # exact rescore table
        self.catalog = build_quantized_catalog(
            self.V, item_mask=item_mask, config=self.config,
            version=catalog_version(V) if version is None else version,
            partitioner=self.partitioner)
        if self.partitioner is not None:
            # the stage-2 rescore table rank-shards too: GSPMD turns its
            # f32 candidate einsum into a partial contraction + all-reduce
            self.V = self.partitioner.shard(self.V, None, "rank")
        self.buckets_seen: set[tuple] = set()  # compile-shape evidence
        # flat stage-1 calls by how exact_top_k picks the candidates,
        # counted from the shape rule it traces with (bound here, as the
        # engine binds its own: no-ops under the default null registry)
        obs = get_registry()
        self._m_select = {
            path: obs.counter("serving_stage1_select_total", path=path)
            for path in ("two_level", "full")}

    def nbytes_per_device(self) -> int:
        """Stage-1 catalog + stage-2 rescore table bytes per device (the
        ISSUE 16 per-device serving footprint)."""
        per_cat = self.catalog.nbytes_per_device()
        shards = getattr(self.V, "addressable_shards", None)
        if shards:
            v_dev = max(int(s.data.size * s.data.dtype.itemsize)
                        for s in shards)
        else:
            v_dev = int(self.V.size * self.V.dtype.itemsize)
        return per_cat + v_dev

    @property
    def version(self) -> int:
        return self.catalog.version

    @property
    def n_rows(self) -> int:
        return self.catalog.n_rows

    def candidate_count(self, k: int) -> int:
        """Stage-1 budget for ``k`` results: ``k · overfetch``, floored
        at ``k`` and clamped to what the layout's top-k can legally
        supply (catalog height flat; probed slab capacity clustered)."""
        cat = self.catalog
        if cat.clustered:
            C, m, _ = cat.slab_q.shape
            hard = (min(self.config.n_probe, C) * m
                    + int(cat.ovf_q.shape[0]))
        else:
            hard = cat.n_rows
        return min(max(k, min(k * self.config.overfetch, cat.n_rows)),
                   hard)

    def topk(self, U_chunk, excl, k: int, stage1_only: bool = False,
             seam=None):
        """Top-``k`` of one padded query chunk: ``(values f32 [b, k],
        rows int32 [b, k])``, rows ≥ ``n_rows`` possible only for slab
        pads (callers clamp, as with mesh padding). The two dispatches
        are the seams ``serving/retrieval/stage1`` (exclusion ship,
        query quantization, stage-1 call) and ``serving/retrieval/stage2``
        (``obs.trace.SEAMS``), opened through ``seam`` (default: the
        installed tracer's; the engine passes its flush's, which also
        closes into the request plane's ledger) — including under
        ``stage1_only`` (the degraded path still attributes its
        approximate stage-2 dispatch)."""
        seam = seam or get_tracer().seam
        cat = self.catalog
        kc = self.candidate_count(k)
        if U_chunk.shape[0] * (cat.n_rows + 1) >= 2**32:
            # stage 2's exclusion membership packs (query, item) into
            # one uint32 key (x64 is disabled repo-wide)
            raise ValueError(
                f"bucket {U_chunk.shape[0]} × catalog {cat.n_rows} "
                f"exceeds the uint32 membership-key capacity — lower "
                f"RetrievalConfig.max_bucket")
        with seam("serving/retrieval/stage1"):
            if self.partitioner is not None:
                # rank-sharded catalogs: the query chunk and exclusion
                # triple replicate onto the mesh so the jitted stages see
                # one device set (GSPMD then partitions the contractions
                # over 'model')
                U_chunk = self.partitioner.shard(U_chunk)
                excl = tuple(self.partitioner.shard(e) for e in excl)
            excl_rows, excl_cols, excl_w = (jnp.asarray(e) for e in excl)
            if cat.clustered:
                n_probe = min(self.config.n_probe, cat.slab_q.shape[0])
                self.buckets_seen.add(("clustered", U_chunk.shape[0], kc))
                cand_v, cand_rows = _stage1_clustered(
                    U_chunk, cat.centroids, cat.slab_q,
                    cat.slab_scale, cat.slab_w, cat.slab_rows,
                    cat.ovf_q, cat.ovf_scale, cat.ovf_w, cat.ovf_rows,
                    kc=kc, n_probe=n_probe)
            else:
                # only the flat int8×int8 dot consumes quantized queries
                qU, u_scale = _quantize_rows(U_chunk)
                self.buckets_seen.add(("flat", U_chunk.shape[0], kc))
                self._m_select[
                    "full" if select_groups(cat.n_rows, kc) is None
                    else "two_level"].inc()
                cand_v, cand_rows = _stage1_flat(
                    qU, u_scale, cat.q, cat.scale, cat.item_w,
                    excl_rows, excl_cols, excl_w, kc=kc)
        with seam("serving/retrieval/stage2"):
            return _stage2(U_chunk, self.V, cat.item_w, cand_v, cand_rows,
                           excl_rows, excl_cols, excl_w,
                           k=min(k, kc), exact=not stage1_only)

    def apply_delta(self, rows, values, version: int) -> None:
        """Install only the touched rows: patch the f32 rescore table
        and re-quantize exactly the dirty rows of the int8 catalog.
        ``values`` are the rows' new full-precision factors."""
        rows = np.asarray(rows)
        if len(rows):
            vals = jnp.asarray(values, jnp.float32)
            if self.partitioner is not None:
                vals = self.partitioner.shard(vals)
                self.V = jax.device_put(
                    self.V.at[jnp.asarray(rows)].set(vals),
                    self.V.sharding)  # re-pin the rank-sharded layout
            else:
                self.V = self.V.at[jnp.asarray(rows)].set(vals)
            self.catalog = self.catalog.apply_delta(rows, vals, version)
        else:
            self.catalog = dataclasses.replace(self.catalog,
                                               version=version)


# --------------------------------------------------------------------------
# Recall measurement
# --------------------------------------------------------------------------


def recall_at_k(approx_ids, exact_ids) -> float:
    """Mean per-query overlap fraction between an approximate top-k id
    list and the exact one. Dead slots (id −1, the assembled form of
    below-threshold scores) are dropped from BOTH sides; a query whose
    exact list is empty contributes 1.0 (nothing to recall)."""
    approx_ids = np.asarray(approx_ids)
    exact_ids = np.asarray(exact_ids)
    if approx_ids.ndim == 1:
        approx_ids = approx_ids[None]
        exact_ids = exact_ids[None]
    total = 0.0
    for a_row, e_row in zip(approx_ids, exact_ids):
        e = set(int(x) for x in e_row if x >= 0)
        if not e:
            total += 1.0
            continue
        a = set(int(x) for x in a_row if x >= 0)
        total += len(a & e) / len(e)
    return total / len(approx_ids)
