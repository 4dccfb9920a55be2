"""Mesh-parallel ALS: block-sharded factor tables, all_gather half-steps.

Distributed form of ``models.als`` (the MLlib-ALS-equivalent,
OnlineSpark.scala:125-131) in the ALX style (PAPERS.md): U and V are
block-sharded over the device mesh exactly like mesh-DSGD; each half-step

    V_full = all_gather(V)                 (factor tables are the small
                                            [n, k] arrays — cheap on ICI)
    A, b   = local gram assembly over the device's OWN ratings
             (ratings are pre-partitioned by user block on the host, so the
             solved side's rows are always device-local — the same
             co-location trick as Spark's ``zipPartitions``,
             OfflineSpark.scala:169-170, without the shuffle)
    U_l    = batched Cholesky solve of the local shard's systems

and symmetrically for V with ratings partitioned by item block. MLlib routes
factor blocks between executors through the block manager each half-step;
here the only communication is the two ``all_gather`` collectives per round,
riding ICI inside one jitted computation.

Shardings, placement (single-process reshard vs multi-process global
assembly) and the gather axis all resolve through the unified
``parallel.partitioner.Partitioner`` rules table — this module
constructs no ``NamedSharding`` of its own.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh  # noqa: F401 — annotation surface

from large_scale_recommendation_tpu.core.types import Ratings
from large_scale_recommendation_tpu.data import blocking
from large_scale_recommendation_tpu.models.als import ALSConfig
from large_scale_recommendation_tpu.models.mf import MFModel
from large_scale_recommendation_tpu.ops import als as als_ops
from large_scale_recommendation_tpu.parallel.mesh import shard_map
from large_scale_recommendation_tpu.parallel.partitioner import (
    Partitioner,
    as_partitioner,
)


def build_mesh_als_step(
    mesh: "Mesh | Partitioner",
    lambda_: float,
    reg_mode: str,
    iterations: int,
    n_user_buckets: int,
    n_item_buckets: int,
    implicit: bool = False,
    gram_dtype=None,
):
    """Jitted distributed ALS round loop over bucketed solve plans.

    ``mesh`` may be a raw ``Mesh`` (legacy) or a ``Partitioner``; every
    sharding and the two per-round ``all_gather`` collectives resolve
    through the partitioner's rules table.

    Inputs (all 0-dim-sharded): U, V, omegas, then ``n_user_buckets`` ×
    4 arrays of the user-side plan followed by ``n_item_buckets`` × 4 of the
    item side (``ops.als.build_sharded_plans`` layouts). Per round: two
    ``all_gather`` collectives + per-shard bucketed gram/solve — the same
    no-scatter matmul formulation as the single-chip path.
    """
    return _build_mesh_als_step(
        as_partitioner(mesh), lambda_, reg_mode, iterations,
        n_user_buckets, n_item_buckets, implicit, gram_dtype)


@lru_cache(maxsize=32)
def _build_mesh_als_step(
    part: Partitioner,
    lambda_: float,
    reg_mode: str,
    iterations: int,
    n_user_buckets: int,
    n_item_buckets: int,
    implicit: bool,
    gram_dtype,
):
    axis = part.data_axis
    spec = part.spec("ratings")
    rank_sharded = part.model_parallel > 1
    model_axis = part.model_axis if rank_sharded else None
    m = part.model_parallel
    n_arrays = 4 + 4 * (n_user_buckets + n_item_buckets)
    if rank_sharded:
        factor_in = (part.spec("users", "rank"), part.spec("items", "rank"))
    else:
        # keep the historical dim-0 specs at model=1 — equivalent layout,
        # distinct cache key (see dsgd_mesh)
        factor_in = (spec, spec)

    @partial(
        shard_map,
        mesh=part.mesh,
        in_specs=factor_in + (spec,) * (n_arrays - 2),
        out_specs=factor_in,
        # rank-sharded kernels slice by lax.axis_index over 'model',
        # which the replication checker cannot statically type across the
        # scan carry — the model-parity tests pin correctness instead
        **({"check_vma": False} if rank_sharded else {}),
    )
    def run(U_l, V_l, ou_l, ov_l, *bucket_arrays):
        # drop the leading sharded dim of the per-device plan arrays
        flat = [a[0] for a in bucket_arrays]
        ub = [tuple(flat[4 * j: 4 * j + 4]) for j in range(n_user_buckets)]
        ib = [tuple(flat[4 * (n_user_buckets + j):
                         4 * (n_user_buckets + j) + 4])
              for j in range(n_item_buckets)]
        nu_l, ni_l = U_l.shape[0], V_l.shape[0]
        scale_u = ou_l if reg_mode == "als_wr" else None
        scale_v = ov_l if reg_mode == "als_wr" else None
        lam = jnp.float32(lambda_)

        def varying_zeros(shape):
            # fresh accumulators marked device-varying so the VMA check can
            # verify the per-shard writes into them (the rank-sharded route
            # runs with the checker off, so the annotation is skipped there)
            z = jnp.zeros(shape, jnp.float32)
            return z if rank_sharded else jax.lax.pcast(z, axis,
                                                        to="varying")

        def full_gram(F):
            # the shared iALS VᵀV term. Replicated tables: one [r, r]
            # einsum per shard, no extra collective. Rank-sharded meshes
            # distribute it instead — each model-axis participant grams a
            # row chunk of the gathered table and the full Gram is the
            # psum over 'model' (the ISSUE 16 reduction collective; rows
            # are zero-padded to a multiple of m, and zero rows contribute
            # exactly nothing to FᵀF, so the only deviation from the
            # replicated result is fp reduction reordering). float32 on a
            # TPU too, as the single-chip ``_full_gram``.
            precision = als_ops.contraction_precision(F.dtype)
            if rank_sharded:
                n = F.shape[0]
                n_pad = -(-n // m) * m
                Fp = jnp.pad(F, ((0, n_pad - n), (0, 0)))
                chunk = n_pad // m
                Fc = jax.lax.dynamic_slice_in_dim(
                    Fp, jax.lax.axis_index(model_axis) * chunk, chunk, 0)
                G = jnp.einsum("nk,nl->kl", Fc, Fc, precision=precision,
                               preferred_element_type=jnp.float32)
                return jax.lax.psum(G, model_axis)
            return jnp.einsum("nk,nl->kl", F, F, precision=precision,
                              preferred_element_type=jnp.float32)

        # explicit path: cast the LOCAL shard before the all_gather —
        # elementwise cast commutes with gather, so this is the same bf16
        # table solve_side_local would build, but both collectives move
        # half the ICI bytes. The implicit path gathers f32 (full_gram's
        # VᵀV term stays full precision) and casts inside the solve.
        pre_cast = gram_dtype is not None and not implicit
        cast = (lambda x: x.astype(gram_dtype)) if pre_cast else (lambda x: x)
        local_dtype = None if pre_cast else gram_dtype

        def gather_full(F_l):
            # rank-sharded shards gather the 'model' axis back to full
            # width FIRST (rank slices are contiguous column ranges, so
            # the tiled axis=1 concat reassembles the exact replicated
            # table — bit-identical, no reduction), then ride the
            # existing data-axis gather. The Cholesky solve needs the
            # full-rank Gram; the memory win is the table AT REST.
            if rank_sharded:
                F_l = jax.lax.all_gather(F_l, model_axis, axis=1, tiled=True)
            return jax.lax.all_gather(F_l, axis, tiled=True)

        def keep_rank_slice(F_lf):
            # back to this shard's rank slice: device j on the model axis
            # owns columns [j·r/m, (j+1)·r/m)
            if not rank_sharded:
                return F_lf
            r_loc = F_lf.shape[1] // m
            return jax.lax.dynamic_slice_in_dim(
                F_lf, jax.lax.axis_index(model_axis) * r_loc, r_loc, 1)

        def round_(carry, _):
            U_l, V_l = carry
            V_full = gather_full(cast(V_l))
            Gv = full_gram(V_full) if implicit else None
            U_l = keep_rank_slice(als_ops.solve_side_local(
                V_full, ub, nu_l, lam, scale_u, varying_zeros, Gv,
                dtype=local_dtype))
            U_full = gather_full(cast(U_l))
            Gu = full_gram(U_full) if implicit else None
            V_l = keep_rank_slice(als_ops.solve_side_local(
                U_full, ib, ni_l, lam, scale_v, varying_zeros, Gu,
                dtype=local_dtype))
            return (U_l, V_l), None

        (U_l, V_l), _ = jax.lax.scan(round_, (U_l, V_l), None,
                                     length=iterations)
        return U_l, V_l

    return jax.jit(run)


class MeshALS:
    """Distributed ALS over a block mesh — same surface as ``MeshDSGD``."""

    def __init__(self, config: ALSConfig | None = None,
                 mesh=None, partitioner: Partitioner | None = None):
        self.config = config or ALSConfig()
        self.partitioner = (partitioner if partitioner is not None
                            else as_partitioner(mesh))
        self.mesh = self.partitioner.mesh
        self.model: MFModel | None = None

    @property
    def num_blocks(self) -> int:
        return self.partitioner.num_blocks

    def fit(self, ratings: Ratings) -> MFModel:
        from large_scale_recommendation_tpu.models.als import ALS

        cfg = self.config
        solver = ALS(cfg)
        gram_dtype = solver._gram_dtype()  # validate BEFORE the plan build
        if ratings.n == 0:
            raise ValueError("cannot fit on an empty ratings set")
        k = self.num_blocks
        self.partitioner.require_rank_divisible(cfg.num_factors, "mesh ALS")

        ru, ri, rv, rw = ratings.to_numpy()
        real = rw > 0
        ru, ri, rv = ru[real], ri[real], rv[real]

        if jax.process_count() > 1 and cfg.seed is None:
            # seed=None draws a fresh blocking permutation PER PROCESS;
            # the global assembly below would then mix mutually
            # inconsistent row layouts into one array — garbage factors
            # with no error. Refuse up front.
            raise ValueError(
                "MeshALS across processes requires a fixed config seed — "
                "the host blocking must be identical on every process")

        users = blocking.build_id_index(ru, num_blocks=k, seed=cfg.seed)
        items = blocking.build_id_index(
            ri, num_blocks=k, seed=None if cfg.seed is None else cfg.seed + 1
        )
        if jax.process_count() > 1:
            # the identical-host-copy contract make_global_array depends
            # on, enforced: a cheap deterministic digest of the blocking
            # (CRC, not the per-process-salted builtin hash) must agree
            # everywhere, or some process was handed different ratings
            from jax.experimental import multihost_utils
            import zlib

            # the digest must cover the (u, i, r) STREAM, not just the id
            # sets: two hosts with the same ids and values but different
            # pairings would agree on ids/values bytes yet block
            # differently (pair-permutation divergence)
            digest = np.int64(zlib.crc32(
                users.ids.tobytes() + items.ids.tobytes()
                + np.asarray(ru, np.int64).tobytes()
                + np.asarray(ri, np.int64).tobytes()
                + np.asarray(rv, np.float32).tobytes()))
            all_d = np.asarray(multihost_utils.process_allgather(digest))
            if not (all_d == all_d[0]).all():
                raise ValueError(
                    "host blocking diverged across processes "
                    f"(digests {all_d.tolist()}) — every process must pass "
                    "the IDENTICAL full ratings set to MeshALS.fit")
        u_rows, _ = users.rows_for(ru)
        i_rows, _ = items.rows_for(ri)
        rv = np.asarray(rv, np.float32)

        # device-major bucketed plans, one per orientation: solved-side rows
        # localized to their shard, fixed side global (indexes the
        # all_gathered table)
        user_plan = als_ops.build_sharded_plans(
            u_rows % users.rows_per_block, u_rows // users.rows_per_block,
            i_rows, rv, k, users.rows_per_block, cfg.num_factors,
            min_pad=cfg.min_pad, implicit_alpha=cfg.implicit_alpha,
        )
        item_plan = als_ops.build_sharded_plans(
            i_rows % items.rows_per_block, i_rows // items.rows_per_block,
            u_rows, rv, k, items.rows_per_block, cfg.num_factors,
            min_pad=cfg.min_pad, implicit_alpha=cfg.implicit_alpha,
        )

        U, V = solver._init_factors(users, items)

        # placement: Partitioner.place is the ONE copy of the
        # single-process-reshard vs multi-process-global-assembly branch
        # (the host blocking above is deterministic + digest-checked
        # identical, so every host's copy can serve its devices' shards)
        part = self.partitioner
        step_fn = build_mesh_als_step(
            part, cfg.lambda_, cfg.reg_mode, cfg.iterations,
            len(user_plan), len(item_plan),
            implicit=cfg.implicit_alpha is not None,
            gram_dtype=gram_dtype,
        )
        U, V = step_fn(
            part.place(U, "users", "rank"), part.place(V, "items", "rank"),
            part.place(users.omega, "users"),
            part.place(items.omega, "items"),
            *(part.place(a, "ratings") for b in user_plan for a in b),
            *(part.place(a, "ratings") for b in item_plan for a in b),
        )
        # a shard's bucket solves, by the routine solve_normal_eq takes
        # inside this step's shard_map
        als_ops.count_solves(
            cfg.num_factors, part.mesh.devices.flat[0].platform,
            cfg.iterations * (len(user_plan) + len(item_plan)),
            vma_checked=part.model_parallel == 1)
        self.model = MFModel(U=U, V=V, users=users, items=items)
        return self.model
