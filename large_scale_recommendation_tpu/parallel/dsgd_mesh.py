"""Mesh-parallel DSGD: shard_map + ppermute stratum rotation.

The heart of the framework (SURVEY §7 step 3, §2.2): the reference rotates
item factor blocks between workers through an engine network shuffle every
superstep (Flink coGroup re-shuffle, DSGDforMF.scala:448-450; Spark
re-partition with ``ShiftedIntHasher(shift=i)``, OfflineSpark.scala:196-201).
Here the rotation is a ``lax.ppermute`` of the item shard around the ICI
ring — pure device-to-device transfer inside ONE jitted computation, no host
involvement for the entire ``iterations × k`` superstep loop.

All shardings and collective axes resolve through the unified
``parallel.partitioner.Partitioner`` rules table (U is logical
``('users', 'rank')``, V ``('items', 'rank')``, strata ``('ratings',)``;
the ring is the partitioner's ``data`` axis) — this module constructs no
``NamedSharding`` of its own.

Layout (k devices on the partitioner's data axis):
- U: [k·rows_per_ublock, r] sharded on dim 0 — device p owns user block p
  (blocks are equal-size contiguous row ranges by construction,
  ``data.blocking.build_id_index``).
- V: [k·rows_per_iblock, r] sharded on dim 0 — device p *starts* with item
  block p (the diagonal stratum, ≙ initial rating block ``b·(k+1)``,
  DSGDforMF.scala:562) and after each sub-step receives the next block via
  ppermute (≙ nextRatingBlock, DSGDforMF.scala:611-619).
- ratings: [k, k, bmax] sharded on dim 0; cell [p, s] holds block
  (p, (p+s) mod k) with row indices already LOCALIZED to the owning shard
  (global → local is a subtraction because blocks are contiguous).
- omegas: sharded per-row arrays; the item-side omega travels with V.

After ``iterations × k`` sub-steps every shard is back home, so the output
sharding equals the input sharding.
"""

from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh  # noqa: F401 — annotation surface

from large_scale_recommendation_tpu.core.types import Ratings
from large_scale_recommendation_tpu.data import blocking
from large_scale_recommendation_tpu.models.mf import MFModel
from large_scale_recommendation_tpu.obs.trace import get_tracer
from large_scale_recommendation_tpu.ops import sgd as sgd_ops
from large_scale_recommendation_tpu.parallel.mesh import shard_map
from large_scale_recommendation_tpu.parallel.partitioner import (
    Partitioner,
    as_partitioner,
)


def device_major_local_strata(
    problem: blocking.BlockedProblem,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Re-layout stratum-major blocks [s, p, b] into device-major [p, s, b]
    with shard-local row indices.

    Cell [p, s] = rating block (p, (p+s) mod k): exactly the block device p
    sweeps at sub-step s under the rotation schedule. Local index = global −
    block_start = global mod rows_per_block (blocks are contiguous ranges).
    """
    br = problem.ratings
    u = br.u_rows.transpose(1, 0, 2) % problem.users.rows_per_block
    i = br.i_rows.transpose(1, 0, 2) % problem.items.rows_per_block
    v = br.values.transpose(1, 0, 2)
    w = br.weights.transpose(1, 0, 2)
    return (u.astype(np.int32), i.astype(np.int32),
            v.astype(np.float32), w.astype(np.float32))


def build_mesh_dsgd_step(
    mesh: "Mesh | Partitioner",
    updater: Any,
    minibatch: int,
    num_blocks: int,
    iterations: int,
    collision: str = "mean",
    with_inv: bool = False,
):
    """Build the jitted multi-chip training function.

    ``mesh`` may be a raw ``jax.sharding.Mesh`` (legacy surface) or a
    ``Partitioner`` — every sharding/collective axis below resolves
    through the partitioner's rules table either way.

    Returns ``fn(U, V, ru, ri, rv, rw, omega_u, omega_v, t0) -> (U, V)``
    where every array argument is sharded on dim 0 over the block axis and
    ``t0`` is a replicated scalar (iterations already completed). The full
    ``iterations × k`` superstep loop (≙ the reference's
    ``.iterate(iterations * k)`` bulk iteration, DSGDforMF.scala:337-344)
    runs as one XLA computation with k·iterations ppermutes on the ICI ring.
    """
    return _build_mesh_dsgd_step(
        as_partitioner(mesh), updater, minibatch, num_blocks, iterations,
        collision, with_inv)


@functools.lru_cache(maxsize=32)
def _build_mesh_dsgd_step(
    part: Partitioner,
    updater: Any,
    minibatch: int,
    num_blocks: int,
    iterations: int,
    collision: str,
    with_inv: bool,
):
    k = num_blocks
    axis = part.data_axis
    perm = part.ring_backward()
    spec = part.spec("ratings")
    rank_sharded = part.model_parallel > 1
    # pred_axis: the mesh axis the SGD prediction dot psums over when
    # U/V arrive as rank slices (ops.sgd.sgd_minibatch_update). None at
    # model_parallel == 1 — the traced computation is then IDENTICAL to
    # the pre-sharding kernel (no collective inserted), which keeps the
    # replicated goldens bit-exact.
    pred_axis = part.model_axis if rank_sharded else None
    n_sharded = 10 if with_inv else 8
    if rank_sharded:
        factor_in = (part.spec("users", "rank"), part.spec("items", "rank"))
    else:
        # dim-0-only specs at model=1: P('data') and P('data', None)
        # resolve equivalent layouts but are distinct cache keys — keep
        # the historical spec so recompiles and goldens are untouched
        factor_in = (spec, spec)

    @partial(
        shard_map,
        mesh=part.mesh,
        in_specs=factor_in + (spec,) * (n_sharded - 2) + (part.spec(),),
        out_specs=factor_in,
        # the rank-sharded route mixes model-axis-varying factor slices
        # with model-replicated strata through a psum, whose varying-axis
        # propagation the checker mis-infers across scan carries — the
        # model-parity tests pin its correctness instead. The replicated
        # route keeps the checker on.
        check_vma=not rank_sharded,
    )
    def run(U_l, V_l, ru_l, ri_l, rv_l, rw_l, ou_l, ov_l, *rest):
        # shard_map gives [1, k, b] for the device-major strata; drop the
        # leading sharded dim.
        ru, ri = ru_l[0], ri_l[0]
        rv, rw = rv_l[0], rw_l[0]
        if with_inv:
            icu, icv, t0 = rest[0][0], rest[1][0], rest[2]
        else:
            icu, icv, t0 = None, None, rest[0]

        # bf16 factor shards: ONE f32 upcast per jitted segment (this
        # whole scan), rounded back on exit — the same
        # cadence as ops.sgd.dsgd_train, so gradient accumulation stays
        # exact across every sweep of the segment. Rounding per block
        # sweep instead stalls convergence at small learning rates (the
        # update magnitude drops below bf16's ~8-bit mantissa and every
        # sweep's work is rounded away — measured: mesh bf16 RMSE froze
        # while f32 kept converging). The in-segment ppermute therefore
        # carries f32 shards; half-width applies AT REST (HBM between
        # segments, checkpoints, host↔device).
        fdt = U_l.dtype
        if fdt == jnp.bfloat16:
            U_l = U_l.astype(jnp.float32)
            V_l = V_l.astype(jnp.float32)

        def step(carry, idx):
            U, V, ov = carry
            s = idx % k
            # t0 = iterations already completed (checkpoint segments) so the
            # η/√t schedule continues instead of restarting (same contract
            # as ops.sgd.dsgd_train)
            t = idx // k + 1 + t0
            U, V = sgd_ops.sgd_block_sweep(
                U, V, ru[s], ri[s], rv[s], rw[s], ou_l, ov,
                updater, t, minibatch, collision,
                None if icu is None else icu[s],
                None if icv is None else icv[s],
                pred_axis,
            )
            # Rotate the item shard (and its omegas) one step down the ring
            # — ≙ the reference's inter-superstep shuffle of item blocks
            # (DSGDforMF.scala:611-619 / OfflineSpark.scala:196-201), now an
            # ICI ppermute on the partitioner's data axis.
            with jax.named_scope("ring/ppermute"):  # HLO metadata only
                V = jax.lax.ppermute(V, axis, perm)
                ov = jax.lax.ppermute(ov, axis, perm)
            return (U, V, ov), None

        (U_l, V_l, ov_l), _ = jax.lax.scan(
            step, (U_l, V_l, ov_l),
            jnp.arange(iterations * k, dtype=jnp.int32),
        )
        if fdt == jnp.bfloat16:
            U_l = U_l.astype(fdt)
            V_l = V_l.astype(fdt)
        return U_l, V_l

    return jax.jit(run)


def sharded_init(part: Partitioner, id_of_user_row, id_of_item_row,
                 omega_u, omega_v, rank: int, scale: float):
    """The device pipeline's factor init (``init_factors_device``: row =
    ``scale * uniform(fold_in(PRNGKey(0), id))``) and the omegas, from the
    replicated row maps straight into the ring's shardings: each chip
    draws only its own blocks' rows."""
    return _sharded_init(part, rank)(id_of_user_row, id_of_item_row,
                                     omega_u, omega_v, jnp.float32(scale))


@functools.lru_cache(maxsize=16)
def _sharded_init(part: Partitioner, rank: int):
    from large_scale_recommendation_tpu.core.initializers import (
        _keyed_uniform_rows_padded,
    )

    def init(id_u, id_v, ou, ov, s):
        key = jax.random.PRNGKey(0)
        return (_keyed_uniform_rows_padded(key, id_u, rank, s),
                _keyed_uniform_rows_padded(key, id_v, rank, s), ou, ov)

    return jax.jit(init, out_shardings=(
        part.sharding("users", "rank"), part.sharding("items", "rank"),
        part.sharding("users"), part.sharding("items")))


@dataclasses.dataclass(frozen=True)
class MeshDSGDConfig:
    """Mesh variant of DSGDConfig; ``num_blocks`` is the mesh size."""

    num_factors: int = 10
    lambda_: float = 1.0
    iterations: int = 10
    learning_rate: float = 0.001
    lr_schedule: str = "inverse_sqrt"
    seed: int | None = 0
    minibatch_size: int = 1024
    init_scale: float = 1.0
    collision_mode: str = "mean"  # see ops.sgd.sgd_minibatch_update
    precompute_collisions: bool = True  # see DSGDConfig
    minibatch_sort: str | None = None  # see DSGDConfig
    # "float32" | "bfloat16" — see DSGDConfig.factor_dtype: half-width
    # factor shards at rest (HBM, checkpoints, the ppermute ring) with
    # f32 accumulation inside the sweep
    factor_dtype: str = "float32"


class MeshDSGD:
    """Distributed DSGD over a device mesh.

    ≙ the reference's multi-worker DSGD deployments (Flink task slots /
    Spark executors, one factor block pair per worker). ``mesh`` accepts a
    raw ``Mesh`` (legacy) or a ``Partitioner``; the default is the global
    ``('data', 'model')`` partitioner over all devices — which spans
    processes when ``jax.distributed`` is up, so the same construction
    runs on a laptop, one TPU VM, or a pod slice.
    """

    def __init__(self, config: MeshDSGDConfig | None = None,
                 mesh=None, updater: Any = None,
                 partitioner: Partitioner | None = None):
        from large_scale_recommendation_tpu.core.updaters import (
            RegularizedSGDUpdater,
            schedule_from_name,
        )

        self.config = config or MeshDSGDConfig()
        self.partitioner = (partitioner if partitioner is not None
                            else as_partitioner(mesh))
        self.mesh = self.partitioner.mesh
        sched = schedule_from_name(self.config.lr_schedule,
                                   self.config.lambda_)
        self.updater = updater or RegularizedSGDUpdater(
            learning_rate=self.config.learning_rate,
            lambda_=self.config.lambda_,
            schedule=sched,
        )
        self.model: MFModel | None = None
        # model-quality hook (obs.quality.OnlineEvaluator or anything
        # with its ``on_segment``): called at every segment boundary with
        # that segment's sharded tables — same contract and same point
        # of the loop as ``DSGD.evaluator``. None (the default) adds one
        # pointer test per segment.
        self.evaluator = None

    @property
    def num_blocks(self) -> int:
        return self.partitioner.num_blocks

    def fit(
        self,
        ratings: Ratings,
        checkpoint_manager=None,
        checkpoint_every: int | None = None,
        resume: bool = False,
    ) -> MFModel:
        """Train on the mesh. The checkpoint contract is identical to the
        single-device driver (models/dsgd.py fit): with
        ``checkpoint_manager`` + ``checkpoint_every`` the superstep loop
        runs in segments with a durable snapshot at each boundary
        (≙ the TemporaryPath persistence barriers, DSGDforMF.scala:291-296),
        and ``resume=True`` restarts from the latest snapshot — valid
        because blocking is deterministic given the same ratings + seed."""
        cfg = self.config
        if ratings.n == 0:
            raise ValueError("cannot fit on an empty ratings set")
        k = self.num_blocks

        problem = blocking.block_problem(
            ratings, num_blocks=k, seed=cfg.seed,
            minibatch_multiple=cfg.minibatch_size,
            minibatch_sort=cfg.minibatch_sort,
        )
        ru, ri, rv, rw = device_major_local_strata(problem)

        from large_scale_recommendation_tpu.models.dsgd import DSGD, DSGDConfig

        # factor init identical to the single-device driver
        U, V = DSGD(
            DSGDConfig(num_factors=cfg.num_factors, seed=cfg.seed,
                       init_scale=cfg.init_scale)
        )._init_factors(problem)

        if cfg.precompute_collisions and cfg.collision_mode == "mean":
            icu, icv = blocking.minibatch_inv_counts(
                problem.ratings, cfg.minibatch_size)
            # same device-major [p, s, b] re-layout as the strata
            inv_args = (icu.transpose(1, 0, 2), icv.transpose(1, 0, 2))
        else:
            inv_args = ()
        U, V = self._train_segments(
            U, V, (ru, ri, rv, rw), problem.users.omega,
            problem.items.omega, inv_args, "mesh_dsgd_segment",
            checkpoint_manager, checkpoint_every, resume,
            n_ratings=int(ratings.n),
        )
        self.model = MFModel(U=U, V=V, users=problem.users,
                             items=problem.items)
        return self.model

    def fit_device(
        self,
        u,
        i,
        r,
        num_users: int,
        num_items: int,
        checkpoint_manager=None,
        checkpoint_every: int | None = None,
        resume: bool = False,
    ) -> MFModel:
        """Train on the mesh via the on-device data pipeline.

        Dense-id COO in (host arrays, arrays on one device, or arrays
        already sharded by the partitioner's ``ratings`` rule). Blocking
        runs as programs over the mesh
        (``data.device_blocking.mesh_block_problem``): each chip counts,
        places and sorts only its share of the entries, one ``all_to_all``
        hands every entry to the chip that owns its user block, and each
        chip lays out its own row of the device-major layout with local
        rows, bit for bit what ``device_block_problem`` and the transposes
        to device-major give. No chip ever holds the whole layout, and
        nothing is re-placed afterwards; the factor init and the omegas
        come out sharded.

        The same programs span processes when the partitioner's mesh does
        (``parallel.distributed.global_device_blocked``: each host passes
        only its own entries; examples/distributed_demo.py).
        """
        from large_scale_recommendation_tpu.data.device_blocking import (
            mesh_block_problem,
        )

        cfg = self.config
        part = self.partitioner
        p = mesh_block_problem(
            u, i, r, num_users, num_items, part,
            minibatch_multiple=cfg.minibatch_size,
            seed=cfg.seed if cfg.seed is not None else 0,
            minibatch_sort=cfg.minibatch_sort,
        )
        with get_tracer().seam("fit/mesh_dsgd/init"):
            U, V, ou, ov = sharded_init(part, p.id_of_user_row,
                                        p.id_of_item_row, p.omega_u,
                                        p.omega_v, cfg.num_factors,
                                        cfg.init_scale)
            if cfg.precompute_collisions and cfg.collision_mode == "mean":
                inv_args = (p.icu, p.icv)
            else:
                inv_args = ()
        U, V = self._train_segments(
            U, V, (p.ru, p.ri, p.rv, p.rw), ou, ov, inv_args,
            "mesh_dsgd_device_segment",
            checkpoint_manager, checkpoint_every, resume,
            n_ratings=int(np.shape(u)[0]),
        )
        users, items = p.to_id_indices()
        self.model = MFModel(U=U, V=V, users=users, items=items)
        return self.model

    def _train_segments(self, U, V, strata, omega_u, omega_v, inv_args,
                        kind, checkpoint_manager, checkpoint_every, resume,
                        n_ratings=None):
        """Shared mesh segment loop + checkpoint/resume for both blocking
        paths. Same kind-tagging contract as the single-device driver
        (models/dsgd.py ``_train_segments``): host-blocked and
        device-blocked row layouts are permutation-incompatible, so
        cross-path resume is refused.

        Checkpoints are PER-SHARD (``ShardedCheckpointManager``): each
        process writes only the rows its devices hold, and restore
        re-shards — no full-model gather anywhere, so the save path works
        at scales where the factors cannot fit one host. A plain
        ``CheckpointManager`` is accepted for API compatibility and is
        re-targeted at the same directory in the sharded format."""
        from large_scale_recommendation_tpu.utils.checkpoint import (
            CheckpointManager,
            ShardedCheckpointManager,
            restore_segment_state_sharded,
        )

        if isinstance(checkpoint_manager, CheckpointManager):
            checkpoint_manager = ShardedCheckpointManager(
                checkpoint_manager.directory, keep=checkpoint_manager.keep)

        cfg = self.config
        part = self.partitioner
        k = self.num_blocks
        done = 0
        if cfg.factor_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"factor_dtype {cfg.factor_dtype!r} unsupported; "
                "float32 or bfloat16")
        fdt = jnp.dtype(cfg.factor_dtype)
        U = jnp.asarray(U).astype(fdt)
        V = jnp.asarray(V).astype(fdt)
        part.require_rank_divisible(int(np.shape(U)[-1]), "mesh DSGD")

        if resume and checkpoint_manager is None:
            raise ValueError("resume=True requires a checkpoint_manager")
        seam = get_tracer().seam
        with seam("fit/mesh/place"):
            if resume:
                # host U/V go in directly: on a successful restore only
                # their shape/dtype are read, so the fresh init tables
                # are never shipped to device just to be discarded
                U, V, done = restore_segment_state_sharded(
                    checkpoint_manager, kind, U, V, partitioner=part)
            else:
                U = part.place(U, "users", "rank")
                V = part.place(V, "items", "rank")
            args = tuple(part.place(x, "ratings") for x in strata)
            ou = part.place(omega_u, "users")
            ov = part.place(omega_v, "items")
            with_inv = bool(inv_args)
            inv_args = tuple(part.place(x, "ratings") for x in inv_args)

        from large_scale_recommendation_tpu.obs.instrument import (
            TrainSegmentTimer,
        )

        timer = TrainSegmentTimer(
            "mesh_dsgd", kind,
            shape_key=(tuple(np.shape(U)), tuple(np.shape(V)),
                       tuple(np.shape(args[0]))))
        segment = checkpoint_every or cfg.iterations
        while done < cfg.iterations:
            seg = min(segment, cfg.iterations - done)
            step_fn = build_mesh_dsgd_step(
                part, self.updater, cfg.minibatch_size, k, seg,
                cfg.collision_mode, with_inv,
            )
            with timer.segment(seg) as h:
                U, V = step_fn(U, V, *args, ou, ov, *inv_args,
                               jnp.asarray(done, jnp.int32))
                h.out = (U, V)
            done += seg
            # the host's time between sweeps
            with seam("fit/mesh_dsgd/after_segment"):
                if self.evaluator is not None:
                    # segment-boundary quality, BEFORE the checkpoint:
                    # the hook sees THIS segment's (still sharded) tables
                    self.evaluator.on_segment(U, V, label=kind, step=done)
                if checkpoint_manager is not None:
                    # every process writes its OWN device shards; no
                    # gather, no replicated copy of the model anywhere
                    jax.block_until_ready((U, V))
                    checkpoint_manager.save(
                        done, {"U": U, "V": V},
                        {"kind": kind, "iterations": cfg.iterations},
                    )
        m = part.model_parallel
        timer.finish(n_ratings, bytes_per_iteration=(
            None if n_ratings is None else sgd_ops.dsgd_bytes_per_sweep(
                n_ratings, int(np.shape(U)[-1]), factor_bytes=fdt.itemsize,
                model_size=m)),
            flops_per_iteration=(
                None if n_ratings is None else sgd_ops.dsgd_flops_per_sweep(
                    n_ratings, int(np.shape(U)[-1]))),
            collective_bytes_per_iteration=(
                None if n_ratings is None
                else sgd_ops.dsgd_collective_bytes_per_sweep(
                    n_ratings, int(np.shape(U)[-1]), m)))
        return U, V
