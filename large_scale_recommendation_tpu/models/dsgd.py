"""DSGD: Gemulla-style stratified SGD matrix factorization (batch solver).

TPU-native rebuild of the reference's two DSGD implementations:
- Flink DataSet bulk-iteration DSGD (DSGDforMF.scala:130-620, FlinkML
  ``Predictor`` with fit/predict)
- Spark zipPartitions DSGD (OfflineSpark.scala:69-207)

Architecture: blocking is a one-time host pass (``data.blocking``), the whole
``iterations × k`` superstep loop is ONE jitted XLA computation
(``ops.sgd.dsgd_train``) — no per-superstep network shuffle, no host
round-trips. On a device mesh the same schedule runs with U/V sharded per
the unified logical-axis rules table (``parallel.partitioner.Partitioner``:
U = ``('users', 'rank')``, V = ``('items', 'rank')``) and ``lax.ppermute``
rotating item shards around the partitioner's data axis
(``parallel.dsgd_mesh``); on a multi-host pod the identical code runs over
the ``Partitioner.create()`` global mesh.

Config parity (reference defaults in FlinkML parameter objects,
MatrixFactorization.scala:201-211, DSGDforMF.scala:161-169):
num_factors=10, lambda=1.0, iterations=10, blocks=None→auto,
learning_rate=0.001, η/√t decay (DSGDforMF.scala:118).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from large_scale_recommendation_tpu.core.initializers import (
    PseudoRandomFactorInitializer,
    RandomFactorInitializer,
)
from large_scale_recommendation_tpu.core.updaters import (
    RegularizedSGDUpdater,
    schedule_from_name,
)
from large_scale_recommendation_tpu.core.types import Ratings
from large_scale_recommendation_tpu.data import blocking
from large_scale_recommendation_tpu.models.mf import MFModel
from large_scale_recommendation_tpu.obs.registry import get_registry
from large_scale_recommendation_tpu.obs.trace import get_tracer
from large_scale_recommendation_tpu.obs.transfers import guard_scope
from large_scale_recommendation_tpu.ops import sgd as sgd_ops


@dataclasses.dataclass(frozen=True)
class DSGDConfig:
    """≙ the FlinkML parameter registry (MatrixFactorization.scala:195-223,
    DSGDforMF.scala:135-169) as one dataclass (SURVEY §5 config layer)."""

    num_factors: int = 10
    lambda_: float = 1.0
    iterations: int = 10
    num_blocks: int | None = None  # None → auto (devices or 1; ≙ Blocks None→1)
    learning_rate: float = 0.001
    # any core.updaters.schedule_from_name name:
    # inverse_sqrt (ref default) | constant | inv_scaling | bottou | xu
    lr_schedule: str = "inverse_sqrt"
    seed: int | None = 0
    minibatch_size: int = 1024
    init_scale: float = 1.0  # factor init upper bound (nextDouble ∈ [0,1))
    collision_mode: str = "mean"  # minibatch row-collision handling (ops.sgd)
    # precompute the "mean"-mode collision scales at blocking time (same
    # math, removes two full-table scatter+gather rounds per minibatch)
    precompute_collisions: bool = True
    # intra-minibatch ordering ("user"|"item"|None): gather/scatter locality
    # lever, same math (data.blocking.block_ratings). Measured at full
    # ML-25M scale: "item" sweeps ~19% faster at an RMSE trajectory
    # identical to 4 decimals (docs/PERF.md "Sort lever") — the default
    # stays None for bit-reproducibility with earlier runs; perf-sensitive
    # callers should set "item" (the bench does).
    minibatch_sort: str | None = None
    # factor table storage dtype: "float32" | "bfloat16" (the ALX recipe,
    # training half). bf16 halves the tables' HBM footprint and per-sweep
    # factor traffic; the sweep accumulates gradients in f32 (dsgd_train
    # upcasts once per segment), so duplicate-row scatter semantics stay
    # exact. Checkpoints round-trip the dtype (utils.checkpoint bit-view
    # encoding).
    factor_dtype: str = "float32"
    # the objective: "squared" (the reference's, on the ratings' values)
    # or "bpr" (Rendle et al., UAI 2009: every entry is a positive and a
    # negative is drawn for it on the device, from the real rows of the
    # visited item block; values are ignored; ops.sgd.bpr_minibatch_update)
    loss: str = "squared"

    def __post_init__(self):
        if self.loss not in ("squared", "bpr"):
            raise ValueError(
                f"unknown loss {self.loss!r}; expected 'squared' or 'bpr'")

    def schedule_fn(self):
        return schedule_from_name(self.lr_schedule, self.lambda_)


class DSGD:
    """Batch DSGD solver. ≙ ``DSGDforMF().setIterations(..).fit(ds)``
    (DSGDforMF.scala:70-85 scaladoc usage)."""

    def __init__(self, config: DSGDConfig | None = None, updater: Any = None):
        self.config = config or DSGDConfig()
        # Pluggable updater — the reference seam (FactorUpdater.scala): any
        # core.updaters implementation may be injected; default is the DSGD
        # λ/ω-regularized rule (DSGDforMF.scala:405-413).
        self.updater = updater or RegularizedSGDUpdater(
            learning_rate=self.config.learning_rate,
            lambda_=self.config.lambda_,
            schedule=self.config.schedule_fn(),
        )
        self.model: MFModel | None = None
        # divergence guard (obs.health.TrainingWatchdog): when attached,
        # each segment boundary scans the full tables for NaN/Inf (a
        # segment is seconds of work — the sweep is noise) and trips per
        # the watchdog's policy. None = one pointer test per segment.
        self.watchdog = None
        # quality hook (obs.quality.OnlineEvaluator): when attached
        # (with a row-space holdout armed via set_offline_holdout),
        # each segment boundary shadow-scores the tables and publishes
        # eval_* gauges — the offline trainers' entry into the same
        # quality series the online path feeds. None = one pointer
        # test per segment.
        self.evaluator = None
        # structured event journal (obs.events): None unless installed —
        # segment/checkpoint emissions are one `is not None` test each,
        # once per segment (seconds of work)
        from large_scale_recommendation_tpu.obs.events import get_events

        self._events = get_events()

    # -- fit ---------------------------------------------------------------

    def fit(
        self,
        ratings: Ratings,
        num_blocks: int | None = None,
        checkpoint_manager=None,
        checkpoint_every: int | None = None,
        resume: bool = False,
    ) -> MFModel:
        """Train. With ``checkpoint_manager`` + ``checkpoint_every``, the
        jitted loop runs in segments of that many iterations with a durable
        snapshot at each boundary (≙ the TemporaryPath persistence barriers,
        DSGDforMF.scala:291-296 — ours also restart: ``resume=True`` picks
        up from the latest snapshot, valid because blocking is deterministic
        given the same ratings + seed)."""
        cfg = self.config
        if ratings.n == 0:
            raise ValueError("cannot fit on an empty ratings set")
        k = num_blocks or cfg.num_blocks or 1

        # Pad each block to the minibatch so chunk boundaries align with
        # block boundaries — this makes the single-device sweep numerically
        # identical to the mesh sweep (blocks in a stratum are row-disjoint,
        # so processing them sequentially here vs in parallel on the mesh is
        # the same math).
        problem = blocking.block_problem(
            ratings,
            num_blocks=k,
            seed=cfg.seed,
            minibatch_multiple=cfg.minibatch_size,
            minibatch_sort=cfg.minibatch_sort,
        )
        U, V = self._init_factors(problem)

        if cfg.precompute_collisions and cfg.collision_mode == "mean":
            icu, icv = blocking.minibatch_inv_counts(
                problem.ratings, cfg.minibatch_size)
            inv = (jnp.asarray(icu), jnp.asarray(icv))
        else:
            inv = (None, None)
        args = (
            jnp.asarray(problem.ratings.u_rows, jnp.int32),
            jnp.asarray(problem.ratings.i_rows, jnp.int32),
            jnp.asarray(problem.ratings.values, jnp.float32),
            jnp.asarray(problem.ratings.weights, jnp.float32),
            jnp.asarray(problem.users.omega),
            jnp.asarray(problem.items.omega),
            *inv,
            *self._negatives(problem.items.omega, k),
        )
        U, V = self._train_segments(
            U, V, args, k, "dsgd_segment",
            checkpoint_manager, checkpoint_every, resume,
            n_ratings=int(ratings.n),
        )
        self.model = MFModel(U=U, V=V, users=problem.users, items=problem.items)
        return self.model

    def _negatives(self, omega_v, k: int) -> tuple:
        """The two arguments ``dsgd_train`` takes after the collision
        scales: under ``loss="bpr"`` each item block's count of real rows
        and the negatives' root key (the config's seed folded with 13;
        blocking folds 10 to 12); under the squared loss none."""
        cfg = self.config
        if cfg.loss == "squared":
            return ()
        key = jax.random.fold_in(
            jax.random.PRNGKey(cfg.seed if cfg.seed is not None else 0), 13)
        return (blocking.seen_rows_per_block(omega_v, k), key)

    def _train_segments(self, U, V, args, k, kind, checkpoint_manager,
                        checkpoint_every, resume, n_ratings=None):
        """Shared segment loop + checkpoint/resume for both blocking paths.

        ``kind`` tags snapshots with the path that wrote them: host (fit)
        and device (fit_device) blocking assign ids to DIFFERENT rows
        (independently seeded permutations), so resuming across paths would
        attach restored factor rows to the wrong ids — same-shape tables,
        silently wrong model. The kind check turns that into an error.

        With observability enabled (``obs.enable()``), each segment gets a
        blocked wall-clock measurement (``train_segment_s{model="dsgd"}``
        + a compile-keyed span) and ``finish`` publishes the
        warmup-excluded throughput gauge; ``n_ratings`` is the
        per-iteration unit count (ratings visited per sweep).
        """
        from large_scale_recommendation_tpu.obs.instrument import (
            TrainSegmentTimer,
        )
        from large_scale_recommendation_tpu.utils.checkpoint import (
            restore_segment_state,
        )

        cfg = self.config
        if cfg.factor_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"factor_dtype {cfg.factor_dtype!r} unsupported; "
                "float32 or bfloat16")
        fdt = jnp.dtype(cfg.factor_dtype)
        U = jnp.asarray(U).astype(fdt)
        V = jnp.asarray(V).astype(fdt)
        done = 0
        if resume:
            if checkpoint_manager is None:
                raise ValueError("resume=True requires a checkpoint_manager")
            U, V, done = restore_segment_state(checkpoint_manager, kind, U, V)
        segment = checkpoint_every or cfg.iterations

        seam = get_tracer().seam
        timer = TrainSegmentTimer(
            "dsgd", kind,
            shape_key=(tuple(np.shape(U)), tuple(np.shape(V)),
                       tuple(np.shape(args[0]))))
        while done < cfg.iterations:
            seg = min(segment, cfg.iterations - done)
            with timer.segment(seg) as h:
                # the segment is one jitted superstep loop: every operand
                # already lives on device, so an armed transfer guard
                # flags any implicit host round-trip sneaking in
                with guard_scope("dsgd.fit"):
                    # module-level jitted fn + hashable static args
                    # (frozen-dataclass updater): refits and segments
                    # of the same shapes hit the XLA compile cache
                    U, V = sgd_ops.dsgd_train(
                        U, V, *args, updater=self.updater,
                        minibatch=cfg.minibatch_size, num_blocks=k,
                        iterations=seg, collision=cfg.collision_mode,
                        t0=done, loss=cfg.loss)
                h.out = (U, V)
            done += seg
            if cfg.loss == "bpr" and n_ratings is not None:
                # one negative a real entry a sweep: the host's count
                get_registry().counter("dsgd_negatives_total").inc(
                    n_ratings * seg)
                # its positive and its negative, through the item side's
                # sorted scatter (ops.sgd.bpr_minibatch_update)
                get_registry().counter("dsgd_item_rows_sorted_total").inc(
                    2 * n_ratings * seg)
            # the host's time between sweeps
            with seam("fit/dsgd/after_segment"):
                if self.watchdog is not None:
                    # BEFORE the checkpoint: a tripped segment must not
                    # persist its poisoned tables as a resume point
                    self.watchdog.after_segment(U, V, label=kind)
                if self.evaluator is not None:
                    # segment-boundary quality: the armed row-space
                    # holdout scores against THIS segment's tables
                    # (segments are seconds of work — the eval is noise
                    # next to them)
                    self.evaluator.on_segment(U, V, label=kind, step=done)
                if self._events is not None:
                    self._events.emit(
                        "train.segment", model="dsgd", kind=kind,
                        iterations=int(seg), done=int(done),
                        total=int(cfg.iterations))
                if checkpoint_manager is not None:
                    checkpoint_manager.save(
                        done, {"U": np.asarray(U), "V": np.asarray(V)},
                        {"kind": kind, "iterations": cfg.iterations},
                    )
                    if self._events is not None:
                        self._events.emit("train.checkpoint", model="dsgd",
                                          kind=kind, step=int(done))
        timer.finish(n_ratings, bytes_per_iteration=(
            None if n_ratings is None else sgd_ops.dsgd_bytes_per_sweep(
                n_ratings, int(np.shape(U)[-1]),
                factor_bytes=jnp.dtype(cfg.factor_dtype).itemsize,
                loss=cfg.loss)),
            flops_per_iteration=(
                None if n_ratings is None else sgd_ops.dsgd_flops_per_sweep(
                    n_ratings, int(np.shape(U)[-1]), loss=cfg.loss)))
        return U, V

    def fit_device(
        self,
        u,
        i,
        r,
        num_users: int,
        num_items: int,
        num_blocks: int | None = None,
        checkpoint_manager=None,
        checkpoint_every: int | None = None,
        resume: bool = False,
    ) -> MFModel:
        """Train via the on-device data pipeline (``data.device_blocking``).

        Takes dense-id COO arrays (host numpy or device arrays, ids in
        ``[0, num_users) × [0, num_items)`` — the contract of compacted
        feature pipelines); blocking, collision scales, init and the whole
        training loop run on chip. Only the id→row maps come back to host
        (a few hundred KB) to build the standard ``MFModel`` surface.

        Prefer this over ``fit`` when ids are already dense: the host never
        materializes the k×k stratum expansion, and host→device traffic is
        the raw COO triple instead of its ~3× padded layout. Arbitrary
        external ids go through ``fit`` (host blocking). Init is always the
        deterministic per-id form (``seed=None`` falls back to seed 0).

        Same checkpoint/segmentation contract as ``fit``. Under
        ``loss="bpr"`` every entry is a positive and ``r`` is not read by
        the step; the rest of the path is the squared loss's.
        """
        from large_scale_recommendation_tpu.data.device_blocking import (
            device_block_problem,
            init_factors_device,
        )

        cfg = self.config
        k = num_blocks or cfg.num_blocks or 1
        p = device_block_problem(
            u, i, r, num_users, num_items, num_blocks=k,
            minibatch_multiple=cfg.minibatch_size,
            seed=cfg.seed if cfg.seed is not None else 0,
            minibatch_sort=cfg.minibatch_sort,
        )
        with get_tracer().seam("fit/dsgd/init"):
            U, V = init_factors_device(p, cfg.num_factors,
                                       scale=cfg.init_scale)

        use_inv = cfg.precompute_collisions and cfg.collision_mode == "mean"
        inv = (p.icu, p.icv) if use_inv else (None, None)
        args = (p.su, p.si, p.sv, p.sw, p.omega_u, p.omega_v, *inv,
                *self._negatives(p.omega_v, k))
        U, V = self._train_segments(
            U, V, args, k, "dsgd_device_segment",
            checkpoint_manager, checkpoint_every, resume,
            n_ratings=int(np.shape(u)[0]),
        )
        users, items = p.to_id_indices()
        self.model = MFModel(U=U, V=V, users=users, items=items)
        return self.model

    def _init_factors(self, problem: blocking.BlockedProblem):
        cfg = self.config
        if cfg.seed is not None:
            # Deterministic per-id init ≙ seeded Random(id ^ seed) factors
            # (DSGDforMF.scala:543-551) — row content is a function of id.
            init_u = PseudoRandomFactorInitializer(cfg.num_factors,
                                                   scale=cfg.init_scale)
            init_v = PseudoRandomFactorInitializer(cfg.num_factors,
                                                   scale=cfg.init_scale)
        else:
            init_u = RandomFactorInitializer(cfg.num_factors, seed=0, salt=0,
                                             scale=cfg.init_scale)
            init_v = RandomFactorInitializer(cfg.num_factors, seed=0, salt=1,
                                             scale=cfg.init_scale)
        U = init_u(np.maximum(problem.users.ids, 0))
        V = init_v(np.maximum(problem.items.ids, 0))
        return U, V

    # -- scoring passthroughs (Predictor-style surface,
    #    MatrixFactorization.scala:239-274,133-192) ------------------------

    def predict(self, user_ids, item_ids, return_mask: bool = False):
        self._require_fitted()
        return self.model.predict(user_ids, item_ids, return_mask=return_mask)

    def empirical_risk(self, data: Ratings) -> float:
        self._require_fitted()
        return self.model.empirical_risk(data, lambda_=self.config.lambda_)

    def _require_fitted(self):
        if self.model is None:
            # ≙ "The ALS model has not been fitted to data..." guard
            # (MatrixFactorization.scala:270-272)
            raise RuntimeError(
                "model has not been fitted; call fit() before predicting"
            )


