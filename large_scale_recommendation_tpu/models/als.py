"""ALS: alternating least squares matrix factorization (batch solver).

TPU-native stand-in for the MLlib ALS the reference calls in its
periodic-retrain branch (reference: spark-adaptive-recom/.../
OnlineSpark.scala:125-131 — ``ALS.train(ratingsHistory, rank,
numberOfIterations, 0.1)``). Capability parity per SURVEY §7 step 5: the
second offline algorithm behind the same fit/predict surface as DSGD.

The solver uses the bucketed-matmul formulation (``ops.als``): a one-time
host plan sorts each orientation by output row and pads per-row rating
lists to power-of-2 buckets, so gram assembly is batched ``[rows, pad, k]``
einsums (MXU work) and the solve is a batched float32 Cholesky (on a TPU the
lanes kernel of ``ops.pallas_als``, vector work) — no scatter in the hot path (the ALX-style formulation, see PAPERS.md) rather than MLlib's
block-routed LAPACK calls.

Precision: float32 tables, and with ``gram_dtype=None`` the Gram matrices,
right-hand sides and solves are float32 on a TPU as on a CPU — the
contractions name ``Precision.HIGHEST`` (``ops.als.contraction_precision``),
since a TPU's default multiplies float32 inputs in bfloat16.
``gram_dtype="bf16"`` is the reduced path: bfloat16 gather and products,
float32 accumulation and solve.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from large_scale_recommendation_tpu.core.initializers import (
    PseudoRandomFactorInitializer,
    RandomFactorInitializer,
)
from large_scale_recommendation_tpu.core.types import Ratings
from large_scale_recommendation_tpu.data import blocking
from large_scale_recommendation_tpu.models.mf import MFModel
from large_scale_recommendation_tpu.ops import als as als_ops


@dataclasses.dataclass(frozen=True)
class ALSConfig:
    """Defaults ≙ the reference call site: rank from config, λ=0.1 hardcoded,
    iterations from config (OnlineSpark.scala:125-131)."""

    num_factors: int = 10
    lambda_: float = 0.1
    iterations: int = 10
    reg_mode: str = "direct"  # "direct" (MLlib ALS.train) | "als_wr" (ω-scaled)
    seed: int | None = 0
    min_pad: int = 8  # smallest per-row bucket width (ops.als plans)
    init_scale: float = 0.1
    # iALS (≙ MLlib ALS.trainImplicit; the BASELINE Criteo-implicit config):
    # treat ratings as interaction strengths with confidence 1 + α·r
    implicit_alpha: float | None = None
    # "bf16" halves the bytes of the hot-path fixed-side row gather and
    # feeds the gram einsums native-MXU bf16 inputs; accumulation + solve
    # stay f32 (ops.als). None = full f32 (the default; MLlib-style
    # numerics): float32 products on a TPU too, at six bfloat16 passes a
    # contraction (PERF.md at the root, Findings, PR 29, has both readings
    # against the float32 reference and what the passes cost).
    gram_dtype: str | None = None


class ALS:
    """Batch ALS solver with the same surface as ``DSGD``."""

    def __init__(self, config: ALSConfig | None = None):
        self.config = config or ALSConfig()
        self.model: MFModel | None = None
        # quality hook (obs.quality.OnlineEvaluator, same contract as
        # DSGD.evaluator): an attached evaluator with a row-space
        # holdout armed scores the fitted tables at the fit boundary
        # (``fit_device``: at every segment's end). None = one pointer
        # test.
        self.evaluator = None

    def fit(self, ratings: Ratings) -> MFModel:
        cfg = self.config
        gram_dtype = self._gram_dtype()  # validate BEFORE the plan build
        if ratings.n == 0:
            raise ValueError("cannot fit on an empty ratings set")

        ru, ri, rv, rw = ratings.to_numpy()
        real = rw > 0
        ru, ri, rv = ru[real], ri[real], rv[real]

        users = blocking.build_id_index(ru, num_blocks=1, seed=cfg.seed)
        items = blocking.build_id_index(
            ri, num_blocks=1, seed=None if cfg.seed is None else cfg.seed + 1
        )
        u_rows, _ = users.rows_for(ru)
        i_rows, _ = items.rows_for(ri)

        # one-time host plans, one per orientation (epoch-invariant)
        user_plan = als_ops.build_solve_plan(
            u_rows, i_rows, rv, users.num_rows, min_pad=cfg.min_pad)
        item_plan = als_ops.build_solve_plan(
            i_rows, u_rows, rv, items.num_rows, min_pad=cfg.min_pad)

        U, V = self._init_factors(users, items)
        from large_scale_recommendation_tpu.obs.instrument import (
            TrainSegmentTimer,
        )

        timer = TrainSegmentTimer(
            "als", "als_planned",
            shape_key=(tuple(np.shape(U)), tuple(np.shape(V))))
        with timer.segment(cfg.iterations) as h:
            U, V = als_ops.als_train_planned(
                U, V, user_plan, item_plan,
                users.omega, items.omega,
                lambda_=cfg.lambda_,
                iterations=cfg.iterations,
                reg_mode=cfg.reg_mode,
                implicit_alpha=cfg.implicit_alpha,
                gram_dtype=gram_dtype,
            )
            h.out = (U, V)
        timer.finish(int(len(ru)))
        if self.evaluator is not None:
            self.evaluator.on_segment(U, V, label="als_planned",
                                      step=cfg.iterations)
        self.model = MFModel(U=U, V=V, users=users, items=items)
        return self.model

    def fit_device(
        self,
        u,
        i,
        r,
        num_users: int,
        num_items: int,
        checkpoint_every: int | None = None,
    ) -> MFModel:
        """Fit via device-built solve plans (``ops.als.device_prepare_counted``).

        Dense-id COO in (host or device arrays, ids in ``[0, num_users) ×
        [0, num_items)``), standard ``MFModel`` out — the ALS counterpart of
        ``DSGD.fit_device``: the sort/bucket/pad plan construction runs on
        chip, so the host never materializes the padded bucket expansion
        and only two ≤33-int size vectors cross the host↔device link.
        Arbitrary external ids go through ``fit`` (host planning).

        ``checkpoint_every`` has the meaning it has in ``DSGD.fit_device``:
        the iterations run in segments of that many sweeps, and at each
        segment's end ``evaluator.on_segment(U, V, label=..., step=done)``
        sees that segment's tables (a fit paid for by its time to a stated
        quality is observable and stoppable at sweep ends). ``als_rounds``
        is a Python loop over jitted half-steps, so k segments of one sweep
        give bit for bit the tables of one segment of k sweeps. None = one
        segment.
        """
        import jax.numpy as jnp

        from large_scale_recommendation_tpu.data.device_blocking import (
            validate_dense_ids,
        )
        from large_scale_recommendation_tpu.obs.instrument import (
            TrainSegmentTimer,
        )
        from large_scale_recommendation_tpu.obs.registry import get_registry
        from large_scale_recommendation_tpu.obs.trace import get_tracer

        cfg = self.config
        # config/input validation first: the device plan build is the
        # long wall of an ALS fit — a typo'd gram_dtype must not cost
        # minutes before raising
        gram_dtype = self._gram_dtype()
        if np.shape(u)[0] == 0:
            raise ValueError("cannot fit on an empty ratings set")
        validate_dense_ids(u, i, num_users, num_items, "ALS.fit_device")
        u = jnp.asarray(u, jnp.int32)
        i = jnp.asarray(i, jnp.int32)
        r = jnp.asarray(r, jnp.float32)
        k = cfg.num_factors
        seam = get_tracer().seam

        # both sides' plans, their class-size read-backs included. Each
        # plan counts its own side's ratings (the lengths of the runs of
        # its row sort): the ALS-WR scale, the mask on V's init and the
        # two id indexes below read those counts, and nothing else of a
        # fit goes over the ratings to count them
        with seam("fit/als/plan"):
            weighted = cfg.reg_mode == "als_wr"
            prep_u, omega_u = als_ops.device_prepare_counted(
                u, i, r, num_users, weighted, min_pad=cfg.min_pad,
                rank_for_chunking=k)
            prep_v, omega_v = als_ops.device_prepare_counted(
                i, u, r, num_items, weighted, min_pad=cfg.min_pad,
                rank_for_chunking=k)
            if cfg.implicit_alpha is not None:
                prep_u = als_ops.implicit_prepared(prep_u,
                                                   cfg.implicit_alpha)
                prep_v = als_ops.implicit_prepared(prep_v,
                                                   cfg.implicit_alpha)
            n_ratings = int(np.shape(u)[0])
            als_ops.publish_plan_sizes("user", prep_u, n_ratings)
            als_ops.publish_plan_sizes("item", prep_v, n_ratings)

        with seam("fit/als/init"):
            init = PseudoRandomFactorInitializer(k, scale=cfg.init_scale)
            # zero the unseen-id rows, matching the host path's zeroed
            # padding rows: the implicit VᵀV term sums the WHOLE table, and
            # the first half-step reads V's init directly (see
            # _init_factors). Only V's init matters mathematically — the
            # first half-step solves U.
            V = init(np.arange(num_items, dtype=np.int32)) \
                * (omega_v > 0)[:, None]

        kind = "als_device_rounds"
        timer = TrainSegmentTimer(
            "als", kind, shape_key=((num_users, k), tuple(np.shape(V))))
        done = 0
        segment = checkpoint_every or cfg.iterations
        while done < cfg.iterations:
            seg = min(segment, cfg.iterations - done)
            with timer.segment(seg) as h:
                U, V = als_ops.als_rounds(
                    V, prep_u, prep_v, num_users, num_items, cfg.lambda_,
                    seg, implicit=cfg.implicit_alpha is not None,
                    gram_dtype=gram_dtype)
                h.out = (U, V)
            done += seg
            if cfg.implicit_alpha is not None:
                get_registry().counter("als_implicit_sweeps_total").inc(seg)
            # the host's time between sweeps
            with seam("fit/als/after_segment"):
                if self.evaluator is not None:
                    self.evaluator.on_segment(U, V, label=kind, step=done)
        timer.finish(n_ratings)

        # dense-vocab IdIndex pair with host-path semantics (ids unseen in
        # training stay unknown → predict 0, dropped from risk)
        def index(omega, n_ids):
            om = np.asarray(omega).astype(np.float32)
            all_ids = np.arange(n_ids, dtype=np.int64)
            present = om > 0
            ids = np.where(present, all_ids, -1)
            return blocking.IdIndex(
                ids=ids, num_blocks=1, rows_per_block=n_ids, omega=om,
                sorted_ids=all_ids[present], sorted_rows=all_ids[present],
            )

        self.model = MFModel(U=U, V=V, users=index(omega_u, num_users),
                             items=index(omega_v, num_items))
        return self.model

    def _gram_dtype(self):
        d = self.config.gram_dtype
        if d is None:
            return None
        if d in ("bf16", "bfloat16"):
            return jnp.bfloat16
        raise ValueError(f"gram_dtype must be None|'bf16', got {d!r}")

    def _init_factors(self, users: blocking.IdIndex, items: blocking.IdIndex):
        cfg = self.config
        # Only V's init matters mathematically (the first half-step solves U
        # from V), but both tables are initialized for API symmetry.
        if cfg.seed is not None:
            init = PseudoRandomFactorInitializer(cfg.num_factors,
                                                 scale=cfg.init_scale)
            U = init(np.maximum(users.ids, 0))
            V = init(np.maximum(items.ids, 0))
        else:
            U = RandomFactorInitializer(cfg.num_factors, seed=0, salt=0,
                                        scale=cfg.init_scale)(
                np.arange(users.num_rows))
            V = RandomFactorInitializer(cfg.num_factors, seed=0, salt=1,
                                        scale=cfg.init_scale)(
                np.arange(items.num_rows))
        # Padding rows (id −1) start at exactly zero: they solve to zero
        # anyway (no ratings), and the implicit VᵀV term sums over the WHOLE
        # table — junk init vectors there would perturb the first half-step
        # (and differently for single-chip vs mesh, whose padding differs).
        import jax.numpy as jnp

        U = jnp.asarray(U) * jnp.asarray((users.ids >= 0)[:, None])
        V = jnp.asarray(V) * jnp.asarray((items.ids >= 0)[:, None])
        return U, V

    # -- scoring passthroughs (same surface as DSGD) -----------------------

    def predict(self, user_ids, item_ids, return_mask: bool = False):
        self._require_fitted()
        return self.model.predict(user_ids, item_ids, return_mask=return_mask)

    def empirical_risk(self, data: Ratings) -> float:
        self._require_fitted()
        return self.model.empirical_risk(data, lambda_=self.config.lambda_)

    def _require_fitted(self):
        if self.model is None:
            raise RuntimeError(
                "model has not been fitted; call fit() before predicting"
            )
