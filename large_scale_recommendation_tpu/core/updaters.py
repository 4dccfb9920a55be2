"""Factor updaters — the SGD math contract.

TPU-native rebuild of the reference updater seam
(reference: core/.../FactorUpdater.scala:3-54). The reference contract is
per-element:

    nextFactors(r, u, v) -> (u', v')   full SGD step
    delta(r, u, v)       -> (du, dv)   additive deltas (for PS push)

with ``SGDUpdater`` the plain **unregularized** rule
(FactorUpdater.scala:37-53)::

    e  = r − u·v
    u' = u + η·e·v
    v' = v + η·e·u

The Flink DSGD path uses a second rule with per-occurrence-weighted L2
(DSGDforMF.scala:405-413, omegas from :537-541; per Yu et al.)::

    e  = r − u·v
    u' = u − η_t·(λ/ω_u·u − e·v)
    v' = v − η_t·(λ/ω_v·v − e·u)

Both rules live here behind one interface (SURVEY §2.4 calls for exactly
this). Everything is **batched**: inputs are ``[b]`` ratings and ``[b, k]``
factor rows, so the whole contract jit-compiles onto the MXU/VPU as fused
elementwise + reduction ops instead of the reference's scalar
``zip``/``ddot`` inner loop (DSGDforMF.scala:405; netlib ddot).

Batched semantics note (SURVEY §7 hard part (b)): the reference applies
ratings strictly sequentially per block. A batched kernel applies one
minibatch at a time; duplicate rows within a minibatch accumulate additive
deltas (gradient accumulation) rather than chaining through intermediate
values. This is standard minibatch SGD — convergence-equivalent, not
bit-identical. Drivers control the batch size; batch size 1 recovers exact
sequential semantics.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Protocol

import jax
import jax.numpy as jnp
import numpy as np

# A learning-rate schedule: (base_lr, iteration_1based) -> effective lr.
# ≙ FlinkML LearningRateMethod (DSGDforMF.scala:383-386): Default is constant,
# the reference default config uses η/√t decay (DSGDforMF.scala:118).
LearningRateSchedule = Callable[[jax.Array, jax.Array], jax.Array]


def constant_lr(base_lr: jax.Array, t: jax.Array) -> jax.Array:
    """≙ LearningRateMethod.Constant: η_t = η."""
    del t
    return base_lr


def inverse_sqrt_lr(base_lr: jax.Array, t: jax.Array) -> jax.Array:
    """≙ LearningRateMethod.Default, the reference's η/√t decay
    (DSGDforMF.scala:118,167-168)."""
    return base_lr / jnp.sqrt(jnp.asarray(t, jnp.float32))


def inv_scaling_lr(decay: float = 0.5) -> LearningRateSchedule:
    """≙ LearningRateMethod.InvScaling(decay): η_t = η / t^decay (the FlinkML
    family the reference's setLearningRateMethod accepts,
    DSGDforMF.scala:147-152)."""
    # Normalize before the cache so f(), f(0.5) and f(decay=0.5) all return
    # the SAME callable (lru_cache keys raw call signatures) — schedule
    # identity is what makes updater dataclasses equal as static jit args.
    return _inv_scaling_lr(float(decay))


@functools.lru_cache(maxsize=None)
def _inv_scaling_lr(decay: float) -> LearningRateSchedule:
    def schedule(base_lr: jax.Array, t: jax.Array) -> jax.Array:
        return base_lr / jnp.power(jnp.asarray(t, jnp.float32), decay)

    return schedule


def bottou_lr(lambda_: float,
              optimal_init: float | None = None) -> LearningRateSchedule:
    """≙ LearningRateMethod.Bottou(optimalInit): η_t = 1/(λ·(t₀ + t − 1)).

    Bottou's asymptotically-optimal schedule for λ-strongly-convex losses;
    requires λ > 0 (the schedule is undefined for the unregularized case —
    validated here so λ=0 fails fast instead of silently training on NaN).
    With an explicit ``optimal_init`` the FlinkML semantics apply verbatim
    (the base learning rate is ignored — and η₁ = 1/(λ·t₀) can be enormous
    for small λ; FlinkML makes callers pick t₀ for exactly this reason).
    Default ``None`` picks t₀ = 1/(λ·η₀) so the schedule *starts at the
    configured base rate* and decays as η₀/(1 + η₀λ(t−1)) — the safe form
    for the by-name config layer, where a diverging default would be a trap.
    """
    if lambda_ <= 0:
        raise ValueError(
            f"bottou schedule requires lambda > 0, got {lambda_}"
        )
    return _bottou_lr(float(lambda_),
                      None if optimal_init is None else float(optimal_init))


@functools.lru_cache(maxsize=None)
def _bottou_lr(lambda_: float,
               optimal_init: float | None) -> LearningRateSchedule:
    def schedule(base_lr: jax.Array, t: jax.Array) -> jax.Array:
        t = jnp.asarray(t, jnp.float32)
        lam = jnp.float32(lambda_)
        if optimal_init is None:
            t0 = 1.0 / (lam * base_lr)
        else:
            t0 = jnp.float32(optimal_init)
        return 1.0 / (lam * (t0 - 1.0 + t))

    return schedule


def xu_lr(lambda_: float, decay: float = -0.75) -> LearningRateSchedule:
    """≙ LearningRateMethod.Xu(decay): η_t = η·(1 + λ·η·t)^decay
    (Xu 2011 averaged-SGD schedule; FlinkML uses a negative decay)."""
    return _xu_lr(float(lambda_), float(decay))


@functools.lru_cache(maxsize=None)
def _xu_lr(lambda_: float, decay: float) -> LearningRateSchedule:
    def schedule(base_lr: jax.Array, t: jax.Array) -> jax.Array:
        return base_lr * jnp.power(
            1.0 + jnp.float32(lambda_) * base_lr * jnp.asarray(t, jnp.float32),
            decay,
        )

    return schedule


def warm_boost_lr(boost_factor: float = 2.5,
                  boost_steps: int = 2) -> LearningRateSchedule:
    """η_t = boost_factor·η for the first ``boost_steps`` sweeps, then η.

    No FlinkML analogue — this one is measured, not inherited: bilinear MF
    spends its first sweeps bootstrapping factor correlations from small
    init, and a brief boosted rate cuts that plateau. The default (2.5×
    for 2 sweeps) is the grid point that hit the north-star bench's RMSE
    target at sweep 3 instead of the constant schedule's sweep 8 — 62%
    off the wall-clock-to-RMSE — AND held across workload seeds, with a
    lower final floor; 3.0× was slightly better on one seed but sits at
    the stability edge (full table: docs/PERF.md).
    """
    return _warm_boost_lr(float(boost_factor), int(boost_steps))


@functools.lru_cache(maxsize=None)
def _warm_boost_lr(boost_factor: float, boost_steps: int) -> LearningRateSchedule:
    def schedule(base_lr: jax.Array, t: jax.Array) -> jax.Array:
        return jnp.where(jnp.asarray(t, jnp.int32) <= boost_steps,
                         jnp.float32(boost_factor) * base_lr, base_lr)

    return schedule


def schedule_from_name(name: str, lambda_: float = 1.0,
                       **kwargs) -> LearningRateSchedule:
    """Config-layer registry: schedule name → callable.

    ≙ the pluggable ``setLearningRateMethod(learningRateMethodTrait)`` seam
    (DSGDforMF.scala:147-152); λ is captured here because the FlinkML
    contract passes the regularization constant into
    ``calculateLearningRate`` (DSGDforMF.scala:383-386).
    """
    if name in ("inverse_sqrt", "default"):
        return inverse_sqrt_lr
    if name == "constant":
        return constant_lr
    # The factories are lru_cached so repeated configs yield the SAME
    # callable — updater dataclasses carrying them stay equal/hashable and
    # hit the jit compile cache.
    if name == "inv_scaling":
        return inv_scaling_lr(**kwargs)
    if name == "bottou":
        return bottou_lr(lambda_, **kwargs)
    if name == "xu":
        return xu_lr(lambda_, **kwargs)
    if name == "warm_boost":
        return warm_boost_lr(**kwargs)
    raise ValueError(
        f"unknown learning-rate schedule {name!r}; expected one of "
        "inverse_sqrt|default|constant|inv_scaling|bottou|xu|warm_boost"
    )


class FactorUpdater(Protocol):
    """Batched updater contract. ≙ ``FactorUpdater`` (FactorUpdater.scala:3-19).

    Shapes: ratings float32[b], u/v float32[b, k], weights float32[b]
    (0 masks padding), omegas float32[b] (per-occurrence counts; only
    regularized rules read them), t scalar iteration (1-based).
    """

    def next_factors(
        self,
        ratings: jax.Array,
        u: jax.Array,
        v: jax.Array,
        *,
        weights: jax.Array | None = None,
        omega_u: jax.Array | None = None,
        omega_v: jax.Array | None = None,
        t: jax.Array | int = 1,
    ) -> tuple[jax.Array, jax.Array]: ...

    def delta(
        self,
        ratings: jax.Array,
        u: jax.Array,
        v: jax.Array,
        *,
        weights: jax.Array | None = None,
        omega_u: jax.Array | None = None,
        omega_v: jax.Array | None = None,
        t: jax.Array | int = 1,
        pred: jax.Array | None = None,
    ) -> tuple[jax.Array, jax.Array]: ...


@functools.lru_cache(maxsize=4096)
def _scalar_lr(schedule, base_lr: float, t: int) -> float:
    """Evaluate a (possibly jnp-based) schedule to a python float, cached
    per (schedule, lr, t) so per-rating host paths don't dispatch a jax op
    per element."""
    return float(schedule(jnp.float32(base_lr), jnp.float32(t)))


def _errors(ratings: jax.Array, u: jax.Array, v: jax.Array,
            pred: jax.Array | None = None) -> jax.Array:
    """e = r − u·v, batched. ≙ the ddot in FactorUpdater.scala:42 /
    DSGDforMF.scala:405, as one einsum on the VPU/MXU.

    ``pred`` overrides the local dot with a caller-supplied prediction —
    the rank-sharded mesh kernels hold only a rank slice of u/v, so the
    full dot is a ``psum`` over the ``'model'`` axis that must happen
    OUTSIDE the updater (ops.sgd.sgd_minibatch_update computes it)."""
    with jax.named_scope("residual"):  # HLO metadata only
        if pred is not None:
            return ratings - pred
        return ratings - jnp.einsum("bk,bk->b", u, v)


@dataclasses.dataclass(frozen=True)
class SGDUpdater:
    """Plain unregularized SGD. ≙ ``SGDUpdater`` (FactorUpdater.scala:35-53)."""

    learning_rate: float = 0.01
    schedule: LearningRateSchedule = staticmethod(constant_lr)

    def delta(self, ratings, u, v, *, weights=None, omega_u=None, omega_v=None,
              t=1, pred=None):
        del omega_u, omega_v
        e = _errors(ratings, u, v, pred)
        if weights is not None:
            e = e * weights
        lr = self.schedule(jnp.float32(self.learning_rate), t)
        # du = η e v ; dv = η e u (FactorUpdater.scala:47-53)
        du = lr * e[:, None] * v
        dv = lr * e[:, None] * u
        return du, dv

    def next_factors(self, ratings, u, v, *, weights=None, omega_u=None,
                     omega_v=None, t=1):
        du, dv = self.delta(ratings, u, v, weights=weights, t=t)
        return u + du, v + dv

    def delta_np(self, rating: float, u, v, t: int = 1):
        """Host-side scalar twin of ``delta`` for per-element consumers
        (the PS online paths apply ONE rating per pull answer, reference
        semantics — an eager jax dispatch per rating costs ~0.5 ms; this is
        microseconds). Kept in lockstep with ``delta`` by an equivalence
        test."""
        lr = _scalar_lr(self.schedule, self.learning_rate, int(t))
        e = rating - float(np.dot(u, v))
        return lr * e * v, lr * e * u


@dataclasses.dataclass(frozen=True)
class RegularizedSGDUpdater:
    """SGD with per-occurrence-weighted L2 (λ/ω), the DSGD rule.

    ≙ DSGDforMF.scala:405-413 (NSE regularization per Yu et al.; omegas —
    occurrence counts per id — computed at blocking time,
    DSGDforMF.scala:537-541). With ``schedule=inverse_sqrt_lr`` this is the
    reference DSGD default configuration (DSGDforMF.scala:118,163-168).
    """

    learning_rate: float = 0.001
    lambda_: float = 1.0
    schedule: LearningRateSchedule = staticmethod(inverse_sqrt_lr)

    def delta(self, ratings, u, v, *, weights=None, omega_u=None, omega_v=None,
              t=1, pred=None):
        e = _errors(ratings, u, v, pred)
        if weights is not None:
            e = e * weights
        lr = self.schedule(jnp.float32(self.learning_rate), t)
        ou = jnp.maximum(omega_u, 1.0) if omega_u is not None else 1.0
        ov = jnp.maximum(omega_v, 1.0) if omega_v is not None else 1.0
        reg_u = (self.lambda_ / ou)[..., None] * u if omega_u is not None \
            else self.lambda_ * u
        reg_v = (self.lambda_ / ov)[..., None] * v if omega_v is not None \
            else self.lambda_ * v
        if weights is not None:
            # Padding rows must contribute exactly zero delta.
            reg_u = reg_u * weights[:, None]
            reg_v = reg_v * weights[:, None]
        # u' = u − η(λ/ω_u·u − e·v) (DSGDforMF.scala:407-413)
        du = -lr * (reg_u - e[:, None] * v)
        dv = -lr * (reg_v - e[:, None] * u)
        return du, dv

    def next_factors(self, ratings, u, v, *, weights=None, omega_u=None,
                     omega_v=None, t=1):
        du, dv = self.delta(
            ratings, u, v, weights=weights, omega_u=omega_u, omega_v=omega_v, t=t
        )
        return u + du, v + dv


@dataclasses.dataclass(frozen=True)
class MockFactorUpdater:
    """No-op updater for plumbing tests. ≙ ``MockFactorUpdater``
    (FactorUpdater.scala:21-33).

    Note the reference's ``delta`` returns ``(user, item)`` — i.e. *adds the
    current factors*, which is almost certainly an accident of copy-paste; the
    honest mock emits zero deltas. We emit zeros (SURVEY §2.4: do not
    replicate reference bugs).
    """

    def delta(self, ratings, u, v, *, weights=None, omega_u=None, omega_v=None,
              t=1, pred=None):
        del ratings, weights, omega_u, omega_v, t, pred
        return jnp.zeros_like(u), jnp.zeros_like(v)

    def next_factors(self, ratings, u, v, *, weights=None, omega_u=None,
                     omega_v=None, t=1):
        del ratings, weights, omega_u, omega_v, t
        return u, v
