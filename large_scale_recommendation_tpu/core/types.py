"""Core data types.

TPU-native equivalent of the reference's core types
(reference: core/.../package.scala:3-25 — ``Rating``, ``FactorVector``,
``UserId``/``ItemId`` aliases, ``UserUpdate``/``ItemUpdate`` ADT).

Design departure from the reference: instead of one object per rating (a
``Rating(user, item, rating)`` case class flowing through a dataflow engine),
ratings travel as struct-of-arrays batches (``Ratings``) so they can be placed
on device and consumed by jitted kernels with static shapes. Padding entries
carry ``weight == 0`` so kernels can mask them without dynamic shapes.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

# Reference aliases UserId = Int, ItemId = Int (core/.../package.scala:5-6).
UserId = int
ItemId = int


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Ratings:
    """A batch of (user, item, rating) triples in struct-of-arrays form.

    ≙ ``DataSet[Rating]`` / ``RDD[Rating]`` batches in the reference
    (core/.../package.scala:8). ``weights`` masks padding: real entries have
    weight 1.0, padding entries 0.0 (static-shape substitute for the
    reference's variable-length blocks, DSGDforMF.scala:205).
    """

    users: jax.Array  # int32[n]
    items: jax.Array  # int32[n]
    ratings: jax.Array  # float32[n]
    weights: jax.Array  # float32[n]; 1.0 = real, 0.0 = padding

    @property
    def n(self) -> int:
        return self.users.shape[0]

    @property
    def num_real(self) -> jax.Array:
        return jnp.sum(self.weights)

    @staticmethod
    def from_arrays(
        users: Any, items: Any, ratings: Any, weights: Any | None = None
    ) -> "Ratings":
        """Build a batch, keeping the arrays HOST-side (numpy).

        Ratings are ingest data: blocking, vocabulary building and PS routing
        all consume them on host, and drivers place the *blocked* arrays on
        device themselves. Eager device placement here costs a full
        device→host round trip per preprocessing pass; jitted consumers
        can pass a host batch directly — jax transfers at trace time.
        """
        users = np.asarray(users, dtype=np.int32)
        items = np.asarray(items, dtype=np.int32)
        ratings = np.asarray(ratings, dtype=np.float32)
        if weights is None:
            weights = np.ones_like(ratings)
        else:
            weights = np.asarray(weights, dtype=np.float32)
        return Ratings(users=users, items=items, ratings=ratings, weights=weights)

    def pad_to(self, n: int) -> "Ratings":
        """Pad with weight-0 entries up to length ``n`` (ids point at row 0;
        weight 0 makes them no-ops in every kernel)."""
        cur = self.n
        if cur > n:
            raise ValueError(f"cannot pad {cur} ratings down to {n}")
        if cur == n:
            return self
        pad = n - cur
        # Stay in whatever memory space the batch already lives in: padding a
        # host batch must not force a device transfer (and vice versa).
        xp = np if isinstance(self.users, np.ndarray) else jnp
        return Ratings(
            users=xp.concatenate([self.users, xp.zeros(pad, xp.int32)]),
            items=xp.concatenate([self.items, xp.zeros(pad, xp.int32)]),
            ratings=xp.concatenate([self.ratings, xp.zeros(pad, xp.float32)]),
            weights=xp.concatenate([self.weights, xp.zeros(pad, xp.float32)]),
        )

    def to_numpy(self) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        return (
            np.asarray(self.users),
            np.asarray(self.items),
            np.asarray(self.ratings),
            np.asarray(self.weights),
        )


@dataclasses.dataclass(frozen=True)
class FactorVector:
    """A single (id, factors) pair — host-side exchange format.

    ≙ ``FactorVector(id, vector)`` (core/.../package.scala:10-14). On device,
    factors live as rows of a dense table; this type appears only at API
    boundaries (updates-only output streams, PS pull answers, model export).
    """

    id: int
    factors: np.ndarray

    def __post_init__(self):
        object.__setattr__(
            self, "factors", np.asarray(self.factors, dtype=np.float32)
        )


@dataclasses.dataclass(frozen=True)
class UserUpdate:
    """≙ ``UserUpdate(vector) extends VectorUpdate`` (core/.../package.scala:16-23)."""

    vector: FactorVector


@dataclasses.dataclass(frozen=True)
class ItemUpdate:
    """≙ ``ItemUpdate(vector) extends VectorUpdate`` (core/.../package.scala:16-23)."""

    vector: FactorVector
