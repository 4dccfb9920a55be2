"""Device-mesh utilities.

TPU-native replacement for the reference's engine parallelism knobs
(reference: ``readParallelism/workerParallelism/psParallelism``
PSOfflineMF.scala:42-44, ``.setParallelism`` FlinkPS.scala:173,208,215-216,
Spark ``defaultParallelism`` OnlineSpark.scala:78). Parallelism here is a
``jax.sharding.Mesh`` shape; communication is XLA collectives over ICI
instead of engine shuffles (SURVEY §2.3).
"""

from __future__ import annotations

import jax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = [
    "BLOCK_AXIS", "shard_map", "select_devices", "make_block_mesh",
    "block_sharding", "replicated", "ring_backward",
]

BLOCK_AXIS = "blocks"


def select_devices(num_devices: int | None = None, devices=None) -> list:
    """The device pick every mesh constructor shares (``make_block_mesh``
    and the Partitioner's ``('data', 'model')`` mesh): the first
    ``num_devices`` of ``devices`` (default: global ``jax.devices()``
    order) — so rings built by either constructor rotate over the same
    devices in the same order. Asking for more devices than exist
    raises: a mesh never lands on another platform than the one asked
    for. CPU-mesh callers (tests, the dry runs) call
    ``utils.platform.force_cpu(n_devices=...)`` first or pass
    ``devices=``."""
    if devices is None:
        devices = jax.devices()
    if num_devices is not None:
        if len(devices) < num_devices:
            platform = devices[0].platform if len(devices) else "none"
            raise ValueError(
                f"need {num_devices} devices, have {len(devices)} "
                f"({platform})")
        devices = devices[:num_devices]
    return list(devices)


def make_block_mesh(num_devices: int | None = None,
                    devices=None) -> Mesh:
    """1D mesh over the block axis — the DSGD stratum ring.

    The reference's k×k stratum grid runs on k workers (each holds one user
    block and one rotating item block); here k = mesh size and the rotation
    is ``lax.ppermute`` around this ring.

    Legacy surface: new code should go through
    ``parallel.partitioner.Partitioner`` (which builds the 2D
    ``('data', 'model')`` mesh); meshes built here are still accepted
    everywhere — the partitioner adopts the 1D ring's only axis as its
    data role, producing identical shardings. Construction itself lives
    in the partitioner module (the sharding-funnel invariant: one
    audited surface builds every mesh/sharding), this is the
    compatibility name.
    """
    from large_scale_recommendation_tpu.parallel.partitioner import (
        make_legacy_block_mesh,
    )

    return make_legacy_block_mesh(num_devices, devices)


def block_sharding(mesh: Mesh) -> NamedSharding:
    """Shard dim 0 over the block axis (factor tables, per-device strata).

    Legacy spelling of ``Partitioner(mesh).sharding("users", "rank")`` /
    ``..."ratings")`` — kept for external callers; the mesh solvers now
    resolve every sharding through the partitioner's rules table."""
    from large_scale_recommendation_tpu.parallel.partitioner import (
        as_partitioner,
    )

    return as_partitioner(mesh).sharding("ratings")


def replicated(mesh: Mesh) -> NamedSharding:
    """Fully-replicated sharding on ANY mesh — routed through the
    funnel's raw constructor (the produced sharding is identical to the
    pre-funnel spelling: same mesh, empty spec). Deliberately NOT
    ``as_partitioner(mesh).replicated()``: the rules table must infer a
    data axis, which arbitrary external meshes may not carry, while an
    empty ``PartitionSpec`` is valid on every mesh."""
    from large_scale_recommendation_tpu.parallel.partitioner import (
        raw_sharding,
    )

    return raw_sharding(mesh, PartitionSpec())


def ring_backward(k: int) -> list[tuple[int, int]]:
    """ppermute pattern rotating shards one step down the ring: device j's
    shard moves to device j−1 (mod k).

    ≙ ``nextRatingBlock`` (DSGDforMF.scala:611-619): after step s device p
    holds item block (p+s) mod k; the block it needs next is on device p+1,
    i.e. every shard travels j → j−1.
    """
    return [(j, (j - 1) % k) for j in range(k)]
