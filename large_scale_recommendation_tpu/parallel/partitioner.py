"""Unified logical-axis Partitioner: ONE sharding layer for every
distributed surface (mesh DSGD, mesh ALS, catalog serving, per-shard
checkpoints), wired for multi-host pods.

The reference scales by shipping rating partitions and factor blocks
through engine-specific partitioners (Flink ``partitionCustom``,
PSOfflineMF.scala:70-72; Spark ``ShiftedIntHasher``,
OfflineSpark.scala:196-201) — every operator hand-rolls its own notion
of "where do these rows live". Our TPU-native stack had grown the same
disease: ``dsgd_mesh``, ``als_mesh`` and ``serving`` each constructed
their own ``NamedSharding``s against a private 1D ``blocks`` ring.

This module replaces all of that with the T5X recipe (SNIPPETS.md
[2]/[3], ALX §4): arrays are annotated with **logical axis names** —
``('users', 'rank')`` for U, ``('items', 'rank')`` for V,
``('ratings',)`` for stratum/entry layouts — and ONE rules table maps
logical axes onto the physical ``('data', 'model')`` device mesh:

    logical axis   role     today
    ------------   ------   ------------------------------------------
    users          data     user rows block-sharded (ring p)
    items          data     item rows block-sharded (rotate)
    ratings        data     stratum dim 0 device-major
    queries        (none)   serving query chunks replicated
    rank           model    factor columns rank-sharded (model axis ≥ 1)

so training, checkpoint resume and the serving scatter all answer
"where does this array live?" through the same table, and changing the
deployment (laptop → one TPU VM → v5e pod slice) changes only the mesh
underneath the table, never the call sites.

Physical axes: ``data`` is the DSGD stratum ring (the axis ``ppermute``
rotates item shards around and ``all_gather`` rides); ``model`` is the
factor-rank sharding axis (the ALX recipe: shard the rank dimension too
at ~1B-row scale). At ``model_parallel > 1`` each device holds a
``rank/m`` column slice of U and V, and the kernels insert the
reduction collectives the math needs: the SGD prediction dot and the
serving score dot ``psum`` their partial contractions over ``'model'``;
mesh ALS all-gathers rank slices back to full width for the Cholesky
solve (the Gram is full-rank) and keeps only its own slice of the
solution. ``model_parallel == 1`` traces the exact pre-sharding
computation (no collective is inserted), so the replicated goldens
stay bit-identical.

Multi-host: ``Partitioner.create()`` brings up ``jax.distributed`` via
``parallel.distributed.initialize_distributed`` and builds the mesh
over the GLOBAL device set, so the same driver script spans processes;
``place`` / ``make_global_array`` assemble global arrays from
process-local shards (no host ever materializes another host's rows).

Backward compatibility: legacy 1D ``('blocks',)`` meshes
(``parallel.mesh.make_block_mesh``; every existing test) are accepted —
the mesh's only axis is adopted as the ``data`` role — and produce
bit-identical shardings to the hand-rolled code this layer replaced
(pinned by tests/test_partitioner.py against pre-refactor goldens).
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from large_scale_recommendation_tpu.parallel.mesh import (
    BLOCK_AXIS,
    select_devices,
)

__all__ = [
    "DATA_AXIS", "MODEL_AXIS", "DEFAULT_RULES", "Partitioner",
    "as_partitioner", "make_data_model_mesh", "make_legacy_block_mesh",
    "raw_sharding",
]

# physical mesh axis roles (T5X's ('data', 'model') convention)
DATA_AXIS = "data"
MODEL_AXIS = "model"

# The ONE rules table: logical axis name -> physical role (or None for
# replicated). Every distributed surface resolves its shardings here.
DEFAULT_RULES: tuple[tuple[str, str | None], ...] = (
    ("users", DATA_AXIS),     # U rows: device p owns user block p
    ("items", DATA_AXIS),     # V rows: block-sharded, rotates on the ring
    ("ratings", DATA_AXIS),   # stratum layouts [k, ...] / entry streams
    ("queries", None),        # serving query chunks: replicated to shards
    ("rank", MODEL_AXIS),     # factor columns: rank-sharded over 'model'
)


def make_data_model_mesh(num_devices: int | None = None, devices=None,
                         model_parallel: int = 1) -> Mesh:
    """The physical ``('data', 'model')`` mesh.

    ``data`` is the block ring (k = total devices / model_parallel);
    ``model`` is the factor-rank sharding axis (default size 1). The
    device pick order matches ``make_block_mesh`` (global ``jax.devices()``
    order; more devices than exist raises), so a ring over the same
    devices rotates the same way whichever constructor built it.
    """
    devices = select_devices(num_devices, devices)
    n = len(devices)
    if model_parallel < 1 or n % model_parallel:
        raise ValueError(
            f"model_parallel={model_parallel} does not divide {n} devices")
    grid = np.asarray(devices).reshape(n // model_parallel, model_parallel)
    return Mesh(grid, (DATA_AXIS, MODEL_AXIS))


def make_legacy_block_mesh(num_devices: int | None = None,
                           devices=None) -> Mesh:
    """The legacy 1D ``('blocks',)`` ring, constructed HERE so every
    mesh in the system comes off the one audited surface (graftlint
    rule ``sharding-funnel``). ``parallel.mesh.make_block_mesh`` is the
    public spelling and delegates to this; the partitioner adopts the
    ring's only axis as its data role, so both mesh spellings resolve
    identical shardings (pinned by tests/test_partitioner.py)."""
    return Mesh(np.array(select_devices(num_devices, devices)),
                (BLOCK_AXIS,))


def raw_sharding(mesh: Mesh, spec: PartitionSpec) -> NamedSharding:
    """The ONE audited constructor for legacy raw-``PartitionSpec``
    callers (``parallel.distributed.make_global_array`` and external
    code that predates the rules table). New code names LOGICAL axes
    through ``Partitioner.sharding``/``spec`` instead — a raw spec is a
    layout decision the rules table cannot see, which is exactly why
    construction is funneled here where the escape hatch is greppable
    (graftlint rule ``sharding-funnel``)."""
    return NamedSharding(mesh, spec)


class Partitioner:
    """Owns the device mesh + the logical-axis rules table; the only
    object that constructs ``NamedSharding``s for the distributed stack.

    Hashable by ``(mesh, rules)`` so jitted-step builders can keep their
    ``lru_cache`` keyed on the partitioner (current jax interns equal
    ``Mesh`` objects, so equal partitioners hash equal across call
    sites).
    """

    def __init__(self, mesh: Mesh | None = None,
                 rules: tuple[tuple[str, str | None], ...] = DEFAULT_RULES,
                 num_devices: int | None = None, devices=None,
                 model_parallel: int = 1):
        if mesh is None:
            mesh = make_data_model_mesh(num_devices, devices,
                                        model_parallel)
        self.mesh = mesh
        self.rules = tuple((str(k), v) for k, v in rules)
        self._rules = dict(self.rules)
        axes = tuple(mesh.axis_names)
        if DATA_AXIS in axes:
            self._data = DATA_AXIS
        elif len(axes) == 1:
            # legacy 1D ring (``make_block_mesh``'s ``blocks`` axis): its
            # only axis IS the data role — same specs, same collectives
            self._data = axes[0]
        else:
            raise ValueError(
                f"mesh axes {axes} name no '{DATA_AXIS}' axis and are "
                "not a 1D ring — cannot infer the data role")
        self._model = MODEL_AXIS if MODEL_AXIS in axes else None

    # -- identity (lru_cache keys on step builders) -------------------------

    def __hash__(self) -> int:
        return hash((self.mesh, self.rules))

    def __eq__(self, other) -> bool:
        return (isinstance(other, Partitioner)
                and self.mesh == other.mesh and self.rules == other.rules)

    def __repr__(self) -> str:
        shape = dict(self.mesh.shape)
        return f"Partitioner(mesh={shape}, data_axis={self._data!r})"

    # -- construction helpers ----------------------------------------------

    @classmethod
    def create(cls, distributed_config=None,
               rules: tuple[tuple[str, str | None], ...] = DEFAULT_RULES,
               model_parallel: int = 1) -> "Partitioner":
        """Pod entry point: bring up ``jax.distributed`` (no-op when the
        ``LSR_*`` env / config names a single process), then build the
        partitioner over the GLOBAL device set — one call that makes the
        same driver script span a laptop, one TPU VM, or a pod slice."""
        from large_scale_recommendation_tpu.parallel.distributed import (
            initialize_distributed,
        )

        initialize_distributed(distributed_config)
        return cls(rules=rules, model_parallel=model_parallel)

    # -- the rules table ----------------------------------------------------

    @property
    def data_axis(self) -> str:
        """Physical mesh axis carrying the ``data`` role (the block
        ring). Collectives — the DSGD ppermute, the ALS/serving
        all_gathers — ride THIS axis."""
        return self._data

    @property
    def model_axis(self) -> str | None:
        return self._model

    @property
    def num_blocks(self) -> int:
        """k: the block-ring size (≙ the reference's worker parallelism)."""
        return int(self.mesh.shape[self._data])

    @property
    def model_parallel(self) -> int:
        return int(self.mesh.shape[self._model]) if self._model else 1

    def physical_axis(self, logical: str) -> str | None:
        """Resolve ONE logical axis to a physical mesh axis (or None for
        replicated). Unknown names raise — the rules table is the closed
        vocabulary of the distributed stack."""
        try:
            role = self._rules[logical]
        except KeyError:
            raise KeyError(
                f"unknown logical axis {logical!r}; rules table knows "
                f"{sorted(self._rules)}") from None
        if role is None:
            return None
        if role == DATA_AXIS:
            return self._data
        if role == MODEL_AXIS:
            return self._model  # None when the mesh has no model axis
        if role in self.mesh.axis_names:
            return role  # rules may also name a physical axis directly
        raise ValueError(
            f"rule {logical!r} -> {role!r} names no axis of mesh "
            f"{tuple(self.mesh.axis_names)}")

    def spec(self, *logical: str | None) -> PartitionSpec:
        """Logical axis names -> ``PartitionSpec``. ``None`` entries (and
        trailing unnamed dims) stay unsharded; no arguments = replicated."""
        return PartitionSpec(*(
            None if ax is None else self.physical_axis(ax)
            for ax in logical))

    def sharding(self, *logical: str | None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(*logical))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec())

    # -- placement -----------------------------------------------------------

    def shard(self, x, *logical: str | None):
        """Single-process placement: device-put ``x`` with the resolved
        sharding (device-resident inputs reshard without a host trip)."""
        import jax.numpy as jnp

        return jax.device_put(jnp.asarray(x), self.sharding(*logical))

    def constrain(self, x, *logical: str | None):
        """``with_sharding_constraint`` under jit: pin an intermediate to
        the rules-table layout so XLA cannot drift it."""
        return jax.lax.with_sharding_constraint(x, self.sharding(*logical))

    def make_global_array(self, host_data, *logical: str | None):
        """Global mesh-sharded array assembled from process-local data:
        each process supplies only the shards of ITS addressable devices
        (``host_data[idx]`` must resolve global indices — a full logical
        copy on every host, or a host-local view with global indexing).
        ≙ the driver→worker rating shipment with no driver."""
        host_data = np.asarray(host_data)
        return jax.make_array_from_callback(
            host_data.shape, self.sharding(*logical),
            lambda idx: host_data[idx])

    def place(self, x, *logical: str | None):
        """The ONE placement routine: single-process resharding via
        ``device_put`` (no host round-trip for device-resident arrays),
        multi-process global assembly from each host's copy. Replaces the
        hand-rolled process-count branches in the mesh solvers."""
        if jax.process_count() > 1:
            return self.make_global_array(np.asarray(x), *logical)
        return self.shard(x, *logical)

    def from_process_local(self, local_data, *logical: str | None):
        """Global array whose row space is the CONCATENATION of every
        process's ``local_data`` (equal-length contract) — the ingest edge
        of the global blocking pipeline."""
        return jax.make_array_from_process_local_data(
            self.sharding(*logical), np.ascontiguousarray(local_data))

    # -- ring collectives -----------------------------------------------------

    def ring_backward(self) -> tuple[tuple[int, int], ...]:
        """ppermute pattern rotating data-axis shards one step down the
        ring (≙ ``nextRatingBlock``, DSGDforMF.scala:611-619)."""
        k = self.num_blocks
        return tuple((j, (j - 1) % k) for j in range(k))

    # -- guards ---------------------------------------------------------------

    def require_no_model_parallel(self, what: str) -> None:
        """ESCAPE HATCH, not a blanket guard: the mainline kernels (mesh
        DSGD, mesh ALS, the serving top-k, the quantized catalog) all
        insert the rank-reduction collectives and run at model_parallel
        > 1. A path that accumulates across the full rank dimension with
        NO cross-model-axis reduction (e.g. a kernel that stages full
        factor rows through VMEM) must refuse loudly here rather than
        silently compute on rank slices. Every call
        site outside this module needs a reasoned inline graftlint
        suppression — rule ``model-guard`` (tools/graftlint) flags any
        new unsuppressed caller, the same contract as the
        ``sharding-funnel`` baseline."""
        if self.model_parallel != 1:
            raise NotImplementedError(
                f"{what} does not support rank (model-axis) sharding; "
                f"mesh has model_parallel={self.model_parallel}")

    def require_rank_divisible(self, rank: int, what: str) -> None:
        """Rank-sharded layouts slice factor columns evenly over the
        ``'model'`` axis; an uneven split would silently drop columns on
        the last shard. Refuse loudly at build time."""
        m = self.model_parallel
        if rank % m:
            raise ValueError(
                f"{what}: rank {rank} is not divisible by "
                f"model_parallel={m}; pick a rank that splits evenly "
                f"over the 'model' axis")


def as_partitioner(mesh_or_partitioner,
                   rules: tuple[tuple[str, str | None], ...] = DEFAULT_RULES,
                   ) -> Partitioner:
    """Coerce a call-site argument: a ``Partitioner`` passes through, a
    ``Mesh`` (legacy surface — every pre-refactor caller) is wrapped,
    ``None`` builds the default global partitioner. Equal meshes produce
    equal (hash-equal) partitioners, so cached step builders dedupe."""
    if isinstance(mesh_or_partitioner, Partitioner):
        return mesh_or_partitioner
    if mesh_or_partitioner is None:
        return Partitioner(rules=rules)
    return Partitioner(mesh=mesh_or_partitioner, rules=rules)
