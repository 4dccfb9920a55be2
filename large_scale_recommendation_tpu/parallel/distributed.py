"""Multi-host execution: jax.distributed init + per-host data sharding.

The reference scales out through its engines' driver→worker edges: Flink job
manager → task managers and Spark driver → executors ship rating partitions
and factor blocks over the cluster network (SURVEY §2.3 — Netty/Akka
channels, custom partitioners). The TPU-native equivalent is a
**multi-controller SPMD** job: one Python process per host, every process
running the same program over a GLOBAL device mesh, with XLA collectives
riding ICI inside a slice and DCN across slices. The "driver→worker ingest
edge" becomes: each host loads only ITS shard of the ratings
(``host_rating_shard``) and assembles global device arrays from
process-local data (``make_global_array`` for pre-blocked layouts,
``global_device_blocked`` for on-mesh blocking); there is no driver that
ever holds the whole dataset.

What maps where:

| reference                                  | here                        |
|--------------------------------------------|-----------------------------|
| Flink/Spark cluster bring-up               | ``initialize_distributed()``|
|                                            | (``Partitioner.create()``)  |
| partitionCustom shipping ratings to workers| ``host_rating_shard``       |
| per-worker factor blocks                   | mesh-sharded U/V (dsgd_mesh)|
| engine network shuffles between supersteps | ``lax.ppermute`` on the ring|

Array layout decisions live in ``parallel.partitioner.Partitioner`` —
the one logical-axis rules table; this module provides the process-group
bring-up and the process-local→global assembly primitives it builds on.

Single-process fallback: every function degrades to the local-only behavior
when ``num_processes == 1``, so the same driver script runs on a laptop, a
single TPU VM, or a v5p-64 pod slice unchanged.
"""

from __future__ import annotations

import dataclasses
import os

import numpy as np


@dataclasses.dataclass(frozen=True)
class DistributedConfig:
    """Process-group description. Defaults read the conventional env vars so
    launchers (mpirun/srun-style wrappers, or the test harness) can inject
    them without code changes."""

    coordinator_address: str | None = None  # "host:port"
    num_processes: int | None = None
    process_id: int | None = None

    @staticmethod
    def from_env() -> "DistributedConfig":
        return DistributedConfig(
            coordinator_address=os.environ.get("LSR_COORDINATOR") or None,
            num_processes=(int(os.environ["LSR_NUM_PROCESSES"])
                           if "LSR_NUM_PROCESSES" in os.environ else None),
            process_id=(int(os.environ["LSR_PROCESS_ID"])
                        if "LSR_PROCESS_ID" in os.environ else None),
        )


def initialize_distributed(config: DistributedConfig | None = None) -> bool:
    """Bring up the jax multi-process runtime (no-op single-process).

    ≙ the engines' cluster bring-up the reference delegates to Flink/Spark
    (SURVEY §2.3). Returns True iff a multi-process group was initialized.
    On TPU pods ``jax.distributed.initialize()`` auto-discovers everything;
    explicit coordinator/process values are for CPU/GPU clusters and tests.
    """
    cfg = config or DistributedConfig.from_env()
    if cfg.num_processes in (None, 1) and cfg.coordinator_address is None:
        return False
    import jax

    # XLA:CPU runs a computation spanning processes through gloo, the
    # installed jax's default ``jax_cpu_collectives_implementation``;
    # nothing is set here, so a process that owns TPUs is left alone.
    jax.distributed.initialize(
        coordinator_address=cfg.coordinator_address,
        num_processes=cfg.num_processes,
        process_id=cfg.process_id,
    )
    return True


def host_rating_shard(
    ru: np.ndarray,
    ri: np.ndarray,
    rv: np.ndarray,
    process_id: int,
    num_processes: int,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """This host's rating partition: ``hash(user) % num_processes``.

    ≙ the driver→worker rating shipment (``partitionCustom`` by user,
    PSOfflineMF.scala:70-72 / OfflineSpark.scala:135-148) — except no
    process ever materializes another host's shard. Every host applies the
    same deterministic filter to its (replicated or range-read) input, so
    the union over hosts is exactly the dataset.
    """
    m = (np.abs(ru) % num_processes) == process_id
    return ru[m], ri[m], rv[m]


def make_global_array(host_data: np.ndarray, mesh, spec):
    """Build a global mesh-sharded array where each process supplies the
    shards of ITS addressable devices from ``host_data`` (indexed by GLOBAL
    row). ``host_data`` may be just this host's slice of a notional global
    array as long as ``host_data[idx]`` resolves the global indices of local
    shards — for the dense block layouts here, passing the full logical
    array on every host (tests) or a host-local view with global indexing
    (real pods) both work.

    Legacy raw-spec surface; ``Partitioner.make_global_array`` /
    ``Partitioner.place`` are the rules-table spellings new code uses.
    """
    import jax

    from large_scale_recommendation_tpu.parallel.partitioner import (
        raw_sharding,
    )

    sharding = raw_sharding(mesh, spec)
    return jax.make_array_from_callback(
        host_data.shape, sharding, lambda idx: host_data[idx]
    )


@dataclasses.dataclass
class GlobalBlockedArrays:
    """Mesh-ready blocked problem from ``global_device_blocked``: strata and
    factors device-major-sharded over the block axis, id maps replicated.
    Feed directly to ``parallel.dsgd_mesh.build_mesh_dsgd_step``."""

    U: object  # [k·rpb_u, rank] sharded P(blocks)
    V: object  # [k·rpb_v, rank] sharded P(blocks)
    ru: object  # [k, k, bmax] device-major LOCAL user rows, sharded dim 0
    ri: object
    rv: object
    rw: object
    icu: object  # collision scales, device-major, sharded dim 0
    icv: object
    omega_u: object  # [k·rpb_u] sharded P(blocks)
    omega_v: object
    row_of_user: np.ndarray  # host copies of the replicated id→row maps
    row_of_item: np.ndarray
    omega_u_host: np.ndarray
    omega_v_host: np.ndarray
    num_blocks: int
    rows_per_block_u: int
    rows_per_block_v: int
    minibatch: int

    def holdout_rows(self, hu: np.ndarray, hi: np.ndarray):
        """Rows + seen-in-training mask for evaluation (host-side maps)."""
        ur = self.row_of_user[hu]
        ir = self.row_of_item[hi]
        mask = ((self.omega_u_host[ur] > 0)
                & (self.omega_v_host[ir] > 0)).astype(np.float32)
        return ur, ir, mask


def global_device_blocked(
    u_local: np.ndarray,
    i_local: np.ndarray,
    r_local: np.ndarray,
    w_local: np.ndarray,
    num_users: int,
    num_items: int,
    mesh,
    minibatch_multiple: int = 1,
    seed: int = 0,
    row_multiple: int = 8,
    rank: int = 8,
    init_scale: float = 0.1,
) -> GlobalBlockedArrays:
    """DSGD blocking computed GLOBALLY on a (possibly multi-process) mesh.

    The multi-host spelling of the ring's blocking
    (``data.device_blocking.mesh_block_problem``, what ``MeshDSGD
    .fit_device`` runs): each process contributes only ITS shard of the
    ratings, the global entry array is assembled shard-wise
    (``jax.make_array_from_process_local_data``) and the same per-chip
    programs run over the process-spanning mesh: counts summed over the
    ring, the replicated row assignment, one ``all_to_all`` to the chip
    of each entry's user block, each chip's own row of the layout, then
    the factor init and omegas in the ring's shardings. No host ever
    materializes another host's shard OR the global layout.

    Contract: every process passes equal-length arrays (pad with
    ``w_local=0`` no-op entries — the same weight-0 contract as the
    single-process pipeline), length divisible by the process's local
    device count. Ids are dense, as in ``device_block_problem``.
    """
    from large_scale_recommendation_tpu.data import device_blocking as db
    from large_scale_recommendation_tpu.parallel.dsgd_mesh import (
        sharded_init,
    )
    from large_scale_recommendation_tpu.parallel.partitioner import (
        as_partitioner,
    )

    part = as_partitioner(mesh)

    def glob(a, dt):
        return part.from_process_local(np.asarray(a, dt), "ratings")

    p = db.mesh_block_problem(
        glob(u_local, np.int32), glob(i_local, np.int32),
        glob(r_local, np.float32), num_users, num_items, part,
        minibatch_multiple=minibatch_multiple, seed=seed,
        row_multiple=row_multiple, weights=glob(w_local, np.float32))
    U, V, ou, ov = sharded_init(part, p.id_of_user_row, p.id_of_item_row,
                                p.omega_u, p.omega_v, rank, init_scale)
    return GlobalBlockedArrays(
        U=U, V=V, ru=p.ru, ri=p.ri, rv=p.rv, rw=p.rw, icu=p.icu, icv=p.icv,
        omega_u=ou, omega_v=ov,
        row_of_user=np.asarray(p.row_of_user).astype(np.int64),
        row_of_item=np.asarray(p.row_of_item).astype(np.int64),
        omega_u_host=np.asarray(p.omega_u),
        omega_v_host=np.asarray(p.omega_v),
        num_blocks=p.num_blocks, rows_per_block_u=p.rows_per_block_u,
        rows_per_block_v=p.rows_per_block_v, minibatch=p.minibatch,
    )
