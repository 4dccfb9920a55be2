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

    The multi-host form of the on-device pipeline
    (``data.device_blocking``): each process contributes only ITS shard of
    the ratings; the global entry array is assembled shard-wise
    (``jax.make_array_from_process_local_data``) and the whole blocking —
    weighted counts, balanced row assignment, bucket sort, stratum scatter,
    collision scales, factor init — runs as jitted global computations with
    explicit output shardings. XLA inserts the cross-process collectives
    the engines' blocking shuffles became (SURVEY §2.3); no host ever
    materializes another host's shard OR the global layout.

    Contract: every process passes equal-length arrays (pad with
    ``w_local=0`` no-op entries — the same weight-0 contract as the
    single-process pipeline), length divisible by the process's local
    device count. Ids are dense, as in ``device_block_problem``.
    """
    import jax
    import jax.numpy as jnp

    from large_scale_recommendation_tpu.data import device_blocking as db
    from large_scale_recommendation_tpu.parallel.partitioner import (
        as_partitioner,
    )

    part = as_partitioner(mesh)
    mesh = part.mesh
    k = part.num_blocks
    shard = part.sharding("ratings")
    rep = part.replicated()
    dm3 = part.sharding("ratings")  # [k, k, b] device-major: dim 0 only

    def glob(a, dt):
        return part.from_process_local(np.asarray(a, dt), "ratings")

    gu = glob(u_local, np.int32)
    gi = glob(i_local, np.int32)
    gr = glob(r_local, np.float32)
    gw = glob(w_local, np.float32)

    rpb_u = db.rows_per_block(num_users, k, row_multiple)
    rpb_v = db.rows_per_block(num_items, k, row_multiple)
    base = jax.random.PRNGKey(seed)

    def phase_a(u, i, r, w):
        counts_u, counts_v = db._weighted_counts(u, i, w, num_users,
                                                 num_items)
        row_of_u, omega_u, id_of_ur = db._assign_rows(
            jax.random.fold_in(base, 10), counts_u, k, rpb_u, k * rpb_u)
        row_of_i, omega_v, id_of_ir = db._assign_rows(
            jax.random.fold_in(base, 11), counts_v, k, rpb_v, k * rpb_v)
        sorted_ = db._bucket_entries(
            jax.random.fold_in(base, 12), u, i, r, w, row_of_u, row_of_i,
            k, rpb_u, rpb_v)
        return sorted_[0], sorted_[1:], (
            row_of_u, row_of_i, omega_u, omega_v, id_of_ur, id_of_ir)

    pa = jax.jit(phase_a,
                 out_shardings=(rep, (shard,) * 5, (rep,) * 6))
    sizes, sorted_entries, maps = pa(gu, gi, gr, gw)
    row_of_u, row_of_i, omega_u, omega_v, id_of_ur, id_of_ir = maps

    sizes_host = np.asarray(sizes)  # replicated → legal on every process
    bmax = max(int(sizes_host.max()), 1)
    mbm = max(minibatch_multiple, 1)
    bmax = -(-bmax // mbm) * mbm

    def phase_b(flat_s, urow_s, irow_s, vals_s, w_s, sizes):
        su, si, sv, sw, icu, icv = db._layout(
            flat_s, urow_s, irow_s, vals_s, w_s, sizes, k, bmax, mbm, None)
        # stratum-major [s, p, b] global rows → device-major [p, s, b]
        # local rows (≙ dsgd_mesh.device_major_local_strata, on mesh)
        ru = jnp.transpose(su, (1, 0, 2)) % rpb_u
        ri = jnp.transpose(si, (1, 0, 2)) % rpb_v
        rv = jnp.transpose(sv, (1, 0, 2))
        rw = jnp.transpose(sw, (1, 0, 2))
        icu = jnp.transpose(icu, (1, 0, 2))
        icv = jnp.transpose(icv, (1, 0, 2))
        return ru, ri, rv, rw, icu, icv

    pb = jax.jit(phase_b, out_shardings=(dm3,) * 6)
    ru, ri, rv, rw, icu, icv = pb(*sorted_entries, sizes)

    from large_scale_recommendation_tpu.core.initializers import (
        _keyed_uniform_rows_padded,
    )

    def init_fn(id_u, id_v):
        key = jax.random.PRNGKey(0)
        s = jnp.float32(init_scale)
        return (_keyed_uniform_rows_padded(key, id_u, rank, s),
                _keyed_uniform_rows_padded(key, id_v, rank, s))

    U, V = jax.jit(init_fn, out_shardings=(
        part.sharding("users", "rank"), part.sharding("items", "rank"),
    ))(id_of_ur, id_of_ir)
    ou, ov = jax.jit(lambda a, b: (a, b), out_shardings=(
        part.sharding("users"), part.sharding("items"),
    ))(omega_u, omega_v)

    return GlobalBlockedArrays(
        U=U, V=V, ru=ru, ri=ri, rv=rv, rw=rw, icu=icu, icv=icv,
        omega_u=ou, omega_v=ov,
        row_of_user=np.asarray(row_of_u).astype(np.int64),
        row_of_item=np.asarray(row_of_i).astype(np.int64),
        omega_u_host=np.asarray(omega_u),
        omega_v_host=np.asarray(omega_v),
        num_blocks=k, rows_per_block_u=rpb_u, rows_per_block_v=rpb_v,
        minibatch=mbm,
    )
