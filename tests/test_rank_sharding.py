"""Rank-sharded factors end-to-end (ISSUE 16): the ``'rank' → 'model'``
rule at ``model_parallel ∈ {2, 4}`` must reproduce the model=1
computation — mesh DSGD to fp reduction tolerance, explicit mesh ALS
bit-compatibly, and serving (mesh top-k + the two-stage retriever) with
IDENTICAL top-k ids — while dividing per-device factor/catalog bytes.

Parity compares EQUAL data-axis sizes: blocking pads tables per k
(= devices / model_parallel), so the m=2 run on 8 devices (k=4) pins
against a 1-D mesh of 4 devices, and m=4 (k=2) against 2 devices —
same padded shapes, same serpentine deal, same minibatch order; the
ONLY delta is the rank split and its psum.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from large_scale_recommendation_tpu.core.generators import (
    SyntheticMFGenerator,
)
from large_scale_recommendation_tpu.models.als import ALSConfig
from large_scale_recommendation_tpu.ops import sgd as sgd_ops
from large_scale_recommendation_tpu.parallel.als_mesh import MeshALS
from large_scale_recommendation_tpu.parallel.dsgd_mesh import (
    MeshDSGD,
    MeshDSGDConfig,
)
from large_scale_recommendation_tpu.parallel.partitioner import Partitioner
from large_scale_recommendation_tpu.parallel.serving import (
    mesh_top_k_recommend,
    shard_catalog,
)
from large_scale_recommendation_tpu.serving.retrieval import (
    RetrievalConfig,
    TwoStageRetriever,
    build_quantized_catalog,
)

NU, NI = 96, 64


@pytest.fixture(scope="module")
def ratings():
    return SyntheticMFGenerator(num_users=NU, num_items=NI, rank=4,
                                noise=0.1, seed=0).generate(6000)


def _dsgd_cfg(rank=8, iters=3):
    return MeshDSGDConfig(num_factors=rank, lambda_=0.01, iterations=iters,
                          learning_rate=0.05, lr_schedule="constant",
                          seed=0, minibatch_size=64, init_scale=0.3)


def _fit_dsgd(part, ratings, rank=8, iters=3):
    ru, ri, rv, _ = ratings.to_numpy()
    m = MeshDSGD(_dsgd_cfg(rank, iters), partitioner=part).fit_device(
        ru, ri, rv, NU, NI)
    jax.block_until_ready((m.U, m.V))
    return m


class TestMeshDSGDParity:
    @pytest.mark.parametrize("m", [2, 4])
    def test_rank_sharded_matches_model1_equal_k(self, ratings, m):
        """Same seed, same blocked layout (equal k) ⇒ same factors up
        to the psum's reduction-order fp tolerance (measured ~3e-08).
        The prediction dot is the ONE reduced term; everything row-space
        runs unchanged on rank slices."""
        base = _fit_dsgd(Partitioner(num_devices=8 // m), ratings)
        shd = _fit_dsgd(Partitioner(num_devices=8, model_parallel=m),
                        ratings)
        np.testing.assert_allclose(np.asarray(shd.U), np.asarray(base.U),
                                   atol=1e-5, rtol=0)
        np.testing.assert_allclose(np.asarray(shd.V), np.asarray(base.V),
                                   atol=1e-5, rtol=0)

    def test_factors_sharded_over_model_axis(self, ratings):
        part = Partitioner(num_devices=8, model_parallel=2)
        model = _fit_dsgd(part, ratings)
        spec = model.U.sharding.spec
        assert tuple(spec) == ("data", "model"), spec
        # each device holds rank/m columns of its row block
        shard = model.U.addressable_shards[0]
        assert shard.data.shape[1] == 8 // 2

    def test_rank_not_divisible_fails_loudly(self, ratings):
        ru, ri, rv, _ = ratings.to_numpy()
        part = Partitioner(num_devices=8, model_parallel=4)
        with pytest.raises(ValueError, match="divisible"):
            MeshDSGD(_dsgd_cfg(rank=6), partitioner=part).fit_device(
                ru, ri, rv, NU, NI)


class TestMeshALSParity:
    def _fit(self, part, ratings, implicit=False):
        cfg = ALSConfig(num_factors=8, lambda_=0.1, iterations=2, seed=0,
                        implicit_alpha=40.0 if implicit else None)
        m = MeshALS(cfg, partitioner=part).fit(ratings)
        jax.block_until_ready((m.U, m.V))
        return m

    @pytest.mark.parametrize("m", [2, 4])
    def test_explicit_bit_compatible_equal_k(self, ratings, m):
        """ALS solves per row on the all-gathered full-rank table: the
        gather concatenates contiguous column slices bit-identically,
        so the rank-sharded solve IS the model=1 solve (measured
        max|dU| = 0.0); each device then keeps only its rank slice."""
        base = self._fit(Partitioner(num_devices=8 // m), ratings)
        shd = self._fit(Partitioner(num_devices=8, model_parallel=m),
                        ratings)
        np.testing.assert_array_equal(np.asarray(shd.U),
                                      np.asarray(base.U))
        np.testing.assert_array_equal(np.asarray(shd.V),
                                      np.asarray(base.V))

    def test_implicit_bit_compatible_equal_k(self, ratings):
        """The implicit path's rank-sharded Gram (row-chunked partial
        einsum + psum over 'model') must reproduce model=1 bit-for-bit
        — including NaN propagation where the baseline NaNs (this
        environment's pre-existing implicit failure), so equality is
        pinned, never finiteness."""
        base = self._fit(Partitioner(num_devices=4), ratings,
                         implicit=True)
        shd = self._fit(Partitioner(num_devices=8, model_parallel=2),
                        ratings, implicit=True)
        np.testing.assert_array_equal(np.asarray(shd.U),
                                      np.asarray(base.U))

    def test_rank_not_divisible_fails_loudly(self, ratings):
        part = Partitioner(num_devices=8, model_parallel=4)
        cfg = ALSConfig(num_factors=6, lambda_=0.1, iterations=1, seed=0)
        with pytest.raises(ValueError, match="divisible"):
            MeshALS(cfg, partitioner=part).fit(ratings)


class TestMeshServingParity:
    @pytest.mark.parametrize("m", [2, 4])
    def test_topk_ids_identical_equal_k(self, m):
        rng = np.random.default_rng(1)
        U = rng.normal(size=(40, 8)).astype(np.float32)
        V = rng.normal(size=(64, 8)).astype(np.float32)
        rows = np.arange(40, dtype=np.int32)
        base_part = Partitioner(num_devices=8 // m)
        shd_part = Partitioner(num_devices=8, model_parallel=m)
        ids_b, sc_b = mesh_top_k_recommend(
            U, V, rows, k=10, catalog=shard_catalog(V, base_part))
        ids_s, sc_s = mesh_top_k_recommend(
            U, V, rows, k=10, catalog=shard_catalog(V, shd_part))
        np.testing.assert_array_equal(np.asarray(ids_s),
                                      np.asarray(ids_b))
        np.testing.assert_allclose(np.asarray(sc_s), np.asarray(sc_b),
                                   atol=1e-5, rtol=0)

    def test_shard_catalog_rank_not_divisible_fails(self):
        V = np.zeros((64, 6), np.float32)
        part = Partitioner(num_devices=8, model_parallel=4)
        with pytest.raises(ValueError, match="divisible"):
            shard_catalog(V, part)


EMPTY_EXCL = (np.zeros(8, np.int32), np.zeros(8, np.int32),
              np.full(8, np.inf, np.float32))


class TestTwoStageRetrieverRankSharded:
    def _tables(self, seed=2, rank=16):
        rng = np.random.default_rng(seed)
        V = rng.normal(size=(512, rank)).astype(np.float32)
        Q = rng.normal(size=(32, rank)).astype(np.float32)
        return V, Q

    @pytest.mark.parametrize("m", [2, 4])
    @pytest.mark.parametrize("clustered", [False, True])
    def test_topk_ids_identical(self, m, clustered):
        """Stage-1 int8 codes are computed from FULL rows before the
        column split (scales identical at any m) and int8 partial dots
        psum exactly in int32 — same candidates, same exact-rescore,
        same ids at every model size."""
        V, Q = self._tables()
        cfg = RetrievalConfig(n_clusters=8 if clustered else None,
                              kmeans_iters=2)
        base = TwoStageRetriever(V, config=cfg)
        shd = TwoStageRetriever(
            V, config=cfg,
            partitioner=Partitioner(num_devices=8, model_parallel=m))
        sc_b, ids_b = base.topk(Q, EMPTY_EXCL, k=10)
        sc_s, ids_s = shd.topk(Q, EMPTY_EXCL, k=10)
        np.testing.assert_array_equal(np.asarray(ids_s),
                                      np.asarray(ids_b))
        np.testing.assert_allclose(np.asarray(sc_s), np.asarray(sc_b),
                                   atol=1e-5, rtol=0)

    def test_apply_delta_requantizes_sharded(self):
        V, Q = self._tables()
        cfg = RetrievalConfig(n_clusters=None)
        base = TwoStageRetriever(V, config=cfg)
        shd = TwoStageRetriever(
            V, config=cfg,
            partitioner=Partitioner(num_devices=8, model_parallel=2))
        rows = np.array([3, 100, 511], np.int32)
        vals = np.random.default_rng(5).normal(
            size=(3, V.shape[1])).astype(np.float32)
        base.apply_delta(rows, vals, version=1)
        shd.apply_delta(rows, vals, version=1)
        _, ids_b = base.topk(Q, EMPTY_EXCL, k=10)
        _, ids_s = shd.topk(Q, EMPTY_EXCL, k=10)
        np.testing.assert_array_equal(np.asarray(ids_s),
                                      np.asarray(ids_b))

    def test_per_device_bytes_shrink(self):
        """The footprint claim: int8 codes + f32 rescore rows divide by
        m, only per-row scales/weights replicate — per-device bytes at
        m=4 land well under half of replicated (the ≤ ~30% acceptance
        is pinned at rank 128 in the MULTICHIP round; this guards the
        mechanism at test scale)."""
        V, _ = self._tables(rank=32)
        cfg = RetrievalConfig(n_clusters=None)
        base = TwoStageRetriever(V, config=cfg)
        shd = TwoStageRetriever(
            V, config=cfg,
            partitioner=Partitioner(num_devices=8, model_parallel=4))
        assert shd.nbytes_per_device() < 0.5 * base.nbytes_per_device()

    def test_build_quantized_catalog_rank_not_divisible(self):
        V = np.zeros((64, 6), np.float32)
        part = Partitioner(num_devices=8, model_parallel=4)
        with pytest.raises(ValueError, match="divisible"):
            build_quantized_catalog(V, partitioner=part)


class TestRankShardedCheckpoint:
    def _manager(self, tmp_path):
        from large_scale_recommendation_tpu.utils.checkpoint import (
            ShardedCheckpointManager,
        )

        return ShardedCheckpointManager(str(tmp_path))

    def test_round_trip_model2(self, tmp_path):
        from large_scale_recommendation_tpu.utils.checkpoint import (
            restore_segment_state_sharded,
        )

        rng = np.random.default_rng(0)
        U = rng.normal(size=(32, 8)).astype(np.float32)
        V = rng.normal(size=(24, 8)).astype(np.float32)
        part = Partitioner(num_devices=8, model_parallel=2)
        mgr = self._manager(tmp_path)
        mgr.save(5, {"U": part.shard(jnp.asarray(U), "users", "rank"),
                     "V": part.shard(jnp.asarray(V), "items", "rank")},
                 {"kind": "mesh"})
        U2, V2, done = restore_segment_state_sharded(
            mgr, "mesh", np.zeros_like(U), np.zeros_like(V),
            partitioner=part)
        assert done == 5
        np.testing.assert_array_equal(np.asarray(U2), U)
        np.testing.assert_array_equal(np.asarray(V2), V)
        assert U2.sharding == part.sharding("users", "rank")

    @pytest.mark.parametrize("m_save,m_load", [(2, 1), (2, 4), (1, 2)])
    def test_changed_model_size_resume_reshards(self, tmp_path,
                                                m_save, m_load):
        """Resume across a CHANGED model size: the 2-D overlap fill
        reassembles each device's slice from whichever saved pieces
        cover it — including old row-only (pre-rank-sharding) files
        restored onto a 2-D layout."""
        from large_scale_recommendation_tpu.utils.checkpoint import (
            restore_segment_state_sharded,
        )

        rng = np.random.default_rng(1)
        U = rng.normal(size=(32, 8)).astype(np.float32)
        V = rng.normal(size=(24, 8)).astype(np.float32)
        saver = Partitioner(num_devices=8, model_parallel=m_save)
        loader = Partitioner(num_devices=8, model_parallel=m_load)
        mgr = self._manager(tmp_path)
        mgr.save(3, {"U": saver.shard(jnp.asarray(U), "users", "rank"),
                     "V": saver.shard(jnp.asarray(V), "items", "rank")},
                 {"kind": "mesh"})
        U2, V2, done = restore_segment_state_sharded(
            mgr, "mesh", np.zeros_like(U), np.zeros_like(V),
            partitioner=loader)
        assert done == 3
        np.testing.assert_array_equal(np.asarray(U2), U)
        np.testing.assert_array_equal(np.asarray(V2), V)

    def test_missing_columns_fail_loudly(self, tmp_path):
        """A snapshot whose pieces do not cover a requested region must
        error on the fill-AREA check — never silently misplace rows."""
        rng = np.random.default_rng(2)
        U = rng.normal(size=(32, 8)).astype(np.float32)
        part = Partitioner(num_devices=8, model_parallel=2)
        mgr = self._manager(tmp_path)
        mgr.save(1, {"U": part.shard(jnp.asarray(U), "users", "rank")},
                 {"kind": "mesh"})
        # doctor the shard file: drop the second column group's pieces
        name = [n for n in os.listdir(tmp_path) if n.endswith(".npz")][0]
        path = os.path.join(str(tmp_path), name)
        with np.load(path) as z:
            payload = {k: z[k] for k in z.files}
        keep = payload["U__cstarts"] == 0
        n_keep = int(keep.sum())
        doctored = {"U__starts": payload["U__starts"][keep],
                    "U__lens": payload["U__lens"][keep],
                    "U__cstarts": payload["U__cstarts"][keep],
                    "U__clens": payload["U__clens"][keep]}
        kept_idx = [j for j, k_ in enumerate(keep) if k_]
        for newj, oldj in enumerate(kept_idx):
            doctored[f"U__p{newj}"] = payload[f"U__p{oldj}"]
        assert n_keep < len(keep)  # the doctoring removed something
        np.savez(path, **doctored)
        with pytest.raises(ValueError, match="missing rows"):
            mgr.restore_array(1, "U", part.sharding("users", "rank"),
                              (32, 8), np.float32)

    def test_fit_device_resume_at_model2(self, ratings, tmp_path):
        """End-to-end through the mesh DSGD superstep loop: 2 sweeps +
        checkpoint, resume for the remaining 2 ⇒ identical factors to
        an unbroken 4-sweep fit at the same model size."""
        ru, ri, rv, _ = ratings.to_numpy()
        part = Partitioner(num_devices=8, model_parallel=2)
        mgr = self._manager(tmp_path)
        MeshDSGD(_dsgd_cfg(iters=2), partitioner=part).fit_device(
            ru, ri, rv, NU, NI, checkpoint_manager=mgr,
            checkpoint_every=2)
        resumed = MeshDSGD(_dsgd_cfg(iters=4),
                           partitioner=part).fit_device(
            ru, ri, rv, NU, NI, checkpoint_manager=mgr,
            checkpoint_every=2, resume=True)
        straight = _fit_dsgd(part, ratings, iters=4)
        np.testing.assert_allclose(np.asarray(resumed.U),
                                   np.asarray(straight.U),
                                   atol=1e-6, rtol=0)


class TestRooflineModelSize:
    def test_bytes_per_sweep_divides_by_model_size(self):
        full = sgd_ops.dsgd_bytes_per_sweep(1000, 64)
        quarter = sgd_ops.dsgd_bytes_per_sweep(1000, 64, model_size=4)
        # the 16-byte COO term is per rating, not per factor column
        assert quarter == 1000 * (4 * 16 * 4 + 16)
        assert quarter < full

    def test_bytes_per_sweep_validates_model_size(self):
        with pytest.raises(ValueError, match="model_size"):
            sgd_ops.dsgd_bytes_per_sweep(1000, 64, model_size=0)
        with pytest.raises(ValueError, match="divisible|divide"):
            sgd_ops.dsgd_bytes_per_sweep(1000, 63, model_size=4)

    def test_collective_bytes_formula(self):
        assert sgd_ops.dsgd_collective_bytes_per_sweep(1000, 64, 1) == 0
        # psum of one f32 per rating: 2·(m−1)/m bytes on the wire per
        # reduced element (ring all-reduce), m=4 ⇒ 1.5 × 4 B × nnz
        assert sgd_ops.dsgd_collective_bytes_per_sweep(1000, 64, 4) == \
            int(1000 * 4 * 2 * 3 / 4)

    def test_roofline_rows_carry_collective_term(self):
        """The interconnect term is its OWN roofline key — wire traffic
        never hides inside the HBM number."""
        from large_scale_recommendation_tpu.obs.introspect import (
            roofline_rows,
        )

        records = [{"key": "train_segment/dsgd", "module": "jit_step",
                    "compiles": 1, "compile_wall_s": 0.1,
                    "flops": 1e6, "bytes_accessed": 1e4}]
        walls = {"train_segment/dsgd":
                 {"execute_count": 2, "execute_total_s": 0.5,
                  "iterations": 8}}
        model_costs = {"train_segment/dsgd": {
            "bytes_per_iteration": 100.0,
            "collective_bytes_per_iteration": 48.0}}
        (row,) = roofline_rows(records, walls, model_costs)
        assert row["model_bytes_per_exec"] == 100.0 * 4
        assert row["model_collective_bytes_per_exec"] == 48.0 * 4
        # replicated kernels (no registered collective term) stay None
        (row1,) = roofline_rows(
            records, walls,
            {"train_segment/dsgd": {"bytes_per_iteration": 100.0}})
        assert row1["model_collective_bytes_per_exec"] is None
