"""Mesh DSGD tests on the 8-device virtual CPU mesh.

Key property: the mesh implementation and the single-device implementation
run the SAME schedule over the SAME blocked data, so with identical seeds
they must produce (near-)identical factors — the ppermute rotation is just a
different physical realization of the stratum walk (≙ nextRatingBlock,
DSGDforMF.scala:611-619).
"""

import numpy as np
import jax
import pytest

from large_scale_recommendation_tpu.core.generators import SyntheticMFGenerator
from large_scale_recommendation_tpu.models.dsgd import DSGD, DSGDConfig
from large_scale_recommendation_tpu.parallel.mesh import (
    make_block_mesh,
    ring_backward,
)
from large_scale_recommendation_tpu.parallel.dsgd_mesh import (
    MeshDSGD,
    MeshDSGDConfig,
    device_major_local_strata,
)
from large_scale_recommendation_tpu.data import blocking


@pytest.fixture(scope="module")
def gen():
    return SyntheticMFGenerator(num_users=200, num_items=150, rank=8,
                                noise=0.05, seed=0)


class TestRing:
    def test_ring_backward_pattern(self):
        assert ring_backward(4) == [(0, 3), (1, 0), (2, 1), (3, 2)]

    def test_mesh_creation(self):
        mesh = make_block_mesh(8)
        assert mesh.shape["blocks"] == 8

    def test_mesh_too_large_raises(self):
        with pytest.raises(ValueError):
            make_block_mesh(1000)


class TestDeviceMajorLayout:
    def test_local_indices_in_range(self):
        g = SyntheticMFGenerator(num_users=100, num_items=90, rank=4, seed=1)
        prob = blocking.block_problem(g.generate(3000), num_blocks=4, seed=0)
        ru, ri, rv, rw = device_major_local_strata(prob)
        assert ru.shape[0] == 4 and ru.shape[1] == 4
        assert ru.max() < prob.users.rows_per_block
        assert ri.max() < prob.items.rows_per_block
        # device-major cell [p, s] holds block (p, (p+s)%k): verify against
        # the stratum-major source [s, p]
        np.testing.assert_array_equal(rv[2, 3], prob.ratings.values[3, 2])


class TestMeshDSGDDevicePipeline:
    def test_fit_device_matches_single_device_fit_device(self, gen):
        """Mesh fit_device and single-device fit_device build the SAME
        on-chip blocked layout (same seed) and run the same schedule →
        factors must agree to float tolerance."""
        train = gen.generate(10000)
        ru, ri, rv, _ = train.to_numpy()
        nu, ni = 200, 150
        mesh = make_block_mesh(4)
        mcfg = MeshDSGDConfig(num_factors=8, lambda_=0.01, iterations=4,
                              learning_rate=0.05, lr_schedule="constant",
                              seed=0, minibatch_size=256, init_scale=0.3)
        mm = MeshDSGD(mcfg, mesh=mesh).fit_device(ru, ri, rv, nu, ni)

        scfg = DSGDConfig(num_factors=8, lambda_=0.01, iterations=4,
                          learning_rate=0.05, lr_schedule="constant",
                          seed=0, minibatch_size=256, init_scale=0.3)
        sm = DSGD(scfg).fit_device(ru, ri, rv, nu, ni, num_blocks=4)

        np.testing.assert_allclose(np.asarray(mm.U), np.asarray(sm.U),
                                   rtol=2e-3, atol=2e-4)
        np.testing.assert_allclose(np.asarray(mm.V), np.asarray(sm.V),
                                   rtol=2e-3, atol=2e-4)
        # identical model surface: same predictions for the same ids
        some_u = ru[:100]
        some_i = ri[:100]
        np.testing.assert_allclose(mm.predict(some_u, some_i),
                                   sm.predict(some_u, some_i),
                                   rtol=2e-3, atol=2e-4)

    def test_fit_device_converges_on_mesh(self, gen):
        train = gen.generate(20000)
        test = gen.generate(2000)
        ru, ri, rv, _ = train.to_numpy()
        mesh = make_block_mesh(8)
        # lr 0.2/15 sweeps measured 0.0702 (noise floor 0.05); lr 0.1/10
        # is still on the bilinear-bootstrap plateau (0.30)
        cfg = MeshDSGDConfig(num_factors=8, lambda_=0.02, iterations=15,
                             learning_rate=0.2, lr_schedule="constant",
                             seed=0, minibatch_size=128, init_scale=0.2)
        m = MeshDSGD(cfg, mesh=mesh).fit_device(ru, ri, rv, 200, 150)
        assert m.rmse(test) < 0.15  # noise floor 0.05


class TestMeshDSGD:
    def test_matches_single_device(self, gen):
        """Mesh and single-device runs execute the same schedule → factors
        must agree to float tolerance."""
        train = gen.generate(10000)
        mesh = make_block_mesh(4)
        mcfg = MeshDSGDConfig(num_factors=8, lambda_=0.01, iterations=4,
                              learning_rate=0.05, lr_schedule="constant",
                              seed=0, minibatch_size=256, init_scale=0.3)
        mm = MeshDSGD(mcfg, mesh=mesh).fit(train)

        scfg = DSGDConfig(num_factors=8, lambda_=0.01, iterations=4,
                          learning_rate=0.05, lr_schedule="constant",
                          seed=0, minibatch_size=256, init_scale=0.3)
        sm = DSGD(scfg).fit(train, num_blocks=4)

        np.testing.assert_allclose(np.asarray(mm.U), np.asarray(sm.U),
                                   rtol=2e-3, atol=2e-4)
        np.testing.assert_allclose(np.asarray(mm.V), np.asarray(sm.V),
                                   rtol=2e-3, atol=2e-4)

    def test_bf16_tracks_f32_at_small_lr(self, gen):
        """factor_dtype='bfloat16' on the mesh XLA route must CONVERGE
        like f32, not just run: the route upcasts once per jitted
        segment (the whole scan), so gradient accumulation is exact
        across every sweep. The regression this pins: rounding to bf16
        after every block sweep silently swallows small-lr updates
        (below bf16's ~8-bit mantissa) — measured as RMSE frozen at the
        init plateau while f32 kept converging. Small lr on purpose."""
        train = gen.generate(10000)
        test = gen.generate(2000)
        mesh = make_block_mesh(4)

        def run(dt):
            cfg = MeshDSGDConfig(num_factors=8, lambda_=0.02,
                                 iterations=12, learning_rate=0.02,
                                 lr_schedule="constant", seed=0,
                                 minibatch_size=256, init_scale=0.3,
                                 factor_dtype=dt)
            return MeshDSGD(cfg, mesh=mesh).fit(train)

        mf, mh = run("float32"), run("bfloat16")
        assert str(mh.U.dtype) == "bfloat16"
        rf, rh = mf.rmse(test), mh.rmse(test)
        # segment-cadence rounding: one bf16 round on exit — the RMSE
        # gap is quantization noise, not a convergence gap
        assert abs(rf - rh) < 0.02, (rf, rh)

    def test_convergence_8_devices(self):
        # fresh generator: the shared module fixture's RNG position depends
        # on which tests ran before (order-dependent data)
        gen = SyntheticMFGenerator(num_users=200, num_items=150, rank=8,
                                   noise=0.05, seed=42)
        train = gen.generate(15000)
        test = gen.generate(2000)
        # 200 users / 8 devices = 25 distinct user rows per block: keep the
        # minibatch at or below the block width (see test_dsgd.py note).
        cfg = MeshDSGDConfig(num_factors=8, lambda_=0.01, iterations=30,
                             learning_rate=0.1, lr_schedule="constant",
                             seed=0, minibatch_size=32, init_scale=0.3)
        model = MeshDSGD(cfg, mesh=make_block_mesh(8)).fit(train)
        rmse = model.rmse(test)
        assert rmse < 0.12, f"mesh RMSE {rmse}"

    def test_convergence_on_skewed_data(self):
        """Power-law user/item popularity (≙ ExponentialRatingGen workloads)
        must not break mesh-DSGD convergence or blow up stratum padding."""
        gen = SyntheticMFGenerator(num_users=240, num_items=160, rank=8,
                                   noise=0.05, seed=11, skew_lam=2.5)
        train = gen.generate(20000)
        test = gen.generate(2000)
        prob = blocking.block_problem(train, num_blocks=8, seed=0)
        assert prob.ratings.max_pad_ratio < 1.5, prob.ratings.max_pad_ratio
        cfg = MeshDSGDConfig(num_factors=8, lambda_=0.01, iterations=30,
                             learning_rate=0.1, lr_schedule="constant",
                             seed=0, minibatch_size=32, init_scale=0.3)
        model = MeshDSGD(cfg, mesh=make_block_mesh(8)).fit(train)
        rmse = model.rmse(test)
        assert rmse < 0.12, f"skewed mesh RMSE {rmse}"

    def test_output_sharded_over_mesh(self, gen):
        train = gen.generate(5000)
        mesh = make_block_mesh(4)
        cfg = MeshDSGDConfig(num_factors=4, iterations=2, seed=0,
                             minibatch_size=128)
        model = MeshDSGD(cfg, mesh=mesh).fit(train)
        # U stays sharded over the block axis (no implicit gather)
        assert len(model.U.sharding.device_set) == 4
