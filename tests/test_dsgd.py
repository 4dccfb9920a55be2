"""Single-device DSGD: oracle parity + convergence integration tests.

Oracle: a NumPy transcription of the reference inner loop
(DSGDforMF.scala:398-417) run in the same minibatch grouping; convergence:
planted low-rank model must reach low RMSE (SURVEY §4 test plan).
"""

import numpy as np
import jax.numpy as jnp
import pytest

from large_scale_recommendation_tpu.core.types import Ratings
from large_scale_recommendation_tpu.core.generators import SyntheticMFGenerator
from large_scale_recommendation_tpu.core.updaters import (
    SGDUpdater,
    RegularizedSGDUpdater,
    inverse_sqrt_lr,
)
from large_scale_recommendation_tpu.models.dsgd import DSGD, DSGDConfig
from large_scale_recommendation_tpu.ops import sgd as sgd_ops


class TestKernelOracle:
    def test_minibatch_update_matches_numpy(self):
        rng = np.random.default_rng(0)
        n_rows, k, b = 20, 6, 8
        U = rng.normal(size=(n_rows, k)).astype(np.float32)
        V = rng.normal(size=(n_rows, k)).astype(np.float32)
        ur = rng.integers(0, n_rows, b)
        ir = rng.integers(0, n_rows, b)
        vals = rng.normal(size=b).astype(np.float32)
        w = np.ones(b, dtype=np.float32)
        omega = np.ones(n_rows, dtype=np.float32) * 2.0
        upd = RegularizedSGDUpdater(learning_rate=0.05, lambda_=0.3,
                                    schedule=lambda lr, t: lr)

        Un, Vn = sgd_ops.sgd_minibatch_update(
            jnp.array(U), jnp.array(V), jnp.array(ur), jnp.array(ir),
            jnp.array(vals), jnp.array(w),
            sgd_ops.lane_view(jnp.array(omega)),
            sgd_ops.lane_view(jnp.array(omega)), upd, 1, collision="sum")

        # NumPy oracle: additive deltas from OLD factors, accumulated
        eU, eV = U.copy(), V.copy()
        for i in range(b):
            u, v = U[ur[i]], V[ir[i]]
            e = vals[i] - u @ v
            eU[ur[i]] += -0.05 * (0.3 / 2.0 * u - e * v)
            eV[ir[i]] += -0.05 * (0.3 / 2.0 * v - e * u)
        np.testing.assert_allclose(np.asarray(Un), eU, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(Vn), eV, rtol=1e-4, atol=1e-5)

    def test_padding_rows_untouched(self):
        """Weight-0 entries must leave factors bit-identical."""
        rng = np.random.default_rng(1)
        U = rng.normal(size=(10, 4)).astype(np.float32)
        V = rng.normal(size=(10, 4)).astype(np.float32)
        ur = np.zeros(8, dtype=np.int32)  # padding points at row 0
        w = np.zeros(8, dtype=np.float32)
        upd = RegularizedSGDUpdater(0.1, 1.0)
        Un, Vn = sgd_ops.sgd_minibatch_update(
            jnp.array(U), jnp.array(V), jnp.array(ur), jnp.array(ur),
            jnp.zeros(8, jnp.float32), jnp.array(w),
            sgd_ops.lane_view(jnp.ones(10)),
            sgd_ops.lane_view(jnp.ones(10)), upd, 1)
        np.testing.assert_array_equal(np.asarray(Un), U)
        np.testing.assert_array_equal(np.asarray(Vn), V)

    def test_batchsize1_matches_sequential_reference_semantics(self):
        """minibatch=1 chains updates exactly like the reference's
        sequential loop (DSGDforMF.scala:398-417)."""
        rng = np.random.default_rng(2)
        n_rows, k, e = 6, 3, 12
        U = rng.normal(size=(n_rows, k)).astype(np.float32)
        V = rng.normal(size=(n_rows, k)).astype(np.float32)
        ur = rng.integers(0, n_rows, e).astype(np.int32)
        ir = rng.integers(0, n_rows, e).astype(np.int32)
        vals = rng.normal(size=e).astype(np.float32)
        lam, lr = 0.2, 0.05
        omega = np.full(n_rows, 2.0, dtype=np.float32)
        upd = RegularizedSGDUpdater(lr, lam, schedule=lambda b, t: b)

        Un, Vn = sgd_ops.sgd_block_sweep(
            jnp.array(U), jnp.array(V), jnp.array(ur), jnp.array(ir),
            jnp.array(vals), jnp.ones(e, jnp.float32),
            jnp.array(omega), jnp.array(omega), upd, 1, minibatch=1)

        eU, eV = U.copy(), V.copy()
        for i in range(e):
            u, v = eU[ur[i]].copy(), eV[ir[i]].copy()
            err = vals[i] - u @ v
            eU[ur[i]] = u - lr * (lam / 2.0 * u - err * v)
            eV[ir[i]] = v - lr * (lam / 2.0 * v - err * u)
        np.testing.assert_allclose(np.asarray(Un), eU, rtol=1e-3, atol=1e-5)
        np.testing.assert_allclose(np.asarray(Vn), eV, rtol=1e-3, atol=1e-5)


class TestDSGDConvergence:
    @pytest.mark.parametrize("num_blocks", [1, 4])
    def test_planted_model_convergence(self, num_blocks):
        gen = SyntheticMFGenerator(num_users=300, num_items=200, rank=8,
                                   noise=0.05, seed=0)
        train = gen.generate(20000)
        test = gen.generate(2000)
        # minibatch sized ≲ rows_per_block (users/k): a block only holds
        # rows_per_block distinct users, so larger minibatches force row
        # collisions whose mean-mode averaging slows convergence (at real
        # scale blocks are 10⁴-10⁵ rows wide and this is moot).
        cfg = DSGDConfig(
            num_factors=8, lambda_=0.01, iterations=20,
            learning_rate=0.1, lr_schedule="constant",
            seed=0, minibatch_size=256 // num_blocks, init_scale=0.3,
        )
        solver = DSGD(cfg)
        model = solver.fit(train, num_blocks=num_blocks)
        rmse = model.rmse(test)
        # planted noise floor is 0.05; < 0.1 means convergence to the floor
        assert rmse < 0.1, f"RMSE {rmse} too high (blocks={num_blocks})"

    def test_risk_decreases(self):
        gen = SyntheticMFGenerator(num_users=100, num_items=80, rank=4,
                                   noise=0.1, seed=1)
        train = gen.generate(5000)
        cfg = DSGDConfig(num_factors=4, lambda_=0.01, iterations=0, seed=0,
                         learning_rate=0.05, minibatch_size=256,
                         init_scale=0.3)
        m0 = DSGD(cfg).fit(train, num_blocks=2)
        risk0 = m0.empirical_risk(train, 0.01)
        cfg10 = DSGDConfig(num_factors=4, lambda_=0.01, iterations=10, seed=0,
                           learning_rate=0.05, minibatch_size=256,
                           init_scale=0.3)
        m1 = DSGD(cfg10).fit(train, num_blocks=2)
        risk1 = m1.empirical_risk(train, 0.01)
        assert risk1 < risk0

    def test_determinism_with_seed(self):
        """≙ the reference's seeded determinism contract
        (DSGDforMF.scala:319-323,553-557)."""
        gen = SyntheticMFGenerator(num_users=50, num_items=40, rank=4, seed=2)
        train = gen.generate(2000)
        cfg = DSGDConfig(num_factors=4, iterations=3, seed=5,
                         minibatch_size=128)
        a = DSGD(cfg).fit(train, num_blocks=2)
        b = DSGD(cfg).fit(train, num_blocks=2)
        np.testing.assert_array_equal(np.asarray(a.U), np.asarray(b.U))
        np.testing.assert_array_equal(np.asarray(a.V), np.asarray(b.V))

    def test_pluggable_updater_seam(self):
        """Injecting core SGDUpdater (unregularized,
        FactorUpdater.scala:35-53) through the DSGD driver."""
        gen = SyntheticMFGenerator(num_users=50, num_items=40, rank=4, seed=3)
        train = gen.generate(3000)
        cfg = DSGDConfig(num_factors=4, iterations=5, seed=0,
                         minibatch_size=128, init_scale=0.3)
        solver = DSGD(cfg, updater=SGDUpdater(learning_rate=0.02))
        model = solver.fit(train, num_blocks=2)
        assert model.rmse(train) < 1.0

    def test_predict_unseen_scores_zero(self):
        gen = SyntheticMFGenerator(num_users=30, num_items=30, rank=4, seed=4)
        model = DSGD(DSGDConfig(num_factors=4, iterations=2,
                                minibatch_size=64)).fit(gen.generate(500))
        scores = model.predict(np.array([0, 99999]), np.array([0, 0]))
        assert scores[1] == 0.0

    def test_predict_return_mask_exposes_join_drop(self):
        """The reference's predict silently drops unseen pairs
        (MatrixFactorization.scala:250-265); return_mask=True surfaces that
        join-drop set so 'model says 0' ≠ 'never seen'."""
        gen = SyntheticMFGenerator(num_users=30, num_items=30, rank=4, seed=4)
        model = DSGD(DSGDConfig(num_factors=4, iterations=2,
                                minibatch_size=64)).fit(gen.generate(500))
        u = np.array([0, 99999, 0])
        i = np.array([0, 0, 99999])
        scores, seen = model.predict(u, i, return_mask=True)
        assert seen.dtype == bool
        np.testing.assert_array_equal(seen, [True, False, False])
        assert scores[1] == 0.0 and scores[2] == 0.0
        # default call unchanged
        np.testing.assert_array_equal(model.predict(u, i), scores)

    def test_unfitted_predict_raises(self):
        with pytest.raises(RuntimeError):
            DSGD().predict(np.array([1]), np.array([1]))


class TestModelExport:
    def test_factor_vectors_roundtrip(self):
        gen = SyntheticMFGenerator(num_users=20, num_items=15, rank=4, seed=5)
        model = DSGD(DSGDConfig(num_factors=4, iterations=1,
                                minibatch_size=64)).fit(gen.generate(300))
        fvs = list(model.user_factors())
        ids = sorted(fv.id for fv in fvs)
        ru, _, _, _ = gen.generate(0).to_numpy()  # not used; check vs index
        assert ids == sorted(i for i in model.users.ids if i >= 0)
        assert all(fv.factors.shape == (4,) for fv in fvs)


class TestPrecomputedCollisions:
    """Precomputed minibatch collision scales (data.blocking.
    minibatch_inv_counts) must be the SAME math as the runtime counters —
    they only move the counting from the kernel hot path to blocking time."""

    def test_precompute_matches_runtime(self):
        gen = SyntheticMFGenerator(num_users=50, num_items=40, rank=4,
                                   noise=0.1, seed=0)
        # small tables + mb > rows_per_block → plenty of collisions
        train = gen.generate(8000)
        base = dict(num_factors=4, lambda_=0.05, iterations=4,
                    learning_rate=0.1, lr_schedule="constant", seed=0,
                    minibatch_size=128, init_scale=0.3)
        on = DSGD(DSGDConfig(precompute_collisions=True, **base)).fit(
            train, num_blocks=2)
        off = DSGD(DSGDConfig(precompute_collisions=False, **base)).fit(
            train, num_blocks=2)
        np.testing.assert_allclose(np.asarray(on.U), np.asarray(off.U),
                                   rtol=2e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(on.V), np.asarray(off.V),
                                   rtol=2e-5, atol=1e-6)

    def test_mesh_precompute_matches_runtime(self):
        from large_scale_recommendation_tpu.parallel.dsgd_mesh import (
            MeshDSGD,
            MeshDSGDConfig,
        )

        gen = SyntheticMFGenerator(num_users=64, num_items=48, rank=4,
                                   noise=0.1, seed=1)
        train = gen.generate(6000)
        base = dict(num_factors=4, lambda_=0.05, iterations=3,
                    learning_rate=0.1, lr_schedule="constant", seed=0,
                    minibatch_size=64, init_scale=0.3)
        on = MeshDSGD(MeshDSGDConfig(precompute_collisions=True,
                                     **base)).fit(train)
        off = MeshDSGD(MeshDSGDConfig(precompute_collisions=False,
                                      **base)).fit(train)
        np.testing.assert_allclose(np.asarray(on.U), np.asarray(off.U),
                                   rtol=2e-5, atol=1e-6)

    def test_inv_counts_values(self):
        from large_scale_recommendation_tpu.data import blocking as blk

        gen = SyntheticMFGenerator(num_users=10, num_items=8, rank=2, seed=2)
        train = gen.generate(500)
        prob = blk.block_problem(train, num_blocks=1, seed=0,
                                 minibatch_multiple=64)
        icu, icv = blk.minibatch_inv_counts(prob.ratings, 64)
        flat_rows = prob.ratings.u_rows.reshape(-1)
        flat_w = prob.ratings.weights.reshape(-1)
        flat_icu = icu.reshape(-1)
        # brute-force check every chunk
        for a in range(0, len(flat_rows), 64):
            rows = flat_rows[a:a + 64]
            w = flat_w[a:a + 64]
            for j in range(64):
                if w[j] == 0:
                    assert flat_icu[a + j] == 1.0
                else:
                    c = int(((rows == rows[j]) & (w > 0)).sum())
                    np.testing.assert_allclose(flat_icu[a + j], 1.0 / c,
                                               rtol=1e-6)


class TestOmegaByLaneRow:
    """The sweep reads ω a 128-lane row at a time (PR 39): the same bits
    as the per-element gather ``omega[rows]`` it replaced."""

    @pytest.mark.parametrize("h", [1, 8, 127, 128, 129, 2224, 4448, 60024])
    def test_take_lane_is_the_gather_bit_for_bit(self, h):
        rng = np.random.default_rng(h)
        omega = rng.integers(0, 3000, h).astype(np.float32)
        omega[rng.integers(0, h, 3)] = [0.0, 1.0, 2.0 ** 24]
        rows = np.concatenate([[0, h - 1], rng.integers(0, h, 509)])
        got = sgd_ops.take_lane(sgd_ops.lane_view(jnp.asarray(omega)),
                                 jnp.asarray(rows, jnp.int32))
        np.testing.assert_array_equal(np.asarray(got), omega[rows])

    @pytest.mark.parametrize("with_omega", [True, False])
    def test_block_sweep_gives_the_per_element_gathers_tables(
            self, monkeypatch, with_omega):
        """``sgd_block_sweep`` with the lane lookup and with the parent's
        ``omega[rows]`` (the view's first ``h`` values) give the same
        tables, bit for bit; without omegas the lookup never runs."""
        rng = np.random.default_rng(3)
        nu, nv, rank, e, mb = 300, 140, 8, 512, 64
        args = (jnp.asarray(rng.normal(size=(nu, rank)), jnp.float32),
                jnp.asarray(rng.normal(size=(nv, rank)), jnp.float32),
                jnp.asarray(rng.integers(0, nu, e), jnp.int32),
                jnp.asarray(rng.integers(0, nv, e), jnp.int32),
                jnp.asarray(rng.normal(size=e), jnp.float32),
                jnp.ones(e, jnp.float32))
        omegas = ((jnp.asarray(rng.integers(1, 40, nu), jnp.float32),
                   jnp.asarray(rng.integers(1, 40, nv), jnp.float32))
                  if with_omega else (None, None))
        upd = RegularizedSGDUpdater(learning_rate=0.05, lambda_=0.3)

        def sweep():
            return sgd_ops.sgd_block_sweep(*args, *omegas, upd, 1, mb,
                                           "mean")

        lanes = sweep()
        monkeypatch.setattr(sgd_ops, "take_lane",
                            lambda view, rows: view.reshape(-1)[rows])
        gathers = sweep()
        for a, b in zip(lanes, gathers):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert not np.array_equal(np.asarray(lanes[0]), np.asarray(args[0]))


def _inv_counts(rows, w, mb):
    """Per-entry 1/occurrence within each minibatch (the precomputed
    collision scales, data.blocking.minibatch_inv_counts semantics)."""
    inv = np.ones_like(w)
    for s in range(0, len(rows), mb):
        sl = slice(s, s + mb)
        cnt = {}
        for r, ww in zip(rows[sl], w[sl]):
            if ww > 0:
                cnt[r] = cnt.get(r, 0) + 1
        inv[sl] = [1.0 / max(cnt.get(r, 1), 1) if ww > 0 else 1.0
                   for r, ww in zip(rows[sl], w[sl])]
    return inv.astype(np.float32)


def _numpy_sweeps(U, V, su, si, sv, sw, omega_u, omega_v, *, k, mb,
                  iterations, t0, lr, lam, collision):
    """``dsgd_train`` written out as loops in float64: sweeps, strata,
    the blocks of a stratum, minibatches, entries. Every delta of a
    minibatch is computed from the rows as they were before it and
    added to the table; λ is divided by the row's ω (at least 1); under
    ``"mean"`` a delta is divided by the weight sum of its row's
    occurrences in the minibatch (at least 1)."""
    U, V = U.astype(np.float64), V.astype(np.float64)
    b = su.shape[-1]
    for sweep in range(iterations):
        eta = lr / np.sqrt(t0 + sweep + 1)
        for s in range(k):
            for p in range(k):
                for start in range(0, b, mb):
                    sl = slice(start, start + mb)
                    ur, ir = su[s, p, sl], si[s, p, sl]
                    r, w = sv[s, p, sl], sw[s, p, sl]
                    U0, V0 = U.copy(), V.copy()
                    cu, cv = {}, {}
                    for n in range(len(ur)):
                        cu[ur[n]] = cu.get(ur[n], 0.0) + w[n]
                        cv[ir[n]] = cv.get(ir[n], 0.0) + w[n]
                    for n in range(len(ur)):
                        u, v = U0[ur[n]], V0[ir[n]]
                        e = w[n] * (r[n] - u @ v)
                        du = -eta * (w[n] * lam / max(omega_u[ur[n]], 1.0)
                                     * u - e * v)
                        dv = -eta * (w[n] * lam / max(omega_v[ir[n]], 1.0)
                                     * v - e * u)
                        if collision == "mean":
                            du = du / max(cu[ur[n]], 1.0)
                            dv = dv / max(cv[ir[n]], 1.0)
                        U[ur[n]] += du
                        V[ir[n]] += dv
    return U, V


@pytest.mark.parametrize("divisor", [1, 4])
@pytest.mark.parametrize("pad_share", [0.0, 0.15])
@pytest.mark.parametrize("collision", ["mean_precomputed", "mean_counted",
                                       "sum"])
def test_dsgd_train_matches_a_numpy_sweep(collision, pad_share, divisor):
    """``ops.sgd.dsgd_train`` against ``_numpy_sweeps`` over two sweeps of
    a hand-built stratum-major layout: k = 4 blocks when a minibatch is a
    whole block visit, k = 2 when it is a quarter of one; a few rows a
    block, so rows repeat inside every minibatch; padding entries (weight
    0, global row 0, as ``data.blocking`` lays them) at ``pad_share``;
    η/√t started at ``t0 = 3``."""
    mode = collision.split("_")[0]
    k = 4 if divisor == 1 else 2
    rpb_u, rpb_v, b, rank = 5, 4, 48, 4
    mb = b // divisor
    lr, lam, t0, iterations = 0.04, 0.1, 3, 2
    rng = np.random.default_rng(17 + divisor)
    su = np.zeros((k, k, b), np.int32)
    si = np.zeros((k, k, b), np.int32)
    for s in range(k):
        for p in range(k):
            su[s, p] = p * rpb_u + rng.integers(0, rpb_u, b)
            si[s, p] = (p + s) % k * rpb_v + rng.integers(0, rpb_v, b)
    sv = rng.normal(0, 1, (k, k, b)).astype(np.float32)
    sw = np.ones((k, k, b), np.float32)
    pad = rng.random((k, k, b)) < pad_share
    sw[pad], su[pad], si[pad] = 0.0, 0, 0
    assert pad.any() == (pad_share > 0)
    omega_u = np.bincount(su.ravel(), sw.ravel(), k * rpb_u)
    omega_v = np.bincount(si.ravel(), sw.ravel(), k * rpb_v)
    U = rng.normal(0, 0.3, (k * rpb_u, rank)).astype(np.float32)
    V = rng.normal(0, 0.3, (k * rpb_v, rank)).astype(np.float32)

    if collision == "mean_precomputed":
        inv = tuple(
            jnp.asarray(np.stack([[_inv_counts(x[s, p], sw[s, p], mb)
                                   for p in range(k)] for s in range(k)]))
            for x in (su, si))
    else:
        inv = (None, None)
    upd = RegularizedSGDUpdater(learning_rate=lr, lambda_=lam,
                                schedule=inverse_sqrt_lr)
    got = sgd_ops.dsgd_train(
        jnp.asarray(U), jnp.asarray(V), jnp.asarray(su), jnp.asarray(si),
        jnp.asarray(sv), jnp.asarray(sw),
        jnp.asarray(omega_u, jnp.float32), jnp.asarray(omega_v, jnp.float32),
        *inv, updater=upd, minibatch=mb, num_blocks=k,
        iterations=iterations, collision=mode, t0=t0)
    want = _numpy_sweeps(U, V, su, si, sv, sw, omega_u, omega_v, k=k,
                         mb=mb, iterations=iterations, t0=t0, lr=lr,
                         lam=lam, collision=mode)
    for g, w, start in zip(got, want, (U, V)):
        assert np.abs(w - start).max() > 1e-2  # the sweeps moved the rows
        np.testing.assert_allclose(np.asarray(g), w, rtol=2e-5, atol=2e-6)


def test_bf16_training_parity_and_rmse():
    """factor_dtype='bfloat16' (half-width tables, f32 accumulation)
    converges to an RMSE within tolerance of the f32 run through the
    public fit surface — and the fitted tables carry the storage
    dtype."""
    gen = SyntheticMFGenerator(num_users=64, num_items=48, rank=4,
                               noise=0.1, seed=1)
    train = gen.generate(3000)
    test = gen.generate(500)
    kw = dict(num_factors=8, lambda_=0.05, iterations=6,
              learning_rate=0.05, lr_schedule="inverse_sqrt", seed=0,
              minibatch_size=128, init_scale=0.3)

    def rmse(model):
        pred, mask = model.predict(test.users, test.items,
                                   return_mask=True)
        err = (np.asarray(pred, np.float64)
               - np.asarray(test.ratings, np.float64)) * np.asarray(mask)
        return float(np.sqrt((err ** 2).sum() / max(mask.sum(), 1)))

    m32 = DSGD(DSGDConfig(**kw)).fit(train, num_blocks=2)
    m16 = DSGD(DSGDConfig(**kw, factor_dtype="bfloat16")).fit(
        train, num_blocks=2)
    assert m16.U.dtype == jnp.bfloat16
    assert m16.V.dtype == jnp.bfloat16
    assert m32.U.dtype == jnp.float32
    r32, r16 = rmse(m32), rmse(m16)
    # bf16 rounding perturbs the trajectory; it must not change the
    # model quality story (ALX's observation, training half)
    assert abs(r16 - r32) < 0.05 * max(r32, 1e-6), (r32, r16)
    # and the factors themselves stay close to the f32 run's
    np.testing.assert_allclose(
        np.asarray(m16.U, np.float32), np.asarray(m32.U),
        rtol=0.1, atol=0.05)


def test_bf16_rejects_unknown_dtype():
    gen = SyntheticMFGenerator(num_users=16, num_items=12, rank=2,
                               noise=0.1, seed=0)
    train = gen.generate(200)
    with pytest.raises(ValueError, match="factor_dtype"):
        DSGD(DSGDConfig(num_factors=4, iterations=1,
                        factor_dtype="float16")).fit(train, num_blocks=1)


def test_train_hbm_gbs_gauge_published():
    """With obs enabled, a segmented DSGD fit publishes the achieved-
    bandwidth gauge next to ratings/s — both phases — priced by the
    dsgd_bytes_per_sweep model."""
    from large_scale_recommendation_tpu import obs

    obs.enable()
    try:
        gen = SyntheticMFGenerator(num_users=32, num_items=24, rank=2,
                                   noise=0.1, seed=0)
        train = gen.generate(500)
        DSGD(DSGDConfig(num_factors=4, iterations=4, seed=0,
                        minibatch_size=64)).fit(train, num_blocks=1)
        snap = obs.get_registry().snapshot()
        names = {(m["name"], m["labels"].get("phase"))
                 for m in snap["metrics"]}
        assert ("train_hbm_gbs", "all") in names
        assert ("train_throughput_ratings_per_s", "all") in names
    finally:
        obs.disable()
