"""On-device blocking pipeline: layout invariants, parity with the host
pass's semantics, and end-to-end convergence through the DSGD kernel.

The device path (data/device_blocking.py) must produce a layout satisfying
the same contract as the host path (data/blocking.py) — disjoint strata,
balanced blocks, correct omegas and collision scales — without being
bit-identical (different seeded permutations).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from large_scale_recommendation_tpu.data import blocking, device_blocking
from large_scale_recommendation_tpu.ops import sgd as sgd_ops
from large_scale_recommendation_tpu.core.updaters import (
    RegularizedSGDUpdater,
    constant_lr,
)


def _toy(n=4000, nu=300, ni=200, seed=0, skew=None):
    rng = np.random.default_rng(seed)
    if skew is None:
        u = rng.integers(0, nu, n)
        i = rng.integers(0, ni, n)
    else:
        u = np.minimum((-np.log1p(-rng.random(n) * (1 - np.exp(-skew)))
                        / skew * nu).astype(np.int64), nu - 1)
        i = np.minimum((-np.log1p(-rng.random(n) * (1 - np.exp(-skew)))
                        / skew * ni).astype(np.int64), ni - 1)
    r = rng.normal(0, 1, n).astype(np.float32)
    return u, i, r, nu, ni


class TestDeviceBlocking:
    @pytest.mark.parametrize("skew", [None, 2.0])
    @pytest.mark.parametrize("k", [2, 4])
    def test_layout_invariants(self, k, skew):
        u, i, r, nu, ni = _toy(skew=skew)
        p = device_blocking.device_block_problem(
            u, i, r, nu, ni, num_blocks=k, minibatch_multiple=64)

        su = np.asarray(p.su)
        si = np.asarray(p.si)
        sv = np.asarray(p.sv)
        sw = np.asarray(p.sw)
        # every real entry appears exactly once, with its value
        assert int(sw.sum()) == len(u)
        assert p.nnz == len(u)
        # stratum-major contract: block [s, pb] holds ratings with
        # user-block pb and item-block (pb+s) mod k
        for s in range(k):
            for pb in range(k):
                m = sw[s, pb] > 0
                if not m.any():
                    continue
                assert (su[s, pb][m] // p.rows_per_block_u == pb).all()
                assert (si[s, pb][m] // p.rows_per_block_v
                        == (pb + s) % k).all()
        # the multiset of (urow, irow, value) matches the input through the
        # id→row maps
        row_u = np.asarray(p.row_of_user)
        row_i = np.asarray(p.row_of_item)
        exp = sorted(zip(row_u[u].tolist(), row_i[i].tolist(),
                         np.float32(r).tolist()))
        got = sorted(zip(su[sw > 0].tolist(), si[sw > 0].tolist(),
                         sv[sw > 0].tolist()))
        assert exp == got

    def test_row_maps_and_omegas(self):
        u, i, r, nu, ni = _toy(skew=2.0)
        k = 4
        p = device_blocking.device_block_problem(
            u, i, r, nu, ni, num_blocks=k, minibatch_multiple=32)
        row_u = np.asarray(p.row_of_user)
        # bijective over ids: every id gets a distinct row
        assert len(set(row_u.tolist())) == nu
        # id_of_row inverts row_of_id
        id_of = np.asarray(p.id_of_user_row)
        assert (id_of[row_u] == np.arange(nu)).all()
        # omegas are the occurrence counts, indexed by row
        cnt = np.bincount(u, minlength=nu)
        assert (np.asarray(p.omega_u)[row_u] == cnt).all()
        # blocks are balanced: per-block id counts differ by at most 1 row
        blk = row_u // p.rows_per_block_u
        sizes = np.bincount(blk, minlength=k)
        assert sizes.max() - sizes.min() <= 1

    def test_load_balance_on_skewed_data(self):
        """The serpentine deal keeps per-block nnz near-equal even with
        power-law ids (same property the host pass guarantees)."""
        u, i, r, nu, ni = _toy(n=20_000, skew=2.0)
        k = 4
        p = device_blocking.device_block_problem(
            u, i, r, nu, ni, num_blocks=k, minibatch_multiple=1)
        blk = np.asarray(p.row_of_user)[u] // p.rows_per_block_u
        per_block = np.bincount(blk, minlength=k)
        assert per_block.max() / per_block.min() < 1.5

    def test_inv_counts_match_numpy_recomputation(self):
        u, i, r, nu, ni = _toy(n=3000, nu=40, ni=30, skew=2.0)  # many dups
        mb = 128
        p = device_blocking.device_block_problem(
            u, i, r, nu, ni, num_blocks=2, minibatch_multiple=mb)
        su = np.asarray(p.su).reshape(-1)
        sw = np.asarray(p.sw).reshape(-1)
        icu = np.asarray(p.icu).reshape(-1)
        # recompute per-minibatch weighted counts in numpy on the SAME layout
        for m0 in range(0, len(su), mb):
            rows = su[m0:m0 + mb]
            w = sw[m0:m0 + mb]
            inv = icu[m0:m0 + mb]
            for j in range(mb):
                cnt = w[rows == rows[j]].sum()
                if w[j] > 0:
                    assert inv[j] == pytest.approx(1.0 / max(cnt, 1.0))

    def test_weight_zero_padding_entries_are_noops(self):
        """The weights channel: padded entries (w=0, id 0) occupy layout
        slots but contribute nothing — counts, omegas, real-entry multiset
        and training all match the unpadded problem (the per-host
        equal-shard padding contract for multi-host ingest)."""
        u, i, r, nu, ni = _toy(n=2000, seed=6, skew=2.0)
        n_pad = 137
        up = np.concatenate([u, np.zeros(n_pad, np.int64)])
        ip = np.concatenate([i, np.zeros(n_pad, np.int64)])
        rp = np.concatenate([r, np.zeros(n_pad, np.float32)])
        wp = np.concatenate([np.ones(len(u), np.float32),
                             np.zeros(n_pad, np.float32)])
        plain = device_blocking.device_block_problem(
            u, i, r, nu, ni, num_blocks=2, minibatch_multiple=64, seed=4)
        padded = device_blocking.device_block_problem(
            up, ip, rp, nu, ni, num_blocks=2, minibatch_multiple=64,
            seed=4, weights=wp)
        assert padded.nnz == plain.nnz == len(u)
        # identical weighted counts → identical row maps and omegas
        np.testing.assert_array_equal(np.asarray(plain.row_of_user),
                                      np.asarray(padded.row_of_user))
        np.testing.assert_array_equal(np.asarray(plain.omega_u),
                                      np.asarray(padded.omega_u))
        # same real-entry multiset through the layout
        def real(p):
            sw = np.asarray(p.sw) > 0
            return sorted(zip(np.asarray(p.su)[sw].tolist(),
                              np.asarray(p.si)[sw].tolist(),
                              np.asarray(p.sv)[sw].tolist()))
        assert real(plain) == real(padded)
        # collision scales ignore the w=0 slots: every real row-0 entry's
        # scale reflects only real occurrences (recomputed in numpy)
        su = np.asarray(padded.su).reshape(-1)
        sw = np.asarray(padded.sw).reshape(-1)
        icu = np.asarray(padded.icu).reshape(-1)
        for m0 in range(0, len(su), 64):
            rows, ws, inv = su[m0:m0 + 64], sw[m0:m0 + 64], icu[m0:m0 + 64]
            for j in range(0, 64, 13):
                if ws[j] > 0:
                    cnt = ws[rows == rows[j]].sum()
                    assert inv[j] == pytest.approx(1.0 / max(cnt, 1.0))

    def test_recompute_inv_counts_other_minibatch(self):
        """recompute_inv_counts(p, mb') on the same layout must equal the
        per-minibatch weighted-count definition at mb' (the bench autotune
        contract: one blocking pass, several kernel minibatches)."""
        u, i, r, nu, ni = _toy(n=3000, nu=40, ni=30, skew=2.0)
        p = device_blocking.device_block_problem(
            u, i, r, nu, ni, num_blocks=2, minibatch_multiple=256)
        for mb in (64, 128):
            icu, _ = device_blocking.recompute_inv_counts(p, mb)
            su = np.asarray(p.su).reshape(-1)
            sw = np.asarray(p.sw).reshape(-1)
            icu = np.asarray(icu).reshape(-1)
            rng = np.random.default_rng(0)
            for m0 in rng.choice(len(su) // mb, 8, replace=False) * mb:
                rows = su[m0:m0 + mb]
                w = sw[m0:m0 + mb]
                for j in range(0, mb, 17):
                    if w[j] > 0:
                        cnt = w[rows == rows[j]].sum()
                        assert icu[m0 + j] == pytest.approx(
                            1.0 / max(cnt, 1.0))
        with pytest.raises(ValueError, match="divide"):
            device_blocking.recompute_inv_counts(p, p.su.shape[-1] * 2)

    def test_collision_scale_semantics_match_host(self):
        """Same definition as blocking.minibatch_inv_counts: a real entry's
        scale is 1/(weight-sum of its row in its minibatch)."""
        u = np.array([0, 0, 0, 1, 1, 2, 3, 3], np.int64)
        i = np.array([0, 1, 2, 0, 1, 0, 0, 1], np.int64)
        r = np.ones(8, np.float32)
        p = device_blocking.device_block_problem(
            u, i, r, 4, 3, num_blocks=1, minibatch_multiple=8, seed=3)
        su = np.asarray(p.su).reshape(-1)[:8]
        icu = np.asarray(p.icu).reshape(-1)[:8]
        cnt = {row: (su == row).sum() for row in set(su.tolist())}
        for j in range(8):
            assert icu[j] == pytest.approx(1.0 / cnt[su[j]])

    def test_truncated_exp_matches_host_distribution(self):
        """Device inverse-CDF draw ≈ host rejection draw (same truncated
        exponential): compare decile masses."""
        from large_scale_recommendation_tpu.core.generators import (
            _next_exp_discrete,
        )

        n_ids, lam, n = 1000, 2.0, 200_000
        host = _next_exp_discrete(np.random.default_rng(0), lam, n_ids, n)
        dev = np.asarray(device_blocking.truncated_exp_ids(
            jax.random.PRNGKey(0), lam, n_ids, n))
        assert dev.min() >= 0 and dev.max() < n_ids
        hh = np.bincount(host // 100, minlength=10) / n
        hd = np.bincount(dev // 100, minlength=10) / n
        np.testing.assert_allclose(hh, hd, atol=0.01)

    def test_synthetic_like_device_stats(self):
        (u, i, r), (hu, hi, hr), (nu, ni) = \
            device_blocking.synthetic_like_device(
                "ml-100k", nnz=50_000, rank=16, noise=0.1, seed=0)
        assert nu == 943 and ni == 1682
        assert u.shape[0] == 47_500 and hu.shape[0] == 2_500
        r = np.asarray(r)
        # planted signal std ≈ 1/sqrt(rank)=0.25, noise 0.1 → total ≈ 0.27
        assert 0.2 < r.std() < 0.35
        assert abs(r.mean()) < 0.02

    def test_end_to_end_convergence_through_dsgd_kernel(self):
        """Device pipeline → dsgd_train recovers planted structure (the
        shape of the bench's DSGD path, miniature)."""
        (u, i, r), (hu, hi, hr), (nu, ni) = \
            device_blocking.synthetic_like_device(
                "ml-100k", nnz=60_000, rank=4, noise=0.05, seed=1)
        k, mb, rank = 2, 512, 8
        p = device_blocking.device_block_problem(
            u, i, r, nu, ni, num_blocks=k, minibatch_multiple=mb, seed=1)
        U, V = device_blocking.init_factors_device(p, rank, scale=0.1)
        upd = RegularizedSGDUpdater(learning_rate=0.2, lambda_=0.05,
                                    schedule=constant_lr)
        hur, hir, hmask = p.holdout_rows(hu, hi)

        def rmse(U, V):
            sse = sgd_ops.sse_rows(U, V, hur, hir, hr, hmask)
            return float(np.sqrt(float(sse) / float(hmask.sum())))

        before = rmse(U, V)
        for t in range(12):
            U, V = sgd_ops.dsgd_train(
                U, V, p.su, p.si, p.sv, p.sw, p.omega_u, p.omega_v,
                p.icu, p.icv, updater=upd, minibatch=mb, num_blocks=k,
                iterations=1, collision="mean", t0=t)
        after = rmse(U, V)
        # measured (CPU and TPU agree): 0.5 → ~0.076 by sweep 12 (noise
        # floor 0.05); the bilinear bootstrap spends ~3 sweeps flat first
        assert after < before * 0.3
        assert after < 0.12

    def test_minibatch_sort_preserves_membership_and_math(self):
        u, i, r, nu, ni = _toy(n=2000, seed=5)
        mb = 64
        ps = device_blocking.device_block_problem(
            u, i, r, nu, ni, num_blocks=2, minibatch_multiple=mb, seed=2,
            minibatch_sort="item")
        pn = device_blocking.device_block_problem(
            u, i, r, nu, ni, num_blocks=2, minibatch_multiple=mb, seed=2)
        # same minibatch membership: each mb-chunk holds the same multiset
        for a, b in ((ps.su, pn.su), (ps.sv, pn.sv)):
            a2 = np.asarray(a).reshape(-1, mb)
            b2 = np.asarray(b).reshape(-1, mb)
            for row_a, row_b in zip(a2, b2):
                assert sorted(row_a.tolist()) == sorted(row_b.tolist())
        # sorted variant is item-ordered within chunks
        si2 = np.asarray(ps.si).reshape(-1, mb)
        assert all((np.diff(row) >= 0).all() for row in si2)

    def test_fit_device_full_model_surface(self):
        """DSGD.fit_device: device pipeline → standard MFModel (predict,
        rmse, risk, unseen-id semantics) at host-path quality."""
        from large_scale_recommendation_tpu.models.dsgd import (
            DSGD,
            DSGDConfig,
        )
        from large_scale_recommendation_tpu.core.types import Ratings

        (u, i, r), (hu, hi, hr), (nu, ni) = \
            device_blocking.synthetic_like_device(
                "ml-100k", nnz=60_000, rank=4, noise=0.05, seed=1)
        cfg = DSGDConfig(num_factors=8, lambda_=0.05, iterations=12,
                         learning_rate=0.2, lr_schedule="constant",
                         minibatch_size=512, seed=1, init_scale=0.1)
        m = DSGD(cfg).fit_device(u, i, r, nu, ni, num_blocks=2)
        test = Ratings.from_arrays(np.asarray(hu).astype(np.int64),
                                   np.asarray(hi).astype(np.int64),
                                   np.asarray(hr))
        assert m.rmse(test) < 0.12  # same floor as the ops-level test
        # host-path comparison on identical arrays
        train = Ratings.from_arrays(np.asarray(u).astype(np.int64),
                                    np.asarray(i).astype(np.int64),
                                    np.asarray(r))
        mh = DSGD(cfg).fit(train, num_blocks=2)
        assert abs(m.rmse(test) - mh.rmse(test)) < 0.03
        # unseen ids score exactly 0 (host IdIndex semantics): synthesize a
        # guaranteed-unseen id by refitting with one user id held out
        held = int(np.asarray(u)[0])
        uh = np.asarray(u).astype(np.int64)
        keep = uh != held
        m3 = DSGD(cfg).fit_device(uh[keep], np.asarray(i)[keep].astype(np.int64),
                                  np.asarray(r)[keep], nu, ni, num_blocks=2)
        s = m3.predict(np.array([held]), np.array([0]))
        assert float(s[0]) == 0.0
        assert np.isfinite(m.empirical_risk(test))

    def test_fit_device_checkpoint_segments_equal_straight_run(self):
        from large_scale_recommendation_tpu.models.dsgd import (
            DSGD,
            DSGDConfig,
        )
        from large_scale_recommendation_tpu.utils.checkpoint import (
            CheckpointManager,
        )
        import tempfile

        import dataclasses as dc

        u, i, r, nu, ni = _toy(n=5000, seed=9)
        cfg = DSGDConfig(num_factors=4, lambda_=0.1, iterations=6,
                         learning_rate=0.1, minibatch_size=256, seed=0,
                         init_scale=0.1)
        straight = DSGD(cfg).fit_device(u, i, r, nu, ni, num_blocks=2)
        with tempfile.TemporaryDirectory() as d:
            # run only 4 of the 6 iterations, snapshotting every 2 …
            cm = CheckpointManager(d)
            DSGD(dc.replace(cfg, iterations=4)).fit_device(
                u, i, r, nu, ni, num_blocks=2,
                checkpoint_manager=cm, checkpoint_every=2)
            # … then resume MID-RUN (restores step 4, trains 2 more with
            # t0=4) and require bitwise-path equality with the straight run
            resumed = DSGD(cfg).fit_device(u, i, r, nu, ni, num_blocks=2,
                                           checkpoint_manager=CheckpointManager(d),
                                           checkpoint_every=2, resume=True)
            # cross-path resume is refused: the host-blocked layout is
            # row-incompatible with these snapshots
            with pytest.raises(ValueError, match="kind"):
                from large_scale_recommendation_tpu.core.types import Ratings
                DSGD(cfg).fit(
                    Ratings.from_arrays(u, i, r), num_blocks=2,
                    checkpoint_manager=CheckpointManager(d), resume=True)
        np.testing.assert_allclose(np.asarray(straight.U),
                                   np.asarray(resumed.U), rtol=1e-5)

    def test_validate_dense_ids_mixed_host_device_no_int32_wrap(self):
        """A wild int64 id in a HOST array must fail validation even when
        the other side is a device array — the mixed path must not route
        the host array through a device cast (int64→int32 wrap would turn
        2^32+5 into a plausible small id that passes the range check)."""
        import jax.numpy as jnp
        wild = np.array([0, 2**32 + 5], np.int64)  # wraps to 5 in int32
        dev_ok = jnp.array([0, 1], jnp.int32)
        with pytest.raises(ValueError, match="dense ids"):
            device_blocking.validate_dense_ids(dev_ok, wild, 100, 100, "t")
        with pytest.raises(ValueError, match="dense ids"):
            device_blocking.validate_dense_ids(wild, dev_ok, 100, 100, "t")
        # all-device path: fused single-readback check still rejects
        with pytest.raises(ValueError, match="dense ids"):
            device_blocking.validate_dense_ids(
                dev_ok, jnp.array([0, 100], jnp.int32), 100, 100, "t")
        # and accepts in-range input in every combination
        device_blocking.validate_dense_ids(dev_ok, dev_ok, 100, 100, "t")
        device_blocking.validate_dense_ids(
            np.array([0, 1]), dev_ok, 100, 100, "t")

    @pytest.mark.slow
    def test_fuzz_layout_invariants(self):
        """Randomized shapes/skews/weights: the layout contract must hold
        for every draw (multiset preservation, stratum property, weighted
        collision scales)."""
        rng = np.random.default_rng(2026)
        for trial in range(20):
            nu = int(rng.integers(3, 400))
            ni = int(rng.integers(3, 300))
            n = int(rng.integers(10, 5000))
            k = int(rng.choice([1, 2, 3, 4, 8]))
            mb = int(rng.choice([1, 16, 64, 256]))
            skew = rng.choice([None, 1.0, 3.0])
            u = (rng.integers(0, nu, n) if skew is None else np.minimum(
                (-np.log1p(-rng.random(n) * (1 - np.exp(-skew))) / skew
                 * nu).astype(np.int64), nu - 1))
            i = rng.integers(0, ni, n)
            r = rng.normal(0, 1, n).astype(np.float32)
            w = (rng.random(n) > 0.2).astype(np.float32) \
                if trial % 3 == 0 else None
            p = device_blocking.device_block_problem(
                u, i, r, nu, ni, num_blocks=k, minibatch_multiple=mb,
                seed=trial, weights=w)
            wreal = np.ones(n) if w is None else w
            assert p.nnz == int((wreal > 0).sum()), (trial, p.nnz)
            su = np.asarray(p.su)
            si = np.asarray(p.si)
            sw = np.asarray(p.sw)
            m = sw > 0
            assert int(m.sum()) == p.nnz
            # stratum property on every real entry
            ub = su[m] // p.rows_per_block_u
            ib = si[m] // p.rows_per_block_v
            s_idx, p_idx, _ = np.nonzero(m)
            assert (ub == p_idx).all(), trial
            assert (ib == (p_idx + s_idx) % k).all(), trial
            # real multiset through the row maps
            keep = wreal > 0
            row_u = np.asarray(p.row_of_user)
            row_i = np.asarray(p.row_of_item)
            exp = sorted(zip(row_u[u[keep]].tolist(),
                             row_i[i[keep]].tolist(),
                             np.float32(r[keep]).tolist()))
            got = sorted(zip(su[m].tolist(), si[m].tolist(),
                             np.asarray(p.sv)[m].tolist()))
            assert exp == got, trial

    def test_init_factors_device_matches_host_initializer(self):
        from large_scale_recommendation_tpu.core.initializers import (
            PseudoRandomFactorInitializer,
        )

        u, i, r, nu, ni = _toy(n=500, nu=50, ni=40)
        p = device_blocking.device_block_problem(
            u, i, r, nu, ni, num_blocks=2, minibatch_multiple=16)
        U, _ = device_blocking.init_factors_device(p, rank=6, scale=0.08)
        init = PseudoRandomFactorInitializer(6, scale=0.08)
        ids = np.asarray(p.id_of_user_row)
        np.testing.assert_allclose(np.asarray(U), np.asarray(init(ids)),
                                   rtol=1e-6)


class TestInvCountsPresorted:
    def test_presorted_path_is_bit_equal(self):
        """The minibatch_sort side's collision scales skip the inner
        argsort (r5 layout optimization) — identical runs on sorted
        input, so the fast path must be bit-equal to the general one."""
        from large_scale_recommendation_tpu.data.device_blocking import (
            _inv_counts_2d,
        )

        rng = np.random.default_rng(0)
        rows = np.sort(rng.integers(0, 30, (16, 64)), axis=-1)
        w = (rng.random((16, 64)) > 0.2).astype(np.float32)
        a = _inv_counts_2d(jnp.asarray(rows), jnp.asarray(w))
        b = _inv_counts_2d(jnp.asarray(rows), jnp.asarray(w),
                           presorted=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------------
# ISSUE 27: the layout is the same arrays, bit for bit, however it is moved
# --------------------------------------------------------------------------


def _bits(a):
    a = np.asarray(a)
    return a.view(np.int32) if a.dtype == np.float32 else a


def _assert_layout_equals_reference(p, u, i, r, nu, ni, *, k, mb, seed,
                                    sort_side):
    """``p`` against the benchmark's plain reference blocking of the same
    input (scatter + argsort + gathers; it imports nothing of the program)."""
    from benchmark.reference import dsgd_ref

    ref = dsgd_ref.block_layout(
        jnp.asarray(u, jnp.int32), jnp.asarray(i, jnp.int32),
        jnp.asarray(r), num_users=nu, num_items=ni, k=k, minibatch=mb,
        solver_seed=seed, sort_side=sort_side)
    assert p.su.shape[-1] == ref["bmax"]
    for name in ("su", "si", "sv", "sw"):
        got = np.asarray(getattr(p, name)).reshape(-1, mb)
        np.testing.assert_array_equal(_bits(got), _bits(ref[name]), name)
    for name in ("omega_u", "omega_v", "row_of_user", "row_of_item",
                 "id_of_user_row", "id_of_item_row"):
        np.testing.assert_array_equal(
            _bits(getattr(p, name)), _bits(ref[name]), name)


def _one_empty_bucket(k=2, per=40):
    """Every user rates the one item 0: only the k buckets of that item's
    block hold entries, the other (k - 1) * k stay empty."""
    nu, ni = 8 * k, 8 * k
    u = np.repeat(np.arange(nu), per)
    return u, np.zeros_like(u), nu, ni


class TestLayoutBitForBit:
    """(a) of ISSUE 27: ``device_block_problem`` against the benchmark's
    plain reference blocking and against the gather/scatter oracle below."""

    @pytest.mark.parametrize("skew", [None, 2.0])
    @pytest.mark.parametrize("sort_side", [None, "user", "item"])
    @pytest.mark.parametrize("k", [2, 4, 8])
    def test_equals_reference_block_layout(self, k, sort_side, skew):
        u, i, r, nu, ni = _toy(n=3000, nu=120, ni=90, seed=k, skew=skew)
        mb = 32
        p = device_blocking.device_block_problem(
            u, i, r, nu, ni, num_blocks=k, minibatch_multiple=mb, seed=7,
            minibatch_sort=sort_side)
        _assert_layout_equals_reference(
            p, u, i, r, nu, ni, k=k, mb=mb, seed=7, sort_side=sort_side)

    @pytest.mark.parametrize("case", ["empty_bucket", "exactly_bmax",
                                      "one_entry", "bmax_over_n"])
    def test_edge_shapes_equal_reference(self, case):
        k, mb = 2, 8
        if case == "empty_bucket":
            u, i, nu, ni = _one_empty_bucket(k)
        elif case == "exactly_bmax":
            # one user, one item: a single bucket of 3 * mb entries, so
            # the fullest bucket fills its padded row to the last slot
            nu, ni = 4, 4
            u = np.zeros(3 * mb, np.int64)
            i = np.zeros(3 * mb, np.int64)
        elif case == "one_entry":
            nu, ni = 5, 3
            u, i = np.array([4]), np.array([2])
        else:  # the padded block is longer than the whole input
            nu, ni = 6, 6
            u, i = np.array([0, 1, 2]), np.array([0, 0, 0])
        r = np.linspace(-1, 1, len(u)).astype(np.float32)
        p = device_blocking.device_block_problem(
            u, i, r, nu, ni, num_blocks=k, minibatch_multiple=mb, seed=3,
            minibatch_sort="item")
        _assert_layout_equals_reference(
            p, u, i, r, nu, ni, k=k, mb=mb, seed=3, sort_side="item")
        sw = np.asarray(p.sw)
        per_bucket = sw.reshape(k * k, -1).sum(axis=1)
        if case == "empty_bucket":
            assert (per_bucket == 0).any() and sw.sum() == len(u)
        if case == "exactly_bmax":
            assert per_bucket.max() == p.su.shape[-1] == 3 * mb

    @pytest.mark.parametrize("sort_side", [None, "user", "item"])
    def test_weight_zero_padding_same_real_entries_bit_for_bit(
            self, sort_side):
        """Padding entries carry w=0 and keep their slots; the real
        entries' row maps, omegas and multiset equal the unpadded call's
        (as ``test_weight_zero_padding_entries_are_noops``), and the
        padded layout equals the gather/scatter oracle's bit for bit."""
        u, i, r, nu, ni = _toy(n=1500, nu=60, ni=50, seed=6, skew=2.0)
        n_pad = 77
        up = np.concatenate([u, np.zeros(n_pad, np.int64)])
        ip = np.concatenate([i, np.zeros(n_pad, np.int64)])
        rp = np.concatenate([r, np.zeros(n_pad, np.float32)])
        wp = np.concatenate([np.ones(len(u), np.float32),
                             np.zeros(n_pad, np.float32)])
        kw = dict(num_blocks=2, minibatch_multiple=32, seed=4,
                  minibatch_sort=sort_side)
        plain = device_blocking.device_block_problem(u, i, r, nu, ni, **kw)
        padded = device_blocking.device_block_problem(
            up, ip, rp, nu, ni, weights=wp, **kw)
        for name in ("row_of_user", "row_of_item", "omega_u", "omega_v",
                     "id_of_user_row", "id_of_item_row"):
            np.testing.assert_array_equal(
                _bits(getattr(plain, name)), _bits(getattr(padded, name)))

        def real(p):
            m = np.asarray(p.sw) > 0
            return sorted(zip(np.asarray(p.su)[m].tolist(),
                              np.asarray(p.si)[m].tolist(),
                              _bits(p.sv)[m].tolist()))
        assert real(plain) == real(padded)
        assert int((np.asarray(padded.sw) > 0).sum()) == len(u)
        oracle = _oracle_layout(up, ip, rp, wp, nu, ni, **kw)
        for name, want in zip(("su", "si", "sv", "sw", "icu", "icv"),
                              oracle):
            np.testing.assert_array_equal(
                _bits(getattr(padded, name)), _bits(want), name)


class TestLaneLookupBitForBit:
    """``bucket/assign`` looks its id→row tables up a 128-lane row at a
    time, ``_LOOKUP_CHUNK`` ids a pass (``_lookup_rows``): the same bits
    as the element gathers ``row_of_u[u]``, ``row_of_i[i]`` it replaced."""

    CHUNK = device_blocking._LOOKUP_CHUNK

    @pytest.mark.parametrize("n", [1000, CHUNK, CHUNK + 1],
                             ids=["below", "one_chunk", "one_over"])
    @pytest.mark.parametrize("h", [1, 127, 128, 129, 17770])
    def test_bucket_entries_equals_element_gathers(self, monkeypatch, h, n):
        k = 4
        rpb = device_blocking.rows_per_block(h, k)
        rng = np.random.default_rng(h * 7 + n)
        u = rng.integers(0, h, n)
        i = rng.integers(0, h, n)
        # the highest ids, whose lanes end the padded view's last row
        u[:2] = i[-2:] = h - 1
        args = (jax.random.PRNGKey(h), jnp.asarray(u, jnp.int32),
                jnp.asarray(i, jnp.int32),
                jnp.asarray(rng.normal(size=n), jnp.float32),
                jnp.asarray(rng.random(n) > 0.1, jnp.float32),
                jnp.asarray(rng.integers(0, k * rpb, h), jnp.int32),
                jnp.asarray(rng.integers(0, k * rpb, h), jnp.int32),
                k, rpb, rpb)
        bucket = device_blocking._bucket_entries
        lanes = bucket(*args)
        monkeypatch.setattr(device_blocking, "_lookup_rows",
                            lambda tables, ids: tuple(
                                t[x] for t, x in zip(tables, ids)))
        bucket.clear_cache()  # trace again with the element gathers
        try:
            gathers = bucket(*args)
        finally:
            bucket.clear_cache()
        for a, b in zip(lanes, gathers):
            np.testing.assert_array_equal(_bits(a), _bits(b))
        assert int(np.asarray(lanes[0]).sum()) == n

    def test_lookups_on_the_registry(self):
        from large_scale_recommendation_tpu import obs

        u, i, r, nu, ni = _toy(n=3001, nu=70, ni=50, seed=2)
        registry, _ = obs.enable()
        try:
            device_blocking.device_block_problem(u, i, r, nu, ni,
                                                 num_blocks=2)
            got = [m["value"] for m in registry.snapshot()["metrics"]
                   if m["name"] == "blocking_lane_lookups_total"]
        finally:
            obs.disable()
        assert got == [2 * 3001]  # a user's row and an item's an entry


# Today's bodies before ISSUE 27, kept as the oracles: an index vector
# computed, then applied one element at a time.


def _oracle_inv_counts_2d(rows, w, presorted=False):
    mb = rows.shape[-1]
    j = jnp.arange(mb, dtype=jnp.int32)[None, :]
    if presorted:
        sr, sw = rows, w
    else:
        sidx = jnp.argsort(rows, axis=-1)
        sr = jnp.take_along_axis(rows, sidx, axis=-1)
        sw = jnp.take_along_axis(w, sidx, axis=-1)
    diff = sr[:, 1:] != sr[:, :-1]
    ones = jnp.ones_like(sr[:, :1], bool)
    new = jnp.concatenate([ones, diff], axis=-1)
    last = jnp.concatenate([diff, ones], axis=-1)
    start = jax.lax.cummax(jnp.where(new, j, -1), axis=1)
    end_rev = jax.lax.cummax(
        jnp.where(last, mb - 1 - j, -1)[:, ::-1], axis=1)[:, ::-1]
    end = mb - 1 - end_rev
    cumw = jnp.cumsum(sw, axis=-1)
    W = (jnp.take_along_axis(cumw, end, axis=-1)
         - jnp.take_along_axis(cumw, start, axis=-1)
         + jnp.take_along_axis(sw, start, axis=-1))
    inv_sorted = 1.0 / jnp.maximum(W, 1.0)
    if presorted:
        return inv_sorted
    inv_back = jnp.argsort(sidx, axis=-1)
    return jnp.take_along_axis(inv_sorted, inv_back, axis=-1)


def _oracle_layout(u, i, r, w, nu, ni, *, num_blocks, minibatch_multiple,
                   seed, minibatch_sort):
    """``device_block_problem``'s six layout arrays by the gather/scatter
    forms: ``perm[argsort(flat[perm])]`` applied by ``[order]``, the
    ``dest`` scatter, argsort + ``take_along_axis``."""
    k, mb = num_blocks, minibatch_multiple
    u, i = jnp.asarray(u, jnp.int32), jnp.asarray(i, jnp.int32)
    r, w = jnp.asarray(r, jnp.float32), jnp.asarray(w, jnp.float32)
    base = jax.random.PRNGKey(seed)
    rpb_u = device_blocking.rows_per_block(nu, k)
    rpb_v = device_blocking.rows_per_block(ni, k)
    cu, cv = device_blocking._weighted_counts(u, i, w, nu, ni)
    row_of_u = device_blocking._assign_rows(
        jax.random.fold_in(base, 10), cu, k, rpb_u, k * rpb_u)[0]
    row_of_i = device_blocking._assign_rows(
        jax.random.fold_in(base, 11), cv, k, rpb_v, k * rpb_v)[0]
    urow, irow = row_of_u[u], row_of_i[i]
    flat = (((irow // rpb_v - urow // rpb_u) % k) * k
            + urow // rpb_u).astype(jnp.int32)
    n = flat.shape[0]
    flat = jnp.where(w > 0, flat, jnp.arange(n, dtype=jnp.int32) % (k * k))
    sizes = jnp.zeros(k * k, jnp.int32).at[flat].add(1)
    perm = jax.random.permutation(jax.random.fold_in(base, 12), n)
    order = perm[jnp.argsort(flat[perm], stable=True)]
    flat_s = flat[order]
    bmax = -(-max(int(sizes.max()), 1) // mb) * mb
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              jnp.cumsum(sizes)[:-1]])
    dest = flat_s * bmax + jnp.arange(n, dtype=jnp.int32) - starts[flat_s]
    cols = [jnp.zeros(k * k * bmax, a.dtype).at[dest].set(a[order])
            .reshape(-1, mb) for a in (urow, irow, r, w)]
    if minibatch_sort is not None:
        o = jnp.argsort(cols[0 if minibatch_sort == "user" else 1], axis=-1)
        cols = [jnp.take_along_axis(a, o, axis=-1) for a in cols]
    su, si, sv, sw = cols
    icu = _oracle_inv_counts_2d(su, sw, presorted=minibatch_sort == "user")
    icv = _oracle_inv_counts_2d(si, sw, presorted=minibatch_sort == "item")
    return [a.reshape(k, k, bmax) for a in (su, si, sv, sw, icu, icv)]


def _inv_count_rows(case, rng, n_mb=6, mb=32):
    if case == "one_run":  # a run spans the whole minibatch
        rows = np.repeat(rng.integers(0, 9, (n_mb, 1)), mb, axis=1)
    elif case == "runs_of_one":  # every row distinct
        rows = np.stack([rng.permutation(mb) for _ in range(n_mb)])
    elif case == "all_padding":
        rows = np.zeros((n_mb, mb), np.int64)
    else:  # mixed: long and short runs
        rows = rng.integers(0, 7, (n_mb, mb))
    if case == "all_padding":
        w = np.zeros((n_mb, mb), np.float32)
    elif case == "fractional":
        w = rng.random((n_mb, mb)).astype(np.float32)
    else:
        w = (rng.random((n_mb, mb)) > 0.25).astype(np.float32)
    return rows.astype(np.int32), w


class TestInvCountsBitForBit:
    """(b) of ISSUE 27: the scan form of ``_inv_counts_2d`` against the
    gather form above, element for element."""

    @pytest.mark.parametrize("presorted", [False, True])
    @pytest.mark.parametrize("case", ["mixed", "one_run", "runs_of_one",
                                      "all_padding", "fractional"])
    def test_equals_gather_form(self, case, presorted):
        rows, w = _inv_count_rows(case, np.random.default_rng(11))
        if presorted:
            order = np.argsort(rows, axis=-1, kind="stable")
            rows = np.take_along_axis(rows, order, axis=-1)
            w = np.take_along_axis(w, order, axis=-1)
        got = device_blocking._inv_counts_2d(
            jnp.asarray(rows), jnp.asarray(w), presorted=presorted)
        want = _oracle_inv_counts_2d(
            jnp.asarray(rows), jnp.asarray(w), presorted=presorted)
        np.testing.assert_array_equal(_bits(got), _bits(want))

    @pytest.mark.parametrize("mb", [1, 2, 5, 64])
    def test_recompute_inv_counts_equals_gather_form(self, mb):
        u, i, r, nu, ni = _toy(n=900, nu=30, ni=20, seed=2, skew=2.0)
        p = device_blocking.device_block_problem(
            u, i, r, nu, ni, num_blocks=2, minibatch_multiple=320)
        icu, icv = device_blocking.recompute_inv_counts(p, mb)
        sw2 = p.sw.reshape(-1, mb)
        for got, rows in ((icu, p.su), (icv, p.si)):
            want = _oracle_inv_counts_2d(rows.reshape(-1, mb), sw2)
            np.testing.assert_array_equal(
                _bits(got).reshape(-1, mb), _bits(want))


class TestNoPerElementIndexing:
    """The mechanism of ISSUE 27, held structurally: at the ratings'
    length the blocking programs move data by sorts, scans and slice
    copies. A ``gather`` or ``scatter`` whose result is as long as the
    entries or the layout is an ``x[order]`` put back."""

    N, NU, NI, K, MB = 5000, 300, 200, 4, 64

    @staticmethod
    def _indexed_ops(lowered):
        """(op, result element count) of every gather / scatter of a
        lowered program's StableHLO module, nested regions included."""
        found = []

        def walk(op):
            name = op.operation.name
            if name in ("stablehlo.gather", "stablehlo.scatter"):
                found.append((name.split(".")[1], max(
                    int(np.prod(r.type.shape)) for r in op.results)))
            for region in op.regions:
                for block in region:
                    for inner in block:
                        walk(inner)

        walk(lowered.compiler_ir(dialect="stablehlo").operation)
        return found

    def test_the_reader_sees_the_forms_it_forbids(self):
        rows = jax.ShapeDtypeStruct((8, self.MB), jnp.int32)
        w = jax.ShapeDtypeStruct((8, self.MB), jnp.float32)
        ops = self._indexed_ops(jax.jit(_oracle_inv_counts_2d).lower(rows, w))
        # (take_along_axis lowers to one private function a dtype: the
        # module is walked whole, every function of it)
        assert ops and set(ops) == {("gather", 8 * self.MB)}, ops
        ops = self._indexed_ops(jax.jit(
            lambda a, d: jnp.zeros(2 * self.N, a.dtype).at[d].set(a)).lower(
                jax.ShapeDtypeStruct((self.N,), jnp.float32),
                jax.ShapeDtypeStruct((self.N,), jnp.int32)))
        assert ops == [("scatter", 2 * self.N)], ops

    def _lowered(self, n=None):
        k, n = self.K, n or self.N
        rpb_u = device_blocking.rows_per_block(self.NU, k)
        rpb_v = device_blocking.rows_per_block(self.NI, k)
        i32 = jax.ShapeDtypeStruct((n,), jnp.int32)
        f32 = jax.ShapeDtypeStruct((n,), jnp.float32)
        bucket = device_blocking._bucket_entries.lower(
            jax.ShapeDtypeStruct((2,), jnp.uint32), i32, i32, f32, f32,
            jax.ShapeDtypeStruct((self.NU,), jnp.int32),
            jax.ShapeDtypeStruct((self.NI,), jnp.int32), k, rpb_u, rpb_v)
        bmax = 6 * self.MB
        layouts = {
            side: device_blocking._layout.lower(
                i32, i32, i32, f32, f32,
                jax.ShapeDtypeStruct((k * k,), jnp.int32), k, bmax,
                self.MB, side)
            for side in (None, "user", "item")}
        return bucket, layouts, k * k * bmax

    def test_bucket_entries_gathers_only_the_row_tables(self):
        # all four steps of the issue are in: step 4 (the bucket phase) too.
        # The two id→row lookups gather 128-lane rows a chunk at a time
        # (_lookup_rows): below a chunk, one chunk of every entry; above
        # it, chunks of _LOOKUP_CHUNK, and nothing as long as the entries
        chunk = device_blocking._LOOKUP_CHUNK
        for n in (self.N, 2 * chunk + 3):
            bucket, _, _ = self._lowered(n)
            long_ops = [(op, sz) for op, sz in
                        self._indexed_ops(bucket) if sz >= min(n, chunk)]
            rows = [("gather", min(n, chunk) * 128)] * 2  # row_of_*'s views
            assert long_ops == rows, (n, long_ops)

    @pytest.mark.parametrize("side", [None, "user", "item"])
    def test_layout_has_no_gather_or_scatter_of_layout_length(self, side):
        _, layouts, total = self._lowered()
        ops = self._indexed_ops(layouts[side])
        long_ops = [(op, sz) for op, sz in ops
                    if sz >= min(self.N, total) // self.K ** 2]
        assert long_ops == [], long_ops
