"""Pallas DSGD block-sweep: interpret-mode parity against the XLA kernel.

The Pallas kernel (ops/pallas_sgd.py) exists to attack the measured HBM
row-gather ceiling on real TPU hardware; on CPU we can only pin its MATH.
These tests run it in interpreter mode and require exact agreement with
``ops.sgd.sgd_block_sweep`` under the same updater rule — including
duplicate rows inside a minibatch (the sequential RMW scatter must
accumulate like ``.at[].add``) and weight-0 padding no-ops. Throughput is
measured by scripts/pallas_probe.py on the device that matters.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from large_scale_recommendation_tpu.core.updaters import (
    RegularizedSGDUpdater,
    constant_lr,
)
from large_scale_recommendation_tpu.ops import sgd as sgd_ops
from large_scale_recommendation_tpu.ops.pallas_sgd import pallas_block_sweep


def _problem(seed, e, rpb_u, rpb_v, rank, pad_frac=0.0):
    rng = np.random.default_rng(seed)
    ur = rng.integers(0, rpb_u, e).astype(np.int32)
    ir = rng.integers(0, rpb_v, e).astype(np.int32)
    vals = rng.normal(0, 1, e).astype(np.float32)
    w = np.ones(e, np.float32)
    if pad_frac:
        w[rng.random(e) < pad_frac] = 0.0
    U = rng.normal(0, 0.1, (rpb_u, rank)).astype(np.float32)
    V = rng.normal(0, 0.1, (rpb_v, rank)).astype(np.float32)
    omega_u = np.maximum(
        np.bincount(ur, weights=w, minlength=rpb_u), 0).astype(np.float32)
    omega_v = np.maximum(
        np.bincount(ir, weights=w, minlength=rpb_v), 0).astype(np.float32)
    return ur, ir, vals, w, U, V, omega_u, omega_v


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


@pytest.mark.parametrize("gather", ["take", "loop"])
@pytest.mark.parametrize("pad_frac", [0.0, 0.15])
def test_matches_xla_kernel(gather, pad_frac):
    lr, lam, mb, rank = 0.1, 0.05, 64, 8
    ur, ir, vals, w, U, V, ou, ov = _problem(0, 256, 40, 24, rank,
                                             pad_frac)
    icu = _inv_counts(ur, w, mb)
    icv = _inv_counts(ir, w, mb)

    upd = RegularizedSGDUpdater(learning_rate=lr, lambda_=lam,
                                schedule=constant_lr)
    U_ref, V_ref = sgd_ops.sgd_block_sweep(
        jnp.asarray(U), jnp.asarray(V),
        jnp.asarray(ur), jnp.asarray(ir), jnp.asarray(vals),
        jnp.asarray(w), jnp.asarray(ou), jnp.asarray(ov),
        upd, 1, mb, "mean", jnp.asarray(icu), jnp.asarray(icv))

    U_p, V_p = pallas_block_sweep(
        jnp.asarray(U), jnp.asarray(V), jnp.asarray(ur), jnp.asarray(ir),
        jnp.asarray(vals), jnp.asarray(w), jnp.asarray(icu),
        jnp.asarray(icv), jnp.asarray(ou), jnp.asarray(ov),
        lr=lr, lam=lam, minibatch=mb, gather=gather, interpret=True)

    np.testing.assert_allclose(np.asarray(U_p), np.asarray(U_ref),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(V_p), np.asarray(V_ref),
                               rtol=2e-5, atol=2e-6)


def test_duplicate_rows_accumulate_not_overwrite():
    """Many entries hitting ONE row in the same minibatch: the scatter
    must behave like .at[].add (a bulk last-write-wins store would keep
    only one delta)."""
    lr, mb, rank = 0.1, 16, 4
    e = 16
    ur = np.zeros(e, np.int32)  # every entry → row 0
    ir = np.arange(e, dtype=np.int32)
    rng = np.random.default_rng(1)
    vals = rng.normal(0, 1, e).astype(np.float32)
    w = np.ones(e, np.float32)
    U = rng.normal(0, 0.1, (4, rank)).astype(np.float32)
    V = rng.normal(0, 0.1, (e, rank)).astype(np.float32)
    ou = np.maximum(np.bincount(ur, minlength=4), 1).astype(np.float32)
    ov = np.ones(e, np.float32)
    icu = _inv_counts(ur, w, mb)
    icv = _inv_counts(ir, w, mb)

    upd = RegularizedSGDUpdater(learning_rate=lr, lambda_=0.05,
                                schedule=constant_lr)
    U_ref, V_ref = sgd_ops.sgd_block_sweep(
        jnp.asarray(U), jnp.asarray(V), jnp.asarray(ur), jnp.asarray(ir),
        jnp.asarray(vals), jnp.asarray(w), jnp.asarray(ou),
        jnp.asarray(ov), upd, 1, mb, "mean",
        jnp.asarray(icu), jnp.asarray(icv))
    U_p, V_p = pallas_block_sweep(
        jnp.asarray(U), jnp.asarray(V), jnp.asarray(ur), jnp.asarray(ir),
        jnp.asarray(vals), jnp.asarray(w), jnp.asarray(icu),
        jnp.asarray(icv), jnp.asarray(ou), jnp.asarray(ov),
        lr=lr, lam=0.05, minibatch=mb, gather="loop", interpret=True)
    np.testing.assert_allclose(np.asarray(U_p), np.asarray(U_ref),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(V_p), np.asarray(V_ref),
                               rtol=2e-5, atol=2e-6)


def test_minibatch_boundary_visibility():
    """Minibatch t+1 must read rows written by minibatch t (the lax.scan
    carry semantics) — two minibatches hitting the same row."""
    lr, mb, rank = 0.2, 8, 4
    e = 16  # two minibatches
    ur = np.full(e, 2, np.int32)
    ir = np.arange(e, dtype=np.int32) % 8
    rng = np.random.default_rng(2)
    vals = rng.normal(0, 1, e).astype(np.float32)
    w = np.ones(e, np.float32)
    U = rng.normal(0, 0.1, (4, rank)).astype(np.float32)
    V = rng.normal(0, 0.1, (8, rank)).astype(np.float32)
    ou = np.maximum(np.bincount(ur, minlength=4), 1).astype(np.float32)
    ov = np.maximum(np.bincount(ir, minlength=8), 1).astype(np.float32)
    icu = _inv_counts(ur, w, mb)
    icv = _inv_counts(ir, w, mb)
    upd = RegularizedSGDUpdater(learning_rate=lr, lambda_=0.05,
                                schedule=constant_lr)
    U_ref, V_ref = sgd_ops.sgd_block_sweep(
        jnp.asarray(U), jnp.asarray(V), jnp.asarray(ur), jnp.asarray(ir),
        jnp.asarray(vals), jnp.asarray(w), jnp.asarray(ou),
        jnp.asarray(ov), upd, 1, mb, "mean",
        jnp.asarray(icu), jnp.asarray(icv))
    U_p, V_p = pallas_block_sweep(
        jnp.asarray(U), jnp.asarray(V), jnp.asarray(ur), jnp.asarray(ir),
        jnp.asarray(vals), jnp.asarray(w), jnp.asarray(icu),
        jnp.asarray(icv), jnp.asarray(ou), jnp.asarray(ov),
        lr=lr, lam=0.05, minibatch=mb, gather="take", interpret=True)
    np.testing.assert_allclose(np.asarray(U_p), np.asarray(U_ref),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(V_p), np.asarray(V_ref),
                               rtol=2e-5, atol=2e-6)


def _full_training_pair(minibatch_divisor: int, schedule, iters: int = 3,
                        t0: int = 0, gather: str = "loop"):
    """Run ops.sgd.dsgd_train and dsgd_train_pallas on the same blocked
    problem; ``minibatch = block_size // minibatch_divisor``. Returns
    ((Uref, Vref), (Up, Vp))."""
    from large_scale_recommendation_tpu.core.generators import (
        SyntheticMFGenerator,
    )
    from large_scale_recommendation_tpu.data import blocking
    from large_scale_recommendation_tpu.models.dsgd import DSGD, DSGDConfig
    from large_scale_recommendation_tpu.ops.pallas_sgd import (
        dsgd_train_pallas,
    )

    gen = SyntheticMFGenerator(num_users=48, num_items=40, rank=4,
                               noise=0.1, seed=0)
    train = gen.generate(2000)
    k = 2
    b = blocking.block_problem(train, num_blocks=k, seed=0,
                               minibatch_multiple=1).ratings.u_rows.shape[-1]
    # pad the block to a multiple of the divisor so mb divides b exactly
    mb_mult = -(-b // minibatch_divisor)
    problem = blocking.block_problem(train, num_blocks=k, seed=0,
                                     minibatch_multiple=mb_mult)
    b = problem.ratings.u_rows.shape[-1]
    mb = b // minibatch_divisor
    icu, icv = blocking.minibatch_inv_counts(problem.ratings, mb)
    U0, V0 = DSGD(DSGDConfig(num_factors=8, seed=0,
                             init_scale=0.2))._init_factors(problem)
    lr, lam = 0.05, 0.1
    upd = RegularizedSGDUpdater(learning_rate=lr, lambda_=lam,
                                schedule=schedule)
    args = (jnp.asarray(problem.ratings.u_rows, jnp.int32),
            jnp.asarray(problem.ratings.i_rows, jnp.int32),
            jnp.asarray(problem.ratings.values, jnp.float32),
            jnp.asarray(problem.ratings.weights, jnp.float32))
    common = (jnp.asarray(U0), jnp.asarray(V0), *args,
              jnp.asarray(problem.users.omega),
              jnp.asarray(problem.items.omega),
              jnp.asarray(icu), jnp.asarray(icv))
    Uref, Vref = sgd_ops.dsgd_train(
        *common, updater=upd, minibatch=mb, num_blocks=k,
        iterations=iters, collision="mean", t0=t0)
    # same positional order as dsgd_train (drop-in twin)
    Up, Vp = dsgd_train_pallas(
        *common, lr=lr, lam=lam, minibatch=mb, num_blocks=k,
        iterations=iters, gather=gather, interpret=True,
        schedule=None if schedule is constant_lr else schedule, t0=t0)
    return (Uref, Vref), (Up, Vp)


@pytest.mark.parametrize("gather", ["take", "loop"])
@pytest.mark.parametrize("divisor", [1, 4])
def test_full_training_matches_dsgd_train(divisor, gather):
    """dsgd_train_pallas (all strata × blocks × sweeps under one scan)
    must equal ops.sgd.dsgd_train, which makes the same block visits in
    the same order — at minibatch == block size (divisor 1) AND at
    minibatch < block size (divisor 4) — on both gather paths (loop is
    the production path; take awaits a Mosaic that can gather across
    vregs)."""
    (Uref, Vref), (Up, Vp) = _full_training_pair(divisor, constant_lr,
                                                 gather=gather)
    np.testing.assert_allclose(np.asarray(Up), np.asarray(Uref),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(Vp), np.asarray(Vref),
                               rtol=2e-5, atol=2e-6)


def test_dsgd_kernel_flag_routes_through_pallas():
    """DSGDConfig(kernel='pallas') must produce the same model as the XLA
    kernel through the PUBLIC fit surface (segmented twice to exercise the
    t0 continuation), and reject configurations the Pallas rule can't
    honor."""
    from large_scale_recommendation_tpu.core.generators import (
        SyntheticMFGenerator,
    )
    from large_scale_recommendation_tpu.models.dsgd import DSGD, DSGDConfig

    gen = SyntheticMFGenerator(num_users=64, num_items=48, rank=4,
                               noise=0.1, seed=1)
    train = gen.generate(3000)
    kw = dict(num_factors=8, lambda_=0.05, iterations=4,
              learning_rate=0.05, lr_schedule="inverse_sqrt", seed=0,
              minibatch_size=128, init_scale=0.3)
    mx = DSGD(DSGDConfig(**kw, kernel="xla")).fit(train, num_blocks=2)
    pallas = DSGD(DSGDConfig(**kw, kernel="pallas", pallas_interpret=True))
    mp = pallas.fit(train, num_blocks=2)
    np.testing.assert_allclose(np.asarray(mp.U), np.asarray(mx.U),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(mp.V), np.asarray(mx.V),
                               rtol=2e-4, atol=2e-5)
    # the run says which of the two Pallas kernels it was
    assert pallas.kernel_route == "pallas/stratum_pipeline"

    with pytest.raises(ValueError, match="pallas"):
        DSGD(DSGDConfig(**{**kw, "collision_mode": "sum"},
                        kernel="pallas", pallas_interpret=True)).fit(
                            train, num_blocks=2)
    with pytest.raises(ValueError, match="kernel"):
        DSGD(DSGDConfig(**kw, kernel="tensorcore")).fit(train,
                                                        num_blocks=2)


def test_pallas_off_tpu_raises_unless_interpretation_is_explicit():
    """kernel='pallas' on a non-TPU backend must RAISE, not quietly run
    the interpreter (which also skips every VMEM/SMEM/alignment guard):
    interpretation is the caller's explicit ``pallas_interpret=True``.
    Single-device model, mesh model and the probe all obey it."""
    from large_scale_recommendation_tpu.core.generators import (
        SyntheticMFGenerator,
    )
    from large_scale_recommendation_tpu.models.dsgd import DSGD, DSGDConfig
    from large_scale_recommendation_tpu.ops.pallas_sgd import (
        probe_variants,
    )
    from large_scale_recommendation_tpu.parallel.dsgd_mesh import (
        MeshDSGD,
        MeshDSGDConfig,
    )
    from large_scale_recommendation_tpu.parallel.partitioner import (
        Partitioner,
    )

    train = SyntheticMFGenerator(num_users=32, num_items=24, rank=4,
                                 noise=0.1, seed=1).generate(800)
    kw = dict(num_factors=8, iterations=1, minibatch_size=64,
              kernel="pallas")
    with pytest.raises(RuntimeError, match="needs a TPU.*'cpu'"):
        DSGD(DSGDConfig(**kw)).fit(train, num_blocks=2)
    with pytest.raises(RuntimeError, match="needs a TPU.*'cpu'"):
        MeshDSGD(MeshDSGDConfig(**kw),
                 partitioner=Partitioner(num_devices=2)).fit(train)
    with pytest.raises(RuntimeError, match="needs a TPU.*'cpu'"):
        probe_variants(rank=8, mb=64, rpb_u=32, rpb_v=24, nnz=128, reps=1,
                       variants=("pallas_loop",))


def test_pallas_route_reports_the_kernel_the_budget_selects():
    """``pipeline=None`` keeps routing by the VMEM/SMEM model — and the
    route is now a visible answer: the AOT-accepted pipelined geometry
    (ML-25M k=32, mb 1024, f32) is the stratum pipeline, the bench's own
    k=32 / mb 2048 geometry models 15.9 MB > 14 and is per-block."""
    from large_scale_recommendation_tpu.ops.pallas_sgd import pallas_route

    assert pallas_route(5080, 1848, 128, 24576, 1024, 4) == \
        "stratum_pipeline"
    assert pallas_route(5080, 1848, 128, 24576, 2048, 4) == "per_block"
    assert pallas_route(5080, 1848, 128, 24576, 2048, 4,
                        gather="take") == "per_block"
    # interpretation skips the budgets, so it always pipelines
    assert pallas_route(5080, 1848, 128, 24576, 2048, 4,
                        interpret=True) == "stratum_pipeline"


def test_full_training_schedule_parity():
    """A decaying η/√t schedule with a nonzero t0 (checkpoint-segment
    continuation) must match the XLA path exactly — the schedule is
    evaluated at trace level and enters the kernel as a runtime scalar."""
    from large_scale_recommendation_tpu.core.updaters import inverse_sqrt_lr

    (Uref, Vref), (Up, Vp) = _full_training_pair(
        2, inverse_sqrt_lr, iters=3, t0=5)
    np.testing.assert_allclose(np.asarray(Up), np.asarray(Uref),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(Vp), np.asarray(Vref),
                               rtol=2e-5, atol=2e-6)


# -- ISSUE 6: double-buffered stratum pipeline + bf16 factor storage -------


def _blocked_training_args(k=3, divisor=4, seed=0):
    """A small blocked problem in dsgd_train_pallas positional layout."""
    from large_scale_recommendation_tpu.core.generators import (
        SyntheticMFGenerator,
    )
    from large_scale_recommendation_tpu.data import blocking
    from large_scale_recommendation_tpu.models.dsgd import DSGD, DSGDConfig

    gen = SyntheticMFGenerator(num_users=48, num_items=40, rank=4,
                               noise=0.1, seed=seed)
    train = gen.generate(2000)
    b = blocking.block_problem(train, num_blocks=k, seed=0,
                               minibatch_multiple=1).ratings.u_rows.shape[-1]
    problem = blocking.block_problem(train, num_blocks=k, seed=0,
                                     minibatch_multiple=-(-b // divisor))
    b = problem.ratings.u_rows.shape[-1]
    mb = b // divisor
    icu, icv = blocking.minibatch_inv_counts(problem.ratings, mb)
    U0, V0 = DSGD(DSGDConfig(num_factors=8, seed=0,
                             init_scale=0.2))._init_factors(problem)
    common = (jnp.asarray(U0), jnp.asarray(V0),
              jnp.asarray(problem.ratings.u_rows, jnp.int32),
              jnp.asarray(problem.ratings.i_rows, jnp.int32),
              jnp.asarray(problem.ratings.values, jnp.float32),
              jnp.asarray(problem.ratings.weights, jnp.float32),
              jnp.asarray(problem.users.omega),
              jnp.asarray(problem.items.omega),
              jnp.asarray(icu), jnp.asarray(icv))
    return common, mb, k


def test_pipeline_matches_per_block_exactly():
    """The double-buffered stratum kernel is the SAME schedule as the
    sequential per-block path — only the copy/compute overlap differs —
    so the two must agree BIT-EXACTLY (and with the XLA reference to
    float tolerance), including at n_mb == 1 (prologue and epilogue in
    the same grid step)."""
    from large_scale_recommendation_tpu.core.updaters import (
        RegularizedSGDUpdater,
        constant_lr,
    )
    from large_scale_recommendation_tpu.ops.pallas_sgd import (
        dsgd_train_pallas,
    )

    for divisor in (1, 4):  # n_mb == 1 and n_mb > 1
        common, mb, k = _blocked_training_args(divisor=divisor)
        kw = dict(lr=0.05, lam=0.1, minibatch=mb, num_blocks=k,
                  iterations=3, gather="loop", interpret=True)
        Up, Vp = dsgd_train_pallas(*common, **kw, pipeline=True)
        Ub, Vb = dsgd_train_pallas(*common, **kw, pipeline=False)
        assert jnp.array_equal(Up, Ub) and jnp.array_equal(Vp, Vb)

        upd = RegularizedSGDUpdater(learning_rate=0.05, lambda_=0.1,
                                    schedule=constant_lr)
        Uref, Vref = sgd_ops.dsgd_train(
            *common, updater=upd, minibatch=mb, num_blocks=k,
            iterations=3, collision="mean", t0=0)
        np.testing.assert_allclose(np.asarray(Up), np.asarray(Uref),
                                   rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(np.asarray(Vp), np.asarray(Vref),
                                   rtol=2e-5, atol=2e-6)


def test_pipeline_rejects_take_gather():
    from large_scale_recommendation_tpu.ops.pallas_sgd import (
        dsgd_train_pallas,
    )

    common, mb, k = _blocked_training_args()
    with pytest.raises(ValueError, match="loop"):
        dsgd_train_pallas(*common, lr=0.05, lam=0.1, minibatch=mb,
                          num_blocks=k, iterations=1, gather="take",
                          interpret=True, pipeline=True)


def test_stratum_pipeline_budget_operating_points():
    """The budget model admits the AOT-calibrated ML-25M production
    points (k=32 at mb ≤ 1024; k=64 at mb 2048, both dtypes) and
    rejects the measured VMEM-stack OOM geometries (k=32 at mb 2048,
    every k=16 point) — the routing contract docs/PERF.md records."""
    from large_scale_recommendation_tpu.ops.pallas_sgd import (
        stratum_pipeline_budget,
    )

    def fits(rpb_u, rpb_v, e, fac_bytes, mb=2048, rank=128):
        vmem_mb, smem_kb = stratum_pipeline_budget(
            rpb_u, rpb_v, rank, e, mb, fac_bytes)
        return vmem_mb <= 14 and smem_kb <= 900

    assert fits(5080, 1848, 24576, 4, mb=1024)  # k=32 f32 (AOT: compiles)
    assert fits(2540, 924, 6144, 4)     # k=64 f32 (AOT: compiles)
    assert fits(2540, 924, 6144, 2)     # k=64 bf16 (AOT: compiles)
    assert not fits(5080, 1848, 24576, 4)  # k=32 f32 mb2048: VMEM OOM
    assert not fits(5080, 1848, 24576, 2)  # k=32 bf16 mb2048: VMEM OOM
    assert not fits(10160, 3696, 92160, 4)  # k=16 f32: VMEM + SMEM
    assert not fits(10160, 3696, 92160, 2)  # k=16 bf16: SMEM (1.4 MB)


def test_bf16_training_parity_and_rmse():
    """factor_dtype='bfloat16' (half-width tables, f32 accumulation)
    converges to an RMSE within tolerance of the f32 run on BOTH
    kernels, through the public fit surface — and the fitted tables
    carry the storage dtype."""
    from large_scale_recommendation_tpu.core.generators import (
        SyntheticMFGenerator,
    )
    from large_scale_recommendation_tpu.models.dsgd import DSGD, DSGDConfig

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

    kw["pallas_interpret"] = True  # read by kernel="pallas" only
    for kernel in ("xla", "pallas"):
        m32 = DSGD(DSGDConfig(**kw, kernel=kernel)).fit(train,
                                                        num_blocks=2)
        m16 = DSGD(DSGDConfig(**kw, kernel=kernel,
                              factor_dtype="bfloat16")).fit(train,
                                                            num_blocks=2)
        assert m16.U.dtype == jnp.bfloat16
        assert m16.V.dtype == jnp.bfloat16
        assert m32.U.dtype == jnp.float32
        r32, r16 = rmse(m32), rmse(m16)
        # bf16 rounding perturbs the trajectory; it must not change the
        # model quality story (ALX's observation, training half)
        assert abs(r16 - r32) < 0.05 * max(r32, 1e-6), (kernel, r32, r16)
        # and the factors themselves stay close to the f32 run's
        np.testing.assert_allclose(
            np.asarray(m16.U, np.float32), np.asarray(m32.U),
            rtol=0.1, atol=0.05)


def test_bf16_rejects_unknown_dtype():
    from large_scale_recommendation_tpu.core.generators import (
        SyntheticMFGenerator,
    )
    from large_scale_recommendation_tpu.models.dsgd import DSGD, DSGDConfig

    gen = SyntheticMFGenerator(num_users=16, num_items=12, rank=2,
                               noise=0.1, seed=0)
    train = gen.generate(200)
    with pytest.raises(ValueError, match="factor_dtype"):
        DSGD(DSGDConfig(num_factors=4, iterations=1,
                        factor_dtype="float16")).fit(train, num_blocks=1)


def test_bf16_block_sweep_dtype_and_accumulation():
    """pallas_block_sweep on bf16 tables returns bf16 and tracks the f32
    reference within bf16 rounding — the f32 work-slice accumulation
    must not collapse duplicate-row updates to last-write-wins."""
    lr, lam, mb, rank = 0.1, 0.05, 64, 8
    ur, ir, vals, w, U, V, ou, ov = _problem(3, 256, 40, 24, rank)
    icu = _inv_counts(ur, w, mb)
    icv = _inv_counts(ir, w, mb)
    Uf, Vf = pallas_block_sweep(
        jnp.asarray(U), jnp.asarray(V), jnp.asarray(ur), jnp.asarray(ir),
        jnp.asarray(vals), jnp.asarray(w), jnp.asarray(icu),
        jnp.asarray(icv), jnp.asarray(ou), jnp.asarray(ov),
        lr=lr, lam=lam, minibatch=mb, gather="loop", interpret=True)
    Uh, Vh = pallas_block_sweep(
        jnp.asarray(U).astype(jnp.bfloat16),
        jnp.asarray(V).astype(jnp.bfloat16),
        jnp.asarray(ur), jnp.asarray(ir),
        jnp.asarray(vals), jnp.asarray(w), jnp.asarray(icu),
        jnp.asarray(icv), jnp.asarray(ou), jnp.asarray(ov),
        lr=lr, lam=lam, minibatch=mb, gather="loop", interpret=True)
    assert Uh.dtype == jnp.bfloat16 and Vh.dtype == jnp.bfloat16
    # bf16 has ~3 decimal digits: the input quantization alone moves
    # values by up to ~0.4% — compare against that scale
    np.testing.assert_allclose(np.asarray(Uh, np.float32),
                               np.asarray(Uf), rtol=0.02, atol=0.01)
    np.testing.assert_allclose(np.asarray(Vh, np.float32),
                               np.asarray(Vf), rtol=0.02, atol=0.01)


def test_probe_script_emits_json_last_line():
    """scripts/pallas_probe.py ends with a machine-readable JSON summary
    as the genuinely LAST line even in a 2>&1-merged stream, carrying per-variant ratings/s and
    effective_hbm_gbs."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "PROBE_CPU": "1", "PROBE_RANK": "8",
           "PROBE_MB": "64", "PROBE_RPB_U": "64", "PROBE_RPB_V": "48",
           "PROBE_NNZ": "128", "PROBE_REPS": "1",
           "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "pallas_probe.py")],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, timeout=280, check=True)
    last = out.stdout.strip().splitlines()[-1]
    summary = json.loads(last)  # the merged stream still parses
    assert summary["tpu"] is False
    assert "xla_ratings_per_s" in summary
    assert "pallas_loop_effective_hbm_gbs" in summary


def test_stratum_pipeline_hbm_target_on_tpu():
    """The ISSUE-6 steady-state target: ≥10% of HBM peak on the
    double-buffered sweep — asserted ONLY where a real memory system
    exists (CPU interpret mode measures the interpreter, not HBM)."""
    import time

    import jax

    if jax.default_backend() != "tpu":
        pytest.skip("HBM-peak target is asserted only on a real TPU")

    from large_scale_recommendation_tpu.ops.pallas_sgd import (
        dsgd_train_pallas,
    )

    k, rank, mb, e = 32, 128, 1024, 24576  # ML-25M shape at k=32 (the
    # AOT-calibrated operating point: mb 2048 OOMs the VMEM stack)
    rpb_u, rpb_v = 5080, 1848
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 6)
    p_arr = jnp.arange(k, dtype=jnp.int32)
    q_arr = (p_arr[None, :] + p_arr[:, None]) % k
    su = (jax.random.randint(ks[0], (k, k, e), 0, rpb_u, jnp.int32)
          + (p_arr * rpb_u)[None, :, None])
    si = (jax.random.randint(ks[1], (k, k, e), 0, rpb_v, jnp.int32)
          + (q_arr * rpb_v)[:, :, None])
    sv = jax.random.normal(ks[2], (k, k, e), jnp.float32)
    sw = jnp.ones((k, k, e), jnp.float32)
    ic = jnp.ones((k, k, e), jnp.float32)
    U = 0.1 * jax.random.normal(ks[3], (k * rpb_u, rank), jnp.float32)
    V = 0.1 * jax.random.normal(ks[4], (k * rpb_v, rank), jnp.float32)
    ou = jnp.ones(k * rpb_u, jnp.float32)
    ov = jnp.ones(k * rpb_v, jnp.float32)

    def sweep(it):
        return dsgd_train_pallas(
            U, V, su, si, sv, sw, ou, ov, ic, ic, lr=0.01, lam=0.1,
            minibatch=mb, num_blocks=k, iterations=it, gather="loop",
            pipeline=True)

    jax.block_until_ready(sweep(1))  # compile + warm
    t0 = time.perf_counter()
    jax.block_until_ready(sweep(2))
    wall = (time.perf_counter() - t0) / 2
    nnz = k * k * e
    bps = sgd_ops.dsgd_bytes_per_sweep(
        nnz, rank, kernel="pallas", num_blocks=k,
        rows_u=k * rpb_u, rows_v=k * rpb_v)
    hbm_gbs = bps / wall / 1e9
    assert hbm_gbs >= 0.10 * 819.0, (
        f"steady-state sweep achieved {hbm_gbs:.1f} GB/s "
        f"< 10% of the 819 GB/s v5e HBM peak (wall {wall:.3f}s/sweep)")


def test_train_hbm_gbs_gauge_published():
    """With obs enabled, a segmented DSGD fit publishes the achieved-
    bandwidth gauge next to ratings/s — both phases — priced by the
    shared dsgd_bytes_per_sweep model (ISSUE 6)."""
    from large_scale_recommendation_tpu import obs
    from large_scale_recommendation_tpu.core.generators import (
        SyntheticMFGenerator,
    )
    from large_scale_recommendation_tpu.models.dsgd import DSGD, DSGDConfig

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
