"""BPR (``DSGDConfig(loss="bpr")``, ``ops.sgd.bpr_minibatch_update``): the
step against the update written out a triple at a time, the fit against
``benchmark/reference/bpr_ref.py``, the sampler's draws, the model's
surface, and ``utils.metrics.expected_percentile_rank``'s chunk."""

import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from large_scale_recommendation_tpu.core.updaters import (
    RegularizedSGDUpdater,
    constant_lr,
)
from large_scale_recommendation_tpu.models.dsgd import DSGD, DSGDConfig
from large_scale_recommendation_tpu.ops import sgd as sgd_ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

LR, LAM = 0.3, 0.05
UPD = RegularizedSGDUpdater(learning_rate=LR, lambda_=LAM,
                            schedule=constant_lr)


def _draw(key, t, s, p, m, n_real, size):
    """The negatives of minibatch ``m`` of block ``p`` in stratum ``s`` at
    sweep ``t``, as ``dsgd_train`` draws them."""
    for x in (t, s, p, m):
        key = jax.random.fold_in(key, x)
    return np.asarray(jax.random.randint(key, (size,), 0, n_real,
                                         dtype=jnp.int32))


def _one_block(nu=6, ni=9, e=12, seed=0):
    rng = np.random.default_rng(seed)
    u = rng.integers(0, nu, e).astype(np.int32)
    i = rng.integers(0, ni, e).astype(np.int32)
    U0 = rng.normal(0, 0.3, (nu, 4)).astype(np.float32)
    V0 = rng.normal(0, 0.3, (ni, 4)).astype(np.float32)
    return u, i, U0, V0


def _train_one_block(U0, V0, u, i, n_real, key, iterations, t0=0,
                     w=None, collision="mean"):
    e = len(u)
    w = np.ones(e, np.float32) if w is None else w
    lay = [jnp.asarray(a).reshape(1, 1, e) for a in (u, i, np.zeros(e), w)]
    return sgd_ops.dsgd_train(
        jnp.asarray(U0), jnp.asarray(V0), *lay,
        jnp.zeros(U0.shape[0]), jnp.zeros(V0.shape[0]), None, None,
        jnp.asarray([n_real], jnp.int32), key, updater=UPD, minibatch=1,
        num_blocks=1, iterations=iterations, collision=collision, t0=t0,
        loss="bpr")


@pytest.mark.parametrize("collision", ["mean", "sum"])
def test_one_triple_at_a_time_is_the_update_written_out(collision):
    """Minibatch 1, one block: every triple applied in turn, two sweeps,
    on the negatives drawn from the same keys. Under "mean" a negative
    that is the positive itself counts its row twice."""
    u, i, U0, V0 = _one_block()
    key = jax.random.PRNGKey(3)
    U, V = _train_one_block(U0, V0, u, i, 7, key, 2, collision=collision)
    Ur, Vr = U0.astype(np.float64), V0.astype(np.float64)
    for t in (1, 2):
        for m in range(len(u)):
            j = int(_draw(key, t, 0, 0, m, 7, 1)[0])
            a, b = u[m], i[m]
            x = Ur[a] @ (Vr[b] - Vr[j])
            g = 1.0 / (1.0 + np.exp(x))
            du = LR * (g * (Vr[b] - Vr[j]) - LAM * Ur[a])
            dvi = LR * (g * Ur[a] - LAM * Vr[b])
            dvj = LR * (-g * Ur[a] - LAM * Vr[j])
            c = 2.0 if (b == j and collision == "mean") else 1.0
            Ur[a] += du
            Vr[b] += dvi / c
            Vr[j] += dvj / c
    np.testing.assert_allclose(np.asarray(U), Ur, atol=1e-6)
    np.testing.assert_allclose(np.asarray(V), Vr, atol=1e-6)


def _unsorted_item_side(V, i_rows, j_rows, weights, dv, collision):
    """The BPR step's item side as it stood before it was applied in row
    order, copied plainly: the weighted count scattered into a zero vector
    and gathered back, the division, and one scatter of
    ``concat([i_rows, j_rows])`` in that order."""
    v_rows = jnp.concatenate([i_rows, j_rows])
    if collision == "mean":
        cv = jnp.zeros(V.shape[0], V.dtype).at[v_rows].add(
            jnp.concatenate([weights, weights]))
        dv = dv / jnp.maximum(cv[v_rows], 1.0)[:, None]
    return V.at[v_rows].add(dv)


@pytest.mark.parametrize("weights", ["binary", "fractional"])
@pytest.mark.parametrize("collision", ["mean", "sum"])
@pytest.mark.parametrize("mb,n_real,h", [(8, 3, 5), (512, 40, 64)])
def test_the_sorted_item_side_is_the_unsorted_scatter(mb, n_real, h,
                                                      collision, weights):
    """``_add_item_side`` against ``_unsorted_item_side`` on the same
    deltas, with a negative equal to its own positive, one negative drawn
    twice and weight-0 padding on local row 0: bit for bit at 0/1 weights
    and under "sum" (the run sums are exact, and a row's addends keep
    their order). At fractional weights under "mean" a run's sum is a
    difference of two cumsums, each within a few float32 ulps of the
    total weight ``S``: a delta moves by at most ``|dv| * 8 * S * eps``."""
    rng = np.random.default_rng(mb)
    V = jnp.asarray(rng.normal(0, 0.3, (h, 4)).astype(np.float32))
    i = rng.integers(0, n_real, mb).astype(np.int32)
    j = rng.integers(0, n_real, mb).astype(np.int32)
    j[0], j[2] = i[0], j[1]  # its own positive; one negative twice
    w = (np.ones(mb, np.float32) if weights == "binary"
         else rng.uniform(0.1, 2.0, mb).astype(np.float32))
    i[-2:], w[-2:] = 0, 0.0  # padding: row 0, and a zero delta
    dv = rng.normal(0, 0.05, (2 * mb, 4)).astype(np.float32)
    dv[[mb - 2, mb - 1, 2 * mb - 2, 2 * mb - 1]] = 0.0
    args = [jnp.asarray(a) for a in (V, i, j, w, dv)]
    want = np.asarray(jax.jit(partial(_unsorted_item_side,
                                      collision=collision))(*args))
    got = np.asarray(jax.jit(partial(sgd_ops._add_item_side,
                                     collision=collision))(*args))
    if weights == "binary" or collision == "sum":
        np.testing.assert_array_equal(got, want)
    else:
        eps = np.finfo(np.float32).eps
        atol = np.abs(dv).max() * 8 * 2 * w.sum() * eps
        np.testing.assert_allclose(got, want, rtol=0, atol=atol)
    assert not np.array_equal(got, np.asarray(V))


def test_a_padding_entry_changes_nothing():
    u, i, U0, V0 = _one_block()
    w = np.zeros(len(u), np.float32)
    U, V = _train_one_block(U0, V0, u, i, 7, jax.random.PRNGKey(3), 1, w=w)
    np.testing.assert_array_equal(np.asarray(U), U0)
    np.testing.assert_array_equal(np.asarray(V), V0)


def test_draws_anew_each_sweep_and_continue_across_segments():
    u, i, U0, V0 = _one_block(e=40)
    key = jax.random.PRNGKey(11)
    whole = _train_one_block(U0, V0, u, i, 9, key, 2)
    first = _train_one_block(U0, V0, u, i, 9, key, 1)
    second = _train_one_block(*first, u, i, 9, key, 1, t0=1)
    for a, b in zip(whole, second):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the constant schedule leaves only the draws to tell sweep 2 from 1
    again = _train_one_block(*first, u, i, 9, key, 1, t0=0)
    assert not np.array_equal(np.asarray(again[1]), np.asarray(second[1]))
    assert not np.array_equal(_draw(key, 1, 0, 0, 0, 9, 64),
                              _draw(key, 2, 0, 0, 0, 9, 64))


def test_the_draw_is_uniform_over_the_real_rows():
    """A seeded draw of 1M from 37 rows: no row outside them, every row's
    count within 5 sigma of its mean."""
    n, size = 37, 1 << 20
    got = _draw(jax.random.PRNGKey(5), 1, 2, 3, 4, n, size)
    assert got.min() >= 0 and got.max() < n
    counts = np.bincount(got, minlength=n)
    mean = size / n
    sigma = np.sqrt(size * (1 / n) * (1 - 1 / n))
    assert np.abs(counts - mean).max() < 5 * sigma


def _planted(nu=300, ni=500, n=12000, seed=0):
    """Ids ``ni - 40`` and up are never seen; the item side's blocks are
    padded (500 items over 4 blocks of 128 rows)."""
    rng = np.random.default_rng(seed)
    u = rng.integers(0, nu, n).astype(np.int32)
    i = (rng.zipf(1.3, n) % (ni - 40)).astype(np.int32)
    return u, i, np.ones(n, np.float32)


def _cfg(**kw):
    base = dict(num_factors=8, lambda_=LAM, iterations=2, num_blocks=4,
                learning_rate=LR, lr_schedule="constant",
                minibatch_size=128, init_scale=0.1, minibatch_sort="item",
                loss="bpr")
    base.update(kw)
    return DSGDConfig(**base)


def _seen_are_a_prefix(omega, k):
    """Each block's seen rows are its first ones, as many as
    ``seen_rows_per_block`` counts."""
    from large_scale_recommendation_tpu.data.blocking import (
        seen_rows_per_block,
    )

    n_seen = np.asarray(seen_rows_per_block(omega, k))
    omega = np.asarray(omega).reshape(k, -1)
    assert n_seen.dtype == np.int32
    assert n_seen.tolist() == (omega > 0).sum(axis=1).tolist()
    rows = np.arange(omega.shape[1])[None, :]
    assert ((omega > 0) == (rows < n_seen[:, None])).all()


def test_negatives_come_from_the_real_rows_of_the_visited_block():
    """Rows no entry is a positive of (ids never seen, padding) are never
    drawn, so they keep their initial values; the seen rows are each
    block's first ones, and ``seen_rows_per_block`` counts them."""
    from large_scale_recommendation_tpu.data.device_blocking import (
        device_block_problem,
        init_factors_device,
    )

    u, i, r = _planted()
    cfg = _cfg()
    p = device_block_problem(u, i, r, 300, 500, num_blocks=4,
                             minibatch_multiple=128, seed=0,
                             minibatch_sort="item")
    _, V0 = init_factors_device(p, 8, scale=0.1)
    model = DSGD(cfg).fit_device(u, i, r, 300, 500)
    _seen_are_a_prefix(p.omega_v, 4)
    omega = np.asarray(p.omega_v).reshape(4, -1)
    unseen = (omega == 0).reshape(-1)
    assert unseen.sum() > 40  # the unseen ids and the padding rows
    np.testing.assert_array_equal(np.asarray(model.V)[unseen],
                                  np.asarray(V0)[unseen])
    assert not np.array_equal(np.asarray(model.V)[~unseen],
                              np.asarray(V0)[~unseen])


@pytest.mark.parametrize("k", [1, 3, 4, 7])
def test_the_host_deal_puts_the_seen_rows_first(k):
    """``blocking.build_id_index``, the deal of the host ``fit`` path,
    which BPR draws from too: the seen ids fill a prefix of every block
    and the padding rows (omega 0) follow."""
    from large_scale_recommendation_tpu.data.blocking import build_id_index

    rng = np.random.default_rng(k)
    ids = rng.zipf(1.4, 3000) % 997
    index = build_id_index(ids, k, seed=5)
    assert (index.omega == 0).sum() == index.num_rows - len(np.unique(ids))
    np.testing.assert_array_equal(index.omega > 0, index.ids >= 0)
    _seen_are_a_prefix(index.omega, k)


def test_the_reference_draws_from_its_own_list_of_seen_rows():
    """``bpr_ref`` does not lean on the deal's prefix: with the seen rows
    scattered through their blocks it still draws only those, each as
    often, and under a prefix its draws are the program's."""
    from benchmark.reference import bpr_ref

    k, rpb = 3, 16
    omega = np.zeros(k * rpb, np.float32)
    seen = [0, 5, 6, 9, 17, 18, 31, 40]
    omega[seen] = 1.0
    rows, n_seen = bpr_ref.seen_rows(jnp.asarray(omega), k)
    assert np.asarray(n_seen).tolist() == [4, 3, 1]
    key = jax.random.PRNGKey(2)
    for q, want in enumerate(([0, 5, 6, 9], [17, 18, 31], [40])):
        got = np.asarray(bpr_ref.negatives(rows, n_seen, key, q, 1 << 14))
        assert sorted(set(got.tolist())) == want
        counts = np.bincount(got)[want]
        assert counts.min() > 0.8 * counts.max()
    prefix = np.zeros(k * rpb, np.float32)
    prefix[[0, 1, 2, 16, 17, 32]] = 1.0
    rows, n_seen = bpr_ref.seen_rows(jnp.asarray(prefix), k)
    for q in range(k):
        program = jax.random.randint(key, (64,), 0, n_seen[q],
                                     dtype=jnp.int32) + q * rpb
        np.testing.assert_array_equal(
            np.asarray(bpr_ref.negatives(rows, n_seen, key, q, 64)),
            np.asarray(program))


def test_fit_device_equals_the_reference_leaf_by_leaf():
    """At the benchmark configuration's toy size (k=4, minibatch 256):
    the program's tables after each of two sweeps against ``bpr_ref``'s,
    over the ids seen in training."""
    from benchmark import harness
    from benchmark.reference import bpr_ref
    from benchmark.runners import fit as fit_runner
    from benchmark.runners import fit_rank
    from benchmark.runners.solvers import bpr as bpr_solver
    from benchmark.spans import Spans

    cell = harness.resolve_cell("mpd66m-bpr-r128.fit-rank")
    cfg = dict(cell.config, **cell.config["toy"])
    assert (cfg["num_blocks"], cfg["minibatch_size"]) == (4, 256)
    (u, i, r), _ = fit_rank.planted_interactions(3, cfg)
    stamps = fit_runner.SegmentStamps(Spans())
    model = bpr_solver.make_fit(cfg, 2, stamps, 1)(u, i, r)
    prog, seen = fit_runner.id_space(model, stamps.tables,
                                     cfg["num_users"], cfg["num_items"])
    ref = bpr_ref.fit(u, i, r, cfg, 2)
    for side in (0, 1):
        np.testing.assert_array_equal(np.asarray(seen[side]),
                                      np.asarray(ref["seen"][side]))
    for mine, theirs in zip(prog, ref["sweeps"]):
        for side in (0, 1):
            keep = np.asarray(seen[side])
            np.testing.assert_allclose(np.asarray(mine[side])[keep],
                                       np.asarray(theirs[side])[keep],
                                       rtol=1e-5, atol=1e-6)


def test_the_model_serves_and_counts_its_negatives():
    from large_scale_recommendation_tpu import obs

    u, i, r = _planted()
    registry, _ = obs.enable()
    try:
        solver = DSGD(_cfg())
        model = solver.fit_device(u, i, r, 300, 500, checkpoint_every=1)
        got = {m["name"]: m.get("value")
               for m in registry.snapshot()["metrics"]}
    finally:
        obs.disable()
    assert got["dsgd_negatives_total"] == 2 * len(u)
    # two sweeps, a positive and a negative an entry each
    assert got["dsgd_item_rows_sorted_total"] == 4 * len(u)
    scores = solver.predict(u[:8], i[:8])
    assert np.isfinite(np.asarray(scores)).all()
    from large_scale_recommendation_tpu.core.types import Ratings

    risk = solver.empirical_risk(Ratings.from_arrays(u[:64], i[:64], r[:64]))
    assert np.isfinite(risk)
    top = model.recommend(np.arange(5), k=3)
    assert np.asarray(top[0]).shape == (5, 3)
    # squared fits count no negatives
    registry, _ = obs.enable()
    try:
        DSGD(_cfg(loss="squared")).fit_device(u, i, r, 300, 500)
        names = {m["name"] for m in registry.snapshot()["metrics"]}
    finally:
        obs.disable()
    assert "dsgd_negatives_total" not in names
    assert "dsgd_item_rows_sorted_total" not in names


@pytest.mark.parametrize("loss,rows,flops", [("bpr", 6, 10),
                                             ("squared", 4, 6)])
def test_the_roofline_gauges_count_the_loss_s_work(monkeypatch, loss, rows,
                                                   flops):
    """The live ``train_hbm_gbs`` gauge over ``train_throughput_ratings_per_s``
    is the bytes a rating: under BPR six rows of a triple (u, v_i, v_j
    read and written), not the squared loss's four; the FLOPs handed to
    the roofline model are 10·rank a triple against 6·rank."""
    from large_scale_recommendation_tpu import obs
    from large_scale_recommendation_tpu.obs.instrument import (
        TrainSegmentTimer,
    )

    handed = {}
    finish = TrainSegmentTimer.finish

    def spy(self, units, **kw):
        handed.update(kw, units=units)
        return finish(self, units, **kw)

    monkeypatch.setattr(TrainSegmentTimer, "finish", spy)
    u, i, r = _planted()
    registry, _ = obs.enable()
    try:
        DSGD(_cfg(loss=loss)).fit_device(u, i, r, 300, 500,
                                          checkpoint_every=1)
        got = {(m["name"], m["labels"].get("phase")): m.get("value")
               for m in registry.snapshot()["metrics"]}
    finally:
        obs.disable()
    rank = 8
    assert handed["units"] == len(u)
    assert handed["bytes_per_iteration"] == len(u) * (rows * rank * 4 + 16)
    assert handed["flops_per_iteration"] == len(u) * flops * rank
    per_rating = (got[("train_hbm_gbs", "all")] * 1e9
                  / got[("train_throughput_ratings_per_s", "all")])
    assert per_rating == pytest.approx(rows * rank * 4 + 16, rel=1e-9)


def test_run_time_user_counts_match_the_precomputed_ones():
    u, i, r = _planted()
    pre = DSGD(_cfg()).fit_device(u, i, r, 300, 500)
    run = DSGD(_cfg(precompute_collisions=False)).fit_device(
        u, i, r, 300, 500)
    np.testing.assert_allclose(np.asarray(run.U), np.asarray(pre.U),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(run.V), np.asarray(pre.V),
                               rtol=1e-5, atol=1e-6)


def test_the_host_path_fits_bpr_on_its_own_blocks():
    from large_scale_recommendation_tpu.core.types import Ratings

    u, i, r = _planted()
    solver = DSGD(_cfg())
    model = solver.fit(Ratings.from_arrays(u, i, r))
    assert np.isfinite(np.asarray(model.U)).all()
    assert np.isfinite(np.asarray(model.V)).all()


def test_an_unknown_loss_and_a_missing_n_real_raise():
    with pytest.raises(ValueError, match="unknown loss"):
        DSGDConfig(loss="hinge")
    u, i, U0, V0 = _one_block()
    with pytest.raises(ValueError, match="n_real"):
        sgd_ops.dsgd_train(
            jnp.asarray(U0), jnp.asarray(V0),
            *[jnp.zeros((1, 1, 12), jnp.int32)] * 4, jnp.zeros(6),
            jnp.zeros(9), updater=UPD, minibatch=1, num_blocks=1,
            iterations=1, loss="bpr")


def test_the_squared_path_draws_nothing():
    """``loss="squared"`` (the default) lowers to a program with no draw
    and no negatives' scope: the program it was before BPR."""
    u, i, U0, V0 = _one_block()
    lay = [jnp.asarray(a).reshape(1, 1, 12) for a in (u, i, np.ones(12),
                                                      np.ones(12))]
    args = (jnp.asarray(U0), jnp.asarray(V0), *lay, jnp.ones(6),
            jnp.ones(9))
    kw = dict(updater=UPD, minibatch=4, num_blocks=1, iterations=1)
    default = sgd_ops.dsgd_train.lower(*args, **kw)
    named = sgd_ops.dsgd_train.lower(*args, loss="squared", **kw)
    assert default.as_text() == named.as_text()
    text = default.as_text(debug_info=True)
    assert "threefry" not in text and "sgd/negatives" not in text
    bpr = sgd_ops.dsgd_train.lower(
        *args, None, None, jnp.asarray([9], jnp.int32),
        jax.random.PRNGKey(0), loss="bpr", **kw).as_text(debug_info=True)
    assert "sgd/negatives" in bpr


# -- utils.metrics.expected_percentile_rank's chunk ---------------------------


def test_the_rank_is_the_same_at_any_chunk():
    from large_scale_recommendation_tpu.utils.metrics import (
        expected_percentile_rank,
    )

    rng = np.random.default_rng(4)
    U = rng.normal(size=(50, 6)).astype(np.float32)
    V = rng.normal(size=(300, 6)).astype(np.float32)
    eu = rng.integers(0, 50, 203)
    ei = rng.integers(0, 300, 203)
    w = rng.integers(1, 4, 203).astype(np.float32)
    got = {chunk: expected_percentile_rank(U, V, eu, ei, w, chunk=chunk)
           for chunk in (8, 16, 64, 2048)}
    assert max(got.values()) - min(got.values()) < 1e-12
    assert 0.3 < got[2048] < 0.7


def test_the_chunk_keeps_the_score_matrix_under_the_budget():
    """Counted, not allocated: 2048 rows at the msd catalog, as before; a
    power of two that keeps ``[rows, 2,262,292]`` float32 under the budget
    at the Million Playlist catalog, where 2048 rows would be 18.5 GB."""
    from large_scale_recommendation_tpu.utils.metrics import (
        SCORE_BUDGET_BYTES,
        score_chunk,
    )

    assert score_chunk(41140) == 2048
    rows = score_chunk(2262292)
    assert rows & (rows - 1) == 0 and rows >= 8
    assert rows * 2262292 * 4 <= SCORE_BUDGET_BYTES
    assert 2 * rows * 2262292 * 4 > SCORE_BUDGET_BYTES
    assert score_chunk(10 ** 9) == 8
    assert score_chunk(2262292, chunk=16) == 16


def test_one_scan_drives_both_losses_steps():
    """``_minibatch_scan`` hands ``step`` the minibatches in order, a
    ``None`` stream as ``None``, and the index first when asked."""
    seen = []

    def step(U, V, *chunk):
        seen.append(chunk)
        return U + 1, V

    a = jnp.arange(12, dtype=jnp.int32)
    U, V = sgd_ops._minibatch_scan(step, jnp.zeros(2), jnp.zeros(3), 4,
                                   (a, None), indexed=True)
    assert np.asarray(U).tolist() == [3.0, 3.0]
    (m, chunk, none), = seen  # traced once
    assert none is None and m.shape == () and chunk.shape == (4,)
    with pytest.raises(AssertionError, match="divisible"):
        sgd_ops._minibatch_scan(step, U, V, 5, (a,))
