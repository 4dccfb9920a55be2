"""Two-stage quantized retrieval: round-trip bounds, recall pins,
delta-swap ≡ full-rebuild equivalence, per-request catalog versions.

The fast path's contract has three legs, each pinned here: (a) int8
per-row quantization is bounded (error ≤ scale/2 per element), (b) the
two-stage engine's recall@k against the exact path meets the ≥0.95 @
overfetch-4 acceptance (flat mode on an unstructured catalog — the
hardest case — and clustered mode on a structured one — the case IVF
routing exists for), and (c) a delta swap installs ONLY touched rows
yet lands bit-equivalent to a full rebuild, on the sharded f32 catalog,
the int8 catalog, and through ``ServingEngine.apply_delta`` +
``StreamingDriver.refresh_serving``.
"""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from large_scale_recommendation_tpu.data.blocking import flat_index
from large_scale_recommendation_tpu.models.mf import MFModel
from large_scale_recommendation_tpu.serving import (
    RecResult,
    RetrievalConfig,
    ServingEngine,
    build_quantized_catalog,
    quantize_rows,
    recall_at_k,
)
from large_scale_recommendation_tpu.obs.registry import (
    MetricsRegistry,
    get_registry,
    set_registry,
)
from large_scale_recommendation_tpu.parallel.partitioner import Partitioner
from large_scale_recommendation_tpu.serving import retrieval
from large_scale_recommendation_tpu.serving.retrieval import (
    TwoStageRetriever,
    dequantize_rows,
    exact_top_k,
    select_groups,
)
from large_scale_recommendation_tpu.utils.metrics import DEAD_SLOT_OFFSET


def random_model(num_users, num_items, rank, seed=0, structured=False,
                 n_centers=16):
    rng = np.random.default_rng(seed)
    if structured:
        centers = rng.normal(size=(n_centers, rank)) * 2.0
        V = (centers[rng.integers(0, n_centers, num_items)]
             + 0.3 * rng.normal(size=(num_items, rank)))
    else:
        V = rng.normal(size=(num_items, rank))
    return MFModel(
        U=jnp.asarray(rng.normal(size=(num_users, rank)).astype(
            np.float32)),
        V=jnp.asarray(V.astype(np.float32)),
        users=flat_index(np.arange(num_users, dtype=np.int64)),
        items=flat_index(np.arange(num_items, dtype=np.int64)))


class TestQuantization:
    def test_roundtrip_error_bounded_per_row(self):
        rng = np.random.default_rng(3)
        X = rng.normal(size=(64, 16)).astype(np.float32)
        X[5] *= 1e4  # large-magnitude row: scale adapts per row
        X[9] = 0.0  # all-zero row: scale 1, exact round-trip
        q, s = quantize_rows(X)
        q, s = np.asarray(q), np.asarray(s)
        assert q.dtype == np.int8
        assert np.abs(q).max() <= 127
        deq = np.asarray(dequantize_rows(jnp.asarray(q), jnp.asarray(s)))
        # symmetric rounding: error ≤ scale/2 per element, every row
        bound = s[:, None] / 2 + 1e-6
        assert (np.abs(deq - X) <= bound).all()
        np.testing.assert_array_equal(deq[9], 0.0)

    def test_scale_is_rowmax_over_127(self):
        X = np.array([[1.0, -254.0], [0.0, 0.5]], np.float32)
        _, s = quantize_rows(X)
        np.testing.assert_allclose(np.asarray(s), [2.0, 0.5 / 127],
                                   rtol=1e-6)


class TestTwoStageRecall:
    def test_flat_recall_pin_at_overfetch_4(self):
        """The acceptance pin: recall@10 ≥ 0.95 at overfetch 4, flat
        int8 stage 1, UNSTRUCTURED catalog (quantization is the only
        approximation — the hardest honest case for stage 1)."""
        model = random_model(300, 2048, 16, seed=1)
        exact = ServingEngine(model, k=10)
        fast = ServingEngine(model, k=10,
                             retrieval=RetrievalConfig(overfetch=4))
        uids = np.arange(300)
        ie, se = exact.recommend(uids)
        ia, sa = fast.recommend(uids)
        assert recall_at_k(ia, ie) >= 0.95

        # stage 2 rescored EXACTLY: every returned (id, score) matches
        # the exact path's score for that id (approximation only picks
        # WHICH items are considered, never what they score)
        exact_scores = {(q, int(i)): se[q, j]
                        for q in range(len(uids))
                        for j, i in enumerate(ie[q])}
        checked = 0
        for q in range(len(uids)):
            for j, i in enumerate(ia[q]):
                key = (q, int(i))
                if key in exact_scores:
                    np.testing.assert_allclose(
                        sa[q, j], exact_scores[key], rtol=1e-4,
                        atol=1e-4)
                    checked += 1
        assert checked > 1000  # the overlap is nearly everything

    def test_clustered_recall_pin_on_structured_catalog(self):
        """Clustered MIPS stage 1 on a catalog WITH cluster structure
        (the regime IVF routing exists for — real embedding catalogs
        cluster): recall@10 ≥ 0.95 probing 12 of 32 cells."""
        model = random_model(256, 4096, 16, seed=2, structured=True)
        exact = ServingEngine(model, k=10)
        fast = ServingEngine(model, k=10, retrieval=RetrievalConfig(
            overfetch=4, n_clusters=32, n_probe=12, kmeans_sample=4096))
        uids = np.arange(256)
        ie, _ = exact.recommend(uids)
        ia, _ = fast.recommend(uids)
        assert recall_at_k(ia, ie) >= 0.95

    def test_engine_contract_conventions(self):
        """The recommend conventions hold on the fast path: unknown
        users → -1/0.0 rows, int64 ids, return_mask, and results are
        RecResult tuples carrying the catalog version."""
        model = random_model(50, 256, 8, seed=3)
        eng = ServingEngine(model, k=5, retrieval="two_stage")
        res = eng.recommend(np.array([1, 2, 99999]), return_mask=True)
        ids, scores, mask = res
        assert isinstance(res, RecResult)
        assert res.catalog_version == eng.version
        assert res.degraded is False
        assert ids.dtype == np.int64
        np.testing.assert_array_equal(mask, [True, True, False])
        np.testing.assert_array_equal(ids[2], -1)
        np.testing.assert_array_equal(scores[2], 0.0)

    def test_train_exclusions_apply_exactly(self):
        """Excluded (train-seen) pairs never surface from the fast path
        — the membership test's semantics match the exact scatter-min."""
        model = random_model(40, 128, 8, seed=4)
        rng = np.random.default_rng(5)
        tu = rng.integers(0, 40, 300).astype(np.int64)
        ti = rng.integers(0, 128, 300).astype(np.int64)
        eng = ServingEngine(model, k=10, train=(tu, ti),
                            retrieval=RetrievalConfig(overfetch=8))
        uids = np.arange(40)
        ids, scores = eng.recommend(uids)
        excluded = set(zip(tu.tolist(), ti.tolist()))
        for q in range(40):
            for i, s in zip(ids[q], scores[q]):
                if i >= 0:
                    assert (q, int(i)) not in excluded

    def test_clustered_slabs_partition_every_row(self):
        """Every catalog row lives at exactly one slab/overflow
        position, and the capacity cap bounds every cluster."""
        rng = np.random.default_rng(6)
        V = rng.normal(size=(1000, 8)).astype(np.float32)
        cat = build_quantized_catalog(V, config=RetrievalConfig(
            n_clusters=8, kmeans_sample=1000, slab_slack=1.5))
        assert cat.clustered
        pos = cat.pos_of_row
        assert len(np.unique(pos)) == 1000  # injective placement
        C, m, _ = cat.slab_q.shape
        rows = np.concatenate([np.asarray(cat.slab_rows).ravel(),
                               np.asarray(cat.ovf_rows)])
        real = rows[rows < 1000]
        assert sorted(real.tolist()) == list(range(1000))
        stats = cat.stats
        assert stats["max_cluster"] <= stats["capacity_cap"] == m


def _score_rows(kind, b, n, k, seed):
    """``f32[b, n]`` rows for ``exact_top_k``, by what they stress."""
    rng = np.random.default_rng(seed)
    if kind == "tie_free":
        return rng.permuted(np.tile(np.arange(n, dtype=np.float32),
                                    (b, 1)), axis=1)
    s = rng.integers(-3, 4, (b, n)).astype(np.float32)  # ties everywhere
    if kind == "dead":  # masked rows and train-seen exclusions
        dead = rng.random((b, n))
        s[dead < 0.3] = -np.inf
        s[dead > 0.8] = DEAD_SLOT_OFFSET
    elif kind == "few_live":  # fewer live scores than k, in late groups
        live = rng.random((b, n)) < (k // 4) / n
        live[:, -1] = True
        s = np.where(live, s, -np.inf).astype(np.float32)
    return s


class TestExactTopK:
    """Stage 1's candidate selection is ``lax.top_k`` of the whole row,
    values and positions, however the row is cut into groups."""

    K = 40  # the benchmark's k * overfetch

    @pytest.mark.parametrize("kind,b,n", [
        ("tie_free", 8, 49152),
        ("few_values", 8, 49152),
        ("dead", 8, 49152),
        ("few_live", 8, 49152),
        ("dead", 8, 50001),        # n not a multiple of S: -inf pads
        ("few_live", 8, 50001),
        ("few_values", 5, 49152),  # a bucket 8 does not divide
        ("dead", 8, 1 << 20),      # wide enough for S = 256
        ("dead", 8, 4096),         # too small: lax.top_k as before
        *[("dead", b, 49152) for b in (16, 32, 64, 128, 256)],
    ])
    def test_equals_lax_top_k(self, kind, b, n):
        groups = select_groups(n, self.K)
        assert (groups is None) == (n == 4096)
        if groups is not None:
            S, G = groups
            assert (S == 256) == (n == 1 << 20)
            assert G * S >= n > (G - 1) * S and G >= 4 * self.K
        scores = jnp.asarray(_score_rows(kind, b, n, self.K, seed=b + n))
        want_v, want_at = jax.lax.top_k(scores, self.K)
        got_v, got_at = jax.jit(partial(exact_top_k, k=self.K))(scores)
        np.testing.assert_array_equal(np.asarray(got_v),
                                      np.asarray(want_v))
        np.testing.assert_array_equal(np.asarray(got_at),
                                      np.asarray(want_at))

    @pytest.mark.parametrize("sharded", [False, True],
                             ids=["one_device", "rank_sharded"])
    def test_retriever_answers_unchanged(self, sharded, monkeypatch):
        """On a catalog wide enough to engage the two levels,
        ``TwoStageRetriever.topk`` hands stage 2 the candidates a
        whole-row ``lax.top_k`` would, masked rows and train-seen
        exclusions among them, and so returns the same answers."""
        rng = np.random.default_rng(11)
        n, rank, b, k = 50000, 16, 32, 10
        V = rng.normal(size=(n, rank)).astype(np.float32)
        U = jnp.asarray(rng.normal(size=(b, rank)).astype(np.float32))
        mask = rng.random(n) > 0.2
        e = 256
        excl = (rng.integers(0, b, e).astype(np.int32),
                rng.integers(0, n, e).astype(np.int32),
                np.full(e, DEAD_SLOT_OFFSET, np.float32))
        part = (Partitioner(num_devices=8, model_parallel=2)
                if sharded else None)
        ret = TwoStageRetriever(V, item_mask=mask, partitioner=part)
        kc = ret.candidate_count(k)
        assert select_groups(n, kc) is not None
        got_v, got_rows = ret.topk(U, excl, k=k)

        cat = ret.catalog
        excl = tuple(jnp.asarray(x) for x in excl)
        if sharded:  # as topk replicates them onto the mesh
            U = part.shard(U)
            excl = tuple(part.shard(x) for x in excl)
        qU, u_scale = retrieval.quantize_rows(U)
        stage1 = (qU, u_scale, cat.q, cat.scale, cat.item_w, *excl)
        cand = retrieval._stage1_flat(*stage1, kc=kc)
        monkeypatch.setattr(retrieval, "select_groups", lambda n, k: None)
        whole = retrieval._stage1_flat.__wrapped__(*stage1, kc=kc)
        for got, want in zip(cand, whole):
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want))
        want_v, want_rows = retrieval._stage2(
            U, ret.V, cat.item_w, *whole, *excl, k=k, exact=True)
        np.testing.assert_array_equal(np.asarray(got_rows),
                                      np.asarray(want_rows))
        np.testing.assert_array_equal(np.asarray(got_v),
                                      np.asarray(want_v))

    @staticmethod
    def _top_k_widths(jaxpr):
        """Operand widths of every ``top_k`` in a jaxpr, nested ones
        included."""
        widths = []
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "top_k":
                widths.append(eqn.invars[0].aval.shape[-1])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                widths.extend(TestExactTopK._top_k_widths(sub))
        return widths

    @pytest.mark.parametrize("n,path", [(1 << 20, "two_level"),
                                        (4096, "full")])
    def test_shape_rule_at_the_benchmarks_size(self, n, path):
        """Shapes only: at ``[256, 1048576]`` and 40 candidates
        ``_stage1_flat`` holds no top-k wider than the group maxima or
        the gathered groups, the toy catalog keeps its whole-row top-k,
        and the retriever's counter names the path from the same rule."""
        b, rank, kc = 256, 512, self.K
        f32, i32 = jnp.float32, jnp.int32
        sds = jax.ShapeDtypeStruct
        jaxpr = jax.make_jaxpr(partial(retrieval._stage1_flat, kc=kc))(
            sds((b, rank), jnp.int8), sds((b,), f32),
            sds((n, rank), jnp.int8), sds((n,), f32), sds((n,), f32),
            sds((8,), i32), sds((8,), i32), sds((8,), f32))
        widths = self._top_k_widths(jaxpr.jaxpr)
        if path == "two_level":
            S, G = select_groups(n, kc)
            assert sorted(widths) == sorted([G, kc * S])
        else:
            assert widths == [n]

        prev = get_registry()
        reg = MetricsRegistry()
        set_registry(reg)
        try:
            rng = np.random.default_rng(3)
            ret = TwoStageRetriever(
                rng.normal(size=(n, 4)).astype(np.float32),
                config=RetrievalConfig(overfetch=4))
        finally:
            set_registry(prev)
        excl = (np.zeros(8, np.int32), np.zeros(8, np.int32),
                np.full(8, np.inf, np.float32))
        ret.topk(jnp.asarray(rng.normal(size=(8, 4)).astype(np.float32)),
                 excl, k=10)
        counts = {dict(c.labels)["path"]: c.value
                  for c in reg.find("serving_stage1_select_total")}
        assert counts == {path: 1, "full" if path == "two_level"
                          else "two_level": 0}


class TestDeltaSwaps:
    def _patched(self, V1, rows, seed=7):
        rng = np.random.default_rng(seed)
        V2 = V1.copy()
        V2[rows] = rng.normal(size=(len(rows), V1.shape[1])).astype(
            np.float32)
        return V2

    def test_sharded_catalog_delta_bit_equals_rebuild(self):
        from large_scale_recommendation_tpu.parallel.serving import (
            shard_catalog,
        )

        rng = np.random.default_rng(8)
        V1 = rng.normal(size=(100, 8)).astype(np.float32)
        rows = np.array([0, 3, 50, 99])
        V2 = self._patched(V1, rows)
        mask = np.ones(100, bool)
        mask[17] = False
        cat1 = shard_catalog(jnp.asarray(V1), item_mask=mask)
        rebuilt = shard_catalog(jnp.asarray(V2), item_mask=mask)
        delta = cat1.apply_delta(rows, V2[rows])
        np.testing.assert_array_equal(np.asarray(delta.V_sh),
                                      np.asarray(rebuilt.V_sh))
        np.testing.assert_array_equal(np.asarray(delta.w_sh),
                                      np.asarray(rebuilt.w_sh))
        assert delta.version != cat1.version
        assert delta.rows_per_shard == cat1.rows_per_shard

    def test_quantized_flat_delta_bit_equals_rebuild(self):
        rng = np.random.default_rng(9)
        V1 = rng.normal(size=(64, 8)).astype(np.float32)
        rows = np.array([1, 7, 63])
        V2 = self._patched(V1, rows)
        cat1 = build_quantized_catalog(jnp.asarray(V1))
        rebuilt = build_quantized_catalog(jnp.asarray(V2))
        delta = cat1.apply_delta(rows, V2[rows], version=rebuilt.version)
        np.testing.assert_array_equal(np.asarray(delta.q),
                                      np.asarray(rebuilt.q))
        np.testing.assert_array_equal(np.asarray(delta.scale),
                                      np.asarray(rebuilt.scale))
        assert delta.version == rebuilt.version

    def test_quantized_clustered_delta_requantizes_dirty_rows(self):
        """Clustered delta keeps each row's cluster slot but its slab
        content must equal a fresh per-row quantization of the new
        factors (re-clustering is a full-rebuild concern)."""
        rng = np.random.default_rng(10)
        V1 = rng.normal(size=(500, 8)).astype(np.float32)
        rows = np.arange(0, 500, 37)
        V2 = self._patched(V1, rows)
        cat = build_quantized_catalog(jnp.asarray(V1),
                                      config=RetrievalConfig(
                                          n_clusters=8,
                                          kmeans_sample=500))
        delta = cat.apply_delta(rows, V2[rows], version=999)
        q2, s2 = quantize_rows(jnp.asarray(V2))
        C, m, r = delta.slab_q.shape
        flat_q = np.concatenate([np.asarray(delta.slab_q).reshape(-1, r),
                                 np.asarray(delta.ovf_q)])
        flat_s = np.concatenate([np.asarray(delta.slab_scale).ravel(),
                                 np.asarray(delta.ovf_scale)])
        pos = cat.pos_of_row
        np.testing.assert_array_equal(flat_q[pos], np.asarray(q2))
        np.testing.assert_array_equal(flat_s[pos], np.asarray(s2))
        assert delta.version == 999

    @pytest.mark.parametrize("retrieval", [None, "flat"])
    def test_engine_delta_equals_full_refresh(self, retrieval):
        """The end contract: an engine that took a DELTA serves results
        bit-identical to an engine fully rebuilt from the patched model
        — exact mesh path and flat fast path both (clustered would
        re-cluster on rebuild; its slab equivalence is pinned above).
        Zero new compiles: a delta never changes a shape."""
        cfg = (None if retrieval is None
               else RetrievalConfig(overfetch=4))
        model_a = random_model(60, 256, 8, seed=11)
        model_b = random_model(60, 256, 8, seed=11)
        rng = np.random.default_rng(12)
        item_rows = np.array([0, 17, 200, 255])
        user_rows = np.array([3, 59])
        V_new = rng.normal(size=(4, 8)).astype(np.float32)
        U_new = rng.normal(size=(2, 8)).astype(np.float32)

        eng_a = ServingEngine(model_a, k=6, retrieval=cfg)
        uids = np.arange(60)
        eng_a.recommend(uids)  # warm
        variants = eng_a.executable_variants
        v0 = eng_a.version
        versions_seen = []
        eng_a.on_refresh = versions_seen.append
        v1 = eng_a.apply_delta(item_rows=item_rows, V_rows=V_new,
                               user_rows=user_rows, U_rows=U_new)
        assert v1 != v0 and versions_seen == [v1]
        assert eng_a.stats["delta_swaps"] == 1
        assert eng_a.executable_variants == variants  # no new compiles

        # full-rebuild reference: patch model_b wholesale, fresh engine
        model_b.V = jnp.asarray(model_b.V).at[
            jnp.asarray(item_rows)].set(jnp.asarray(V_new))
        model_b.U = jnp.asarray(model_b.U).at[
            jnp.asarray(user_rows)].set(jnp.asarray(U_new))
        eng_b = ServingEngine(model_b, k=6, retrieval=cfg)
        ra = eng_a.recommend(uids)
        rb = eng_b.recommend(uids)
        np.testing.assert_array_equal(ra[0], rb[0])
        np.testing.assert_array_equal(ra[1], rb[1])
        # per-request version moved with the delta (the mid-flight-swap
        # detection satellite): results carry the post-delta token
        assert ra.catalog_version == v1

    def test_engine_delta_rejects_vocab_growth(self):
        model = random_model(20, 64, 4, seed=13)
        eng = ServingEngine(model, k=4)
        with pytest.raises(ValueError, match="vocab grew"):
            eng.apply_delta(item_rows=np.array([64]),
                            V_rows=np.zeros((1, 4), np.float32))
        with pytest.raises(ValueError, match="vocab grew"):
            eng.apply_delta(user_rows=np.array([20]),
                            U_rows=np.zeros((1, 4), np.float32))


class TestDriverDeltaShipping:
    def test_refresh_serving_ships_delta_and_matches_full(self, tmp_path):
        """The streaming wire: batches applied through the driver mark
        dirty ids; ``refresh_serving()`` ships ONLY those rows and the
        engine then serves exactly what a full re-snapshot refresh
        would."""
        from large_scale_recommendation_tpu.core.generators import (
            SyntheticMFGenerator,
        )
        from large_scale_recommendation_tpu.models.online import (
            OnlineMF,
            OnlineMFConfig,
        )
        from large_scale_recommendation_tpu.streams.driver import (
            StreamingDriver,
            StreamingDriverConfig,
        )
        from large_scale_recommendation_tpu.streams.log import EventLog

        gen = SyntheticMFGenerator(num_users=40, num_items=30, rank=3,
                                   noise=0.05, seed=14)
        model = OnlineMF(OnlineMFConfig(num_factors=4,
                                        learning_rate=0.05,
                                        minibatch_size=64))
        log = EventLog(str(tmp_path / "wal"))
        # seed the vocab, then attach the engine (so later batches only
        # touch KNOWN ids — the geometry-stable delta regime)
        model.partial_fit(gen.generate(800))
        driver = StreamingDriver(model, log, str(tmp_path / "ckpt"),
                                 config=StreamingDriverConfig(
                                     batch_records=200))
        engine = driver.serving_engine(k=5)
        v0 = engine.version
        log.append(0, gen.generate(400))
        driver.run()
        tel = driver.telemetry()
        assert tel["dirty_users"] > 0 and tel["dirty_items"] > 0
        driver.refresh_serving(delta=True)  # asserts the delta path ran
        assert engine.stats["delta_swaps"] == 1
        assert engine.version != v0
        assert driver.telemetry()["dirty_users"] == 0
        # the delta-refreshed engine answers exactly like the live model
        uids = np.arange(40)
        ids_d, scores_d = engine.recommend(uids)
        ids_f, scores_f = model.to_model().recommend(uids, k=5)
        np.testing.assert_array_equal(ids_d, ids_f)
        np.testing.assert_allclose(scores_d, scores_f, rtol=1e-6,
                                   atol=1e-7)

    def test_refresh_serving_falls_back_on_vocab_growth(self, tmp_path):
        """New ids since the engine's snapshot change the geometry: auto
        mode silently takes the full-refresh path; delta=True raises."""
        from large_scale_recommendation_tpu.core.generators import (
            SyntheticMFGenerator,
        )
        from large_scale_recommendation_tpu.models.online import (
            OnlineMF,
            OnlineMFConfig,
        )
        from large_scale_recommendation_tpu.streams.driver import (
            StreamingDriver,
        )
        from large_scale_recommendation_tpu.streams.log import EventLog

        gen = SyntheticMFGenerator(num_users=20, num_items=15, rank=3,
                                   noise=0.05, seed=15)
        model = OnlineMF(OnlineMFConfig(num_factors=4,
                                        minibatch_size=64))
        model.partial_fit(gen.generate(200))
        log = EventLog(str(tmp_path / "wal"))
        driver = StreamingDriver(model, log, str(tmp_path / "ckpt"))
        engine = driver.serving_engine(k=4)
        # grow the vocab directly on the model (new user/item ids)
        bigger = SyntheticMFGenerator(num_users=40, num_items=30, rank=3,
                                      noise=0.05, seed=16)
        log.append(0, bigger.generate(300))
        driver.run()
        with pytest.raises(ValueError, match="geometry"):
            driver.refresh_serving(delta=True)
        v0 = engine.version
        driver.refresh_serving()  # auto: falls back to full refresh
        assert engine.version != v0
        assert engine.stats["delta_swaps"] == 0
