"""The unified logical-axis Partitioner (ISSUE 7): rules-table
resolution on 1/8/16-device meshes, sharding equality with the
hand-rolled constructions it replaced, placement/checkpoint wiring, and
the equivalence pins — mesh DSGD / mesh ALS / mesh serving give the
same arrays **bit for bit** over the raw 1-D ``('blocks',)`` mesh and
over the ``('data', 'model')`` ``Partitioner``, both run in this process.
"""

import numpy as np
import pytest

import jax
from jax.sharding import AbstractMesh, NamedSharding, PartitionSpec as P

from large_scale_recommendation_tpu.parallel.mesh import (
    BLOCK_AXIS,
    make_block_mesh,
    ring_backward,
)
from large_scale_recommendation_tpu.parallel.partitioner import (
    DATA_AXIS,
    DEFAULT_RULES,
    MODEL_AXIS,
    Partitioner,
    as_partitioner,
    make_data_model_mesh,
)

LOGICAL_AXES = [name for name, _ in DEFAULT_RULES]


class TestRulesTable:
    """Every logical axis must resolve on every mesh shape the stack
    runs on: 1 device (laptop), 8 (the conftest virtual mesh / one TPU
    VM), 16 (pod-shaped — abstract here; scripts/pod_dryrun.py resolves
    the same table over 16 REAL virtual devices and test_pod_scale pins
    its JSON contract)."""

    @pytest.mark.parametrize("n_dev", [1, 4, 8])
    def test_all_axes_resolve_on_real_meshes(self, n_dev):
        for part in (Partitioner(num_devices=n_dev),
                     Partitioner(mesh=make_block_mesh(n_dev))):
            assert part.num_blocks == n_dev
            for name in LOGICAL_AXES:
                part.spec(name)       # must not raise
                part.sharding(name)   # must build a NamedSharding
            assert part.spec("users", "rank") == part.spec("items", "rank")

    def test_all_axes_resolve_on_16_device_abstract_mesh(self):
        part = Partitioner(mesh=AbstractMesh((16, 1),
                                             (DATA_AXIS, MODEL_AXIS)))
        assert part.num_blocks == 16
        for name in LOGICAL_AXES:
            part.spec(name)
        assert part.spec("users", "rank") == P(DATA_AXIS, MODEL_AXIS)
        assert part.spec("ratings") == P(DATA_AXIS)
        assert part.spec("queries") == P(None)
        assert len(part.ring_backward()) == 16

    def test_data_model_mesh_shape(self):
        part = Partitioner(num_devices=8)
        assert tuple(part.mesh.axis_names) == (DATA_AXIS, MODEL_AXIS)
        assert dict(part.mesh.shape) == {DATA_AXIS: 8, MODEL_AXIS: 1}
        assert part.data_axis == DATA_AXIS
        assert part.model_axis == MODEL_AXIS
        assert part.model_parallel == 1

    def test_legacy_blocks_mesh_adopts_its_axis_as_data(self):
        mesh = make_block_mesh(4)
        part = Partitioner(mesh=mesh)
        assert part.data_axis == BLOCK_AXIS
        assert part.model_axis is None
        # 'rank' maps to the (absent) model axis -> unsharded dim
        assert part.spec("users", "rank") == P(BLOCK_AXIS, None)

    def test_unknown_logical_axis_raises(self):
        part = Partitioner(num_devices=4)
        with pytest.raises(KeyError, match="unknown logical axis"):
            part.spec("wombats")

    def test_ring_matches_legacy_helper(self):
        part = Partitioner(num_devices=8)
        assert list(part.ring_backward()) == ring_backward(8)

    def test_model_parallel_guard(self):
        part = Partitioner(mesh=AbstractMesh((4, 2),
                                             (DATA_AXIS, MODEL_AXIS)))
        assert part.model_parallel == 2
        with pytest.raises(NotImplementedError, match="rank"):
            part.require_no_model_parallel("mesh DSGD")

    def test_model_parallel_must_divide_devices(self):
        with pytest.raises(ValueError, match="does not divide"):
            make_data_model_mesh(num_devices=8, model_parallel=3)


class TestShardingEquality:
    """The partitioner must hand back EXACTLY the shardings the
    hand-rolled code constructed — equality of layouts, not just of
    results."""

    def test_matches_hand_rolled_on_legacy_mesh(self):
        mesh = make_block_mesh(4)
        part = Partitioner(mesh=mesh)
        hand = NamedSharding(mesh, P(BLOCK_AXIS))
        assert part.sharding("users", "rank").is_equivalent_to(hand, 2)
        assert part.sharding("items", "rank").is_equivalent_to(hand, 2)
        assert part.sharding("ratings").is_equivalent_to(hand, 3)
        assert part.sharding("users").is_equivalent_to(hand, 1)
        assert part.replicated().is_equivalent_to(
            NamedSharding(mesh, P()), 2)

    def test_size1_model_axis_is_layout_noop(self):
        part = Partitioner(num_devices=4)
        flat = NamedSharding(part.mesh, P(DATA_AXIS))
        assert part.sharding("users", "rank").is_equivalent_to(flat, 2)

    def test_as_partitioner_identity_and_hash(self):
        mesh = make_block_mesh(4)
        p1, p2 = as_partitioner(mesh), as_partitioner(mesh)
        assert p1 == p2 and hash(p1) == hash(p2)
        assert as_partitioner(p1) is p1
        assert p1 != Partitioner(mesh=make_block_mesh(8))


class TestPlacement:
    def test_shard_places_with_rules_sharding(self):
        part = Partitioner(num_devices=4)
        x = np.arange(32, dtype=np.float32).reshape(8, 4)
        arr = part.shard(x, "users", "rank")
        assert arr.sharding.is_equivalent_to(
            part.sharding("users", "rank"), 2)
        np.testing.assert_array_equal(np.asarray(arr), x)

    def test_place_single_process_equals_shard(self):
        part = Partitioner(num_devices=4)
        x = np.arange(16, dtype=np.float32)
        np.testing.assert_array_equal(
            np.asarray(part.place(x, "ratings")),
            np.asarray(part.shard(x, "ratings")))

    def test_make_global_array_roundtrips(self):
        part = Partitioner(num_devices=4)
        x = np.arange(64, dtype=np.float32).reshape(16, 4)
        arr = part.make_global_array(x, "items", "rank")
        np.testing.assert_array_equal(np.asarray(arr), x)
        assert arr.sharding.is_equivalent_to(
            part.sharding("items", "rank"), 2)

    def test_constrain_under_jit(self):
        part = Partitioner(num_devices=4)
        x = np.arange(16, dtype=np.float32).reshape(8, 2)

        @jax.jit
        def f(a):
            return part.constrain(a * 2.0, "users", "rank")

        out = f(x)
        np.testing.assert_allclose(np.asarray(out), x * 2.0)
        assert out.sharding.is_equivalent_to(
            part.sharding("users", "rank"), 2)


class TestCheckpointWiring:
    """restore_segment_state_sharded(partitioner=...) re-shards via the
    rules table — the resume path training actually runs under."""

    def test_partitioner_restore_roundtrip(self, tmp_path):
        from large_scale_recommendation_tpu.utils.checkpoint import (
            ShardedCheckpointManager,
            restore_segment_state_sharded,
        )

        part = Partitioner(num_devices=4)
        U = part.shard(np.arange(32, dtype=np.float32).reshape(8, 4),
                       "users", "rank")
        V = part.shard(-np.arange(16, dtype=np.float32).reshape(8, 2),
                       "items", "rank")
        mgr = ShardedCheckpointManager(str(tmp_path))
        mgr.save(3, {"U": U, "V": V}, {"kind": "t"})
        U2, V2, done = restore_segment_state_sharded(
            mgr, "t", np.zeros((8, 4), np.float32),
            np.zeros((8, 2), np.float32), partitioner=part)
        assert done == 3
        np.testing.assert_array_equal(np.asarray(U2), np.asarray(U))
        np.testing.assert_array_equal(np.asarray(V2), np.asarray(V))
        assert U2.sharding.is_equivalent_to(
            part.sharding("users", "rank"), 2)

    def test_sharding_and_partitioner_are_exclusive(self, tmp_path):
        from large_scale_recommendation_tpu.utils.checkpoint import (
            ShardedCheckpointManager,
            restore_segment_state_sharded,
        )

        part = Partitioner(num_devices=4)
        mgr = ShardedCheckpointManager(str(tmp_path))
        with pytest.raises(ValueError, match="not both"):
            restore_segment_state_sharded(
                mgr, "t", np.zeros((8, 2)), np.zeros((8, 2)),
                sharding=part.replicated(), partitioner=part)


def run_workloads(mesh_factory, n_devices):
    """The four mesh workloads of the equivalence pins, run over
    ``mesh_factory(n_devices)``-built meshes. Returns {name: np.ndarray}."""
    from large_scale_recommendation_tpu.core.generators import (
        SyntheticMFGenerator,
    )
    from large_scale_recommendation_tpu.models.als import ALSConfig
    from large_scale_recommendation_tpu.parallel.als_mesh import MeshALS
    from large_scale_recommendation_tpu.parallel.dsgd_mesh import (
        MeshDSGD,
        MeshDSGDConfig,
    )
    from large_scale_recommendation_tpu.parallel.serving import (
        mesh_top_k_recommend,
    )

    out: dict = {}
    gen = SyntheticMFGenerator(num_users=120, num_items=90, rank=6,
                               noise=0.1, seed=3)
    train = gen.generate(6000)
    ru, ri, rv, _ = train.to_numpy()

    # mesh DSGD, host-blocked path
    dcfg = MeshDSGDConfig(num_factors=6, lambda_=0.01, iterations=3,
                          learning_rate=0.05, lr_schedule="constant",
                          seed=0, minibatch_size=128, init_scale=0.3)
    m = MeshDSGD(dcfg, mesh=mesh_factory(n_devices)).fit(train)
    out["dsgd_U"], out["dsgd_V"] = np.asarray(m.U), np.asarray(m.V)

    # mesh DSGD, device-blocked path
    md = MeshDSGD(dcfg, mesh=mesh_factory(n_devices)).fit_device(
        ru, ri, rv, 120, 90)
    out["dsgd_dev_U"] = np.asarray(md.U)
    out["dsgd_dev_V"] = np.asarray(md.V)

    # mesh ALS
    acfg = ALSConfig(num_factors=6, lambda_=0.05, iterations=3, seed=0)
    ma = MeshALS(acfg, mesh=mesh_factory(n_devices)).fit(train)
    out["als_U"], out["als_V"] = np.asarray(ma.U), np.asarray(ma.V)

    # mesh serving over a fixed random catalog (exclusions exercised)
    rng = np.random.default_rng(7)
    U = rng.normal(size=(60, 6)).astype(np.float32)
    V = rng.normal(size=(83, 6)).astype(np.float32)
    rows, scores = mesh_top_k_recommend(
        U, V, np.arange(40, dtype=np.int32), k=7, chunk=16,
        train_u=ru[:400] % 60, train_i=ri[:400] % 83,
        mesh=mesh_factory(n_devices))
    out["serve_rows"], out["serve_scores"] = rows, scores
    return out


@pytest.fixture(scope="module")
def unified_outputs():
    """The workloads run over BOTH mesh spellings the unified layer
    accepts, at the ring cell's size (4) and the suite's whole virtual
    mesh (8; 2 would be a ring that is its own inverse). Module-scoped:
    each run trains mesh DSGD twice, mesh ALS once and serves once."""
    return {
        (spelling, n): run_workloads(factory, n)
        for n in (4, 8)
        for spelling, factory in (
            ("legacy", make_block_mesh),
            ("partitioner", lambda k: Partitioner(num_devices=k)))
    }


class TestSpellingEquivalence:
    """The raw 1-D ring mesh and the partitioner's own ('data', 'model')
    mesh give the same arrays bit for bit. Both sides run here, so the
    pin holds on any JAX; what the mesh solvers compute is pinned against
    the one-device solvers in test_dsgd_mesh, test_als, test_mesh_serving
    and test_rank_sharding."""

    @pytest.mark.parametrize("n", [4, 8])
    @pytest.mark.parametrize("key", [
        "dsgd_U", "dsgd_V",            # mesh DSGD, host-blocked
        "dsgd_dev_U", "dsgd_dev_V",    # mesh DSGD, device-blocked
        "als_U", "als_V",              # mesh ALS
        "serve_rows", "serve_scores",  # mesh serving
    ])
    def test_both_spellings_agree_bitwise(self, unified_outputs, key, n):
        np.testing.assert_array_equal(
            unified_outputs["legacy", n][key],
            unified_outputs["partitioner", n][key],
            err_msg=f"{key} at {n} devices")


class TestSolverSurfaces:
    def test_serving_engine_accepts_partitioner(self):
        from large_scale_recommendation_tpu.core.generators import (
            SyntheticMFGenerator,
        )
        from large_scale_recommendation_tpu.models.als import ALS, ALSConfig
        from large_scale_recommendation_tpu.serving.engine import (
            ServingEngine,
        )

        train = SyntheticMFGenerator(num_users=40, num_items=30, rank=4,
                                     noise=0.05, seed=5).generate(3000)
        model = ALS(ALSConfig(num_factors=4, lambda_=0.05,
                              iterations=3)).fit(train)
        part = Partitioner(num_devices=4)
        eng = ServingEngine(model, k=5, mesh=part, max_batch=16,
                            min_bucket=4)
        ids_e, scores_e = eng.recommend(np.arange(8))
        ids_m, scores_m = model.recommend(np.arange(8), k=5)
        np.testing.assert_allclose(scores_e, scores_m, rtol=1e-5)
        np.testing.assert_array_equal(ids_e, ids_m)

    def test_model_recommend_accepts_partitioner(self):
        from large_scale_recommendation_tpu.core.generators import (
            SyntheticMFGenerator,
        )
        from large_scale_recommendation_tpu.models.als import ALS, ALSConfig

        train = SyntheticMFGenerator(num_users=30, num_items=25, rank=4,
                                     noise=0.05, seed=6).generate(2000)
        model = ALS(ALSConfig(num_factors=4, lambda_=0.05,
                              iterations=3)).fit(train)
        part = Partitioner(num_devices=4)
        i1, s1 = model.recommend(np.arange(6), k=4, mesh=part)
        i2, s2 = model.recommend(np.arange(6), k=4)
        np.testing.assert_allclose(s1, s2, rtol=1e-5)
        np.testing.assert_array_equal(i1, i2)
        # the catalog cache keys on the interned Mesh: a raw-mesh caller
        # shares the partitioner caller's build
        assert part.mesh in model.__dict__["_serving_catalogs"]

    def test_package_public_surface(self):
        import large_scale_recommendation_tpu.parallel as par

        for name in ("Partitioner", "as_partitioner", "DEFAULT_RULES",
                     "DistributedConfig", "initialize_distributed",
                     "host_rating_shard", "make_global_array",
                     "global_device_blocked", "make_block_mesh",
                     "MeshDSGD", "MeshALS", "shard_catalog",
                     "mesh_top_k_recommend"):
            assert getattr(par, name) is not None
        assert "Partitioner" in par.__all__
        with pytest.raises(AttributeError):
            par.no_such_symbol


class TestShardingFunnel:
    """Pins for the ISSUE-15 sharding-funnel fixes: the legacy surfaces
    (``mesh.make_block_mesh``/``mesh.replicated``/
    ``distributed.make_global_array``) now construct THROUGH
    ``parallel/partitioner.py`` (graftlint rule ``sharding-funnel``) and
    must keep producing the exact pre-funnel objects."""

    def test_make_block_mesh_delegates_unchanged(self):
        from large_scale_recommendation_tpu.parallel.mesh import (
            select_devices,
        )
        from large_scale_recommendation_tpu.parallel.partitioner import (
            make_legacy_block_mesh,
        )

        mesh = make_block_mesh(4)
        assert mesh.axis_names == (BLOCK_AXIS,)
        assert list(mesh.devices.flat) == select_devices(4)
        assert mesh == make_legacy_block_mesh(4)

    def test_more_devices_than_exist_raises_instead_of_going_to_cpu(
            self, monkeypatch):
        """A one-chip host asked for a 4-device mesh must RAISE. The
        removed behaviour: ``select_devices`` looked at
        ``jax.devices("cpu")`` and quietly handed back virtual CPU
        devices, so ``Partitioner(num_devices=4)`` "worked" — off the
        chip."""
        import jax

        from large_scale_recommendation_tpu.parallel import mesh as mesh_mod

        cpus = jax.devices()
        assert len(cpus) >= 4  # the virtual devices the fallback found

        class OneChip:
            platform = "tpu"

        chip = [OneChip()]

        def devices(backend=None):
            return cpus if backend == "cpu" else chip

        monkeypatch.setattr(mesh_mod.jax, "devices", devices)
        assert mesh_mod.select_devices() == chip
        with pytest.raises(ValueError, match=r"need 4 devices, have 1 "
                                             r"\(tpu\)"):
            mesh_mod.select_devices(4)
        with pytest.raises(ValueError, match="need 4 devices, have 1"):
            Partitioner(num_devices=4)
        with pytest.raises(ValueError, match="need 4 devices, have 1"):
            make_block_mesh(4)
        # explicit devices= is how CPU-mesh callers name their devices
        assert mesh_mod.select_devices(4, devices=cpus) == list(cpus[:4])

    def test_replicated_equals_hand_rolled(self):
        from large_scale_recommendation_tpu.parallel.mesh import (
            replicated,
        )

        mesh = make_block_mesh(4)
        assert replicated(mesh) == NamedSharding(mesh, P())
        mesh2 = make_data_model_mesh(4)
        assert replicated(mesh2) == NamedSharding(mesh2, P())

    def test_replicated_works_on_any_mesh(self):
        """The compatibility surface must accept meshes the rules table
        cannot adopt (no inferable data axis) — an empty spec is valid
        on every mesh, exactly as before the funnel refactor."""
        from jax.sharding import Mesh

        from large_scale_recommendation_tpu.parallel.mesh import (
            replicated,
            select_devices,
        )

        weird = Mesh(np.asarray(select_devices(4)).reshape(2, 2),
                     ("x", "y"))
        assert replicated(weird) == NamedSharding(weird, P())

    def test_raw_sharding_equals_hand_rolled(self):
        from large_scale_recommendation_tpu.parallel.partitioner import (
            raw_sharding,
        )

        mesh = make_block_mesh(4)
        spec = P(BLOCK_AXIS)
        assert raw_sharding(mesh, spec) == NamedSharding(mesh, spec)

    def test_make_global_array_routes_through_funnel(self):
        from large_scale_recommendation_tpu.parallel.distributed import (
            make_global_array,
        )

        mesh = make_block_mesh(4)
        data = np.arange(32, dtype=np.float32).reshape(8, 4)
        arr = make_global_array(data, mesh, P(BLOCK_AXIS))
        assert arr.sharding == NamedSharding(mesh, P(BLOCK_AXIS))
        np.testing.assert_array_equal(np.asarray(arr), data)

    def test_package_is_funnel_clean(self):
        """The mechanical form of the invariant: graftlint's
        sharding-funnel rule finds nothing in the production package."""
        from tools.graftlint import run_lint

        res = run_lint(rules=["sharding-funnel"])
        assert res.findings == [], [f.path for f in res.findings]


@pytest.mark.slow
class TestTwoProcessSmoke:
    """The 2-process jax.distributed local-cluster smoke (satellite):
    subprocesses on CPU via the pod_dryrun harness function; SKIPPED
    (not failed) where the jaxlib lacks cross-process CPU collectives."""

    def test_two_process_pass(self):
        from scripts.pod_dryrun import run_two_process_pass

        out = run_two_process_pass(timeout_s=420.0)
        if out.get("skipped"):
            pytest.skip(out.get("reason", "2-process pass unsupported"))
        assert out.get("ok"), out
        assert out["n_processes"] == 2
