"""The seams (``obs.trace.SEAMS``) reach the profiler's trace, on its
clock, with nothing enabled: one tiny flush of each serving path and one
tiny fit of each trainer run under a ``jax.profiler`` capture, which is
read back with the benchmark's own reader (``benchmark.trace_reduce
.read_xplane`` — the code the per-layer metrics stand on). One case per
seam of the table: it is there and it lies inside its caller's extent.
Then: ``obs.enable()`` puts the same names in the Chrome export; with
nothing enabled a flush and a fit record nothing anywhere; the mesh
trainer's ``evaluator.on_segment`` hook.
"""

import os
import tempfile

import numpy as np
import pytest

import jax

from benchmark import trace_reduce
from large_scale_recommendation_tpu import obs
from large_scale_recommendation_tpu.core.generators import (
    SyntheticMFGenerator,
)
from large_scale_recommendation_tpu.models.als import ALS, ALSConfig
from large_scale_recommendation_tpu.models.dsgd import DSGD, DSGDConfig
from large_scale_recommendation_tpu.obs.trace import (
    SEAMS,
    get_tracer,
    validate_chrome_trace,
)
from large_scale_recommendation_tpu.parallel import MeshDSGD, make_block_mesh
from large_scale_recommendation_tpu.parallel.dsgd_mesh import MeshDSGDConfig
from large_scale_recommendation_tpu.serving import (
    RetrievalConfig,
    ServingEngine,
)

NU, NI, RANK = 200, 150, 8
SEGMENTS = 2
# the spans the benchmark puts around the program, which the test puts
# there in its place: every seam must lie inside one of its caller's
CALLER = {"serving": "serving/flush", "fit": "fit/fit_device"}
# names the benchmark's own files emit around the program
BENCHMARK_SPANS = tuple(CALLER.values())


def _model(seed=0):
    import jax.numpy as jnp

    from large_scale_recommendation_tpu.data.blocking import flat_index
    from large_scale_recommendation_tpu.models.mf import MFModel

    rng = np.random.default_rng(seed)
    return MFModel(
        U=jnp.asarray(rng.normal(size=(NU, RANK)).astype(np.float32)),
        V=jnp.asarray(rng.normal(size=(NI, RANK)).astype(np.float32)),
        users=flat_index(np.arange(NU, dtype=np.int64)),
        items=flat_index(np.arange(NI, dtype=np.int64)))


def _ratings():
    gen = SyntheticMFGenerator(num_users=NU, num_items=NI, rank=4,
                               noise=0.05, seed=0)
    train = gen.generate(6000)
    ru, ri, rv, _ = train.to_numpy()
    return train, (ru, ri, rv)


def _solver_kw():
    return dict(num_factors=RANK, lambda_=0.01, iterations=SEGMENTS,
                learning_rate=0.05, lr_schedule="constant", seed=0,
                minibatch_size=256, init_scale=0.3)


class _Hook:
    """Stands where ``obs.quality.OnlineEvaluator`` does."""

    def __init__(self):
        self.calls = []

    def on_segment(self, U, V, label="segment", step=None):
        self.calls.append((np.asarray(U).copy(), np.asarray(V).copy(),
                           label, step))


def _stream(directory):
    """Two micro-batches of the online stream through the driver, from a
    written log."""
    from large_scale_recommendation_tpu.models.online import (
        OnlineMF,
        OnlineMFConfig,
    )
    from large_scale_recommendation_tpu.streams import (
        EventLog,
        StreamingDriver,
        StreamingDriverConfig,
    )

    _, (ru, ri, rv) = _ratings()
    log = EventLog(os.path.join(directory, "log"), fsync=False)
    log.append_arrays(0, ru[:512], ri[:512], rv[:512])
    driver = StreamingDriver(
        OnlineMF(OnlineMFConfig(num_factors=RANK)), log,
        os.path.join(directory, "ckpt"),
        config=StreamingDriverConfig(batch_records=256,
                                     checkpoint_every=None))
    assert driver.run() == 2
    log.close()


def _drive(annotate):
    """One flush of each serving path, one fit of each trainer and one
    run of the stream's driver, each inside the span its benchmark runner
    would put around it."""
    train, (ru, ri, rv) = _ratings()
    rng = np.random.default_rng(1)
    engines = [
        ServingEngine(_model(), k=5, max_batch=64,
                      retrieval=RetrievalConfig(n_clusters=None,
                                                overfetch=4)),
        ServingEngine(_model(), k=5, max_batch=64)]
    for eng in engines:
        eng.submit(rng.integers(0, NU, 100).astype(np.int64))
        with annotate(CALLER["serving"]):
            assert len(eng.flush()) == 1
    with annotate(CALLER["fit"]):
        DSGD(DSGDConfig(num_blocks=2, **_solver_kw())).fit_device(
            ru, ri, rv, NU, NI, checkpoint_every=1)
    with annotate(CALLER["fit"]):
        MeshDSGD(MeshDSGDConfig(**_solver_kw()),
                 mesh=make_block_mesh(4)).fit_device(
            ru, ri, rv, NU, NI, checkpoint_every=1)
    with annotate(CALLER["fit"]):
        ALS(ALSConfig(num_factors=RANK, lambda_=0.05,
                      iterations=1)).fit(train)
    with annotate(CALLER["fit"]):
        ALS(ALSConfig(num_factors=RANK, lambda_=0.05,
                      iterations=SEGMENTS)).fit_device(
            ru, ri, rv, NU, NI, checkpoint_every=1)
    with tempfile.TemporaryDirectory() as directory, \
            annotate(CALLER["fit"]):
        _stream(directory)


@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    """The host events of one profiler capture of ``_drive``, with the
    null obs layer installed: no ``enable`` of any kind."""
    assert not get_tracer().enabled
    trace_dir = str(tmp_path_factory.mktemp("seam_trace"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        _drive(jax.profiler.TraceAnnotation)
    finally:
        jax.profiler.stop_trace()
    events = trace_reduce.read_xplane(trace_reduce.find_xplane(trace_dir))
    return events["host"]


@pytest.mark.parametrize("seam", sorted(SEAMS))
def test_seam_reaches_the_profiler_trace(capture, seam):
    mine = [(s, s + d) for n, s, d in capture if n == seam]
    assert mine, f"{seam} is in no event of the capture"
    callers = [(s, s + d) for n, s, d in capture
               if n == CALLER[seam.split("/", 1)[0]]]
    for a, b in mine:
        assert any(c0 <= a and b <= c1 for c0, c1 in callers), (
            f"a {seam} span lies outside every {CALLER} span")


def test_no_seam_has_a_name_the_benchmark_emits(capture):
    for name in SEAMS:
        assert name.startswith(("serving/", "fit/"))  # read_xplane keeps
        assert name not in BENCHMARK_SPANS
        assert not name.startswith("bench/")
    # and the program emitted none of the benchmark's names itself: the
    # only events of those names are the ones _drive opened
    counts = {n: sum(1 for e in capture if e[0] == n)
              for n in CALLER.values()}
    assert counts == {"serving/flush": 2, "fit/fit_device": 5}


def test_every_seam_is_a_row_of_the_docs_table():
    # the code holds the names, docs/OBSERVABILITY.md the table
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "docs", "OBSERVABILITY.md")) as f:
        rows = [line for line in f if line.startswith("| `")]
    for name in SEAMS:
        assert any(f"`{name}`" in r.split("|")[1] for r in rows), name


def test_segment_seams_are_one_per_segment(capture):
    for label in ("dsgd", "mesh_dsgd"):
        for what in ("segment", "after_segment"):
            n = sum(1 for e in capture if e[0] == f"fit/{label}/{what}")
            assert n == SEGMENTS, (label, what, n)


def test_als_fit_device_opens_its_seams_in_order(capture):
    """plan (both sides, the read-backs included), init, then one segment
    and one after_segment a sweep, none overlapping: ``ALS.fit`` (host
    plans, one segment) opened the first ``fit/als/segment`` before."""
    mine = sorted((s, s + d, n) for n, s, d in capture
                  if n.startswith("fit/als/"))
    assert [n for _, _, n in mine] == (
        ["fit/als/segment", "fit/als/plan", "fit/als/init"]
        + ["fit/als/segment", "fit/als/after_segment"] * SEGMENTS)
    for (_, end, _), (start, _, _) in zip(mine, mine[1:]):
        assert end <= start


def test_als_plan_sizes_reach_the_registry(null_obs):
    registry, _ = obs.enable()
    try:
        _, (ru, ri, rv) = _ratings()
        ALS(ALSConfig(num_factors=RANK, lambda_=0.05,
                      iterations=1)).fit_device(ru, ri, rv, NU, NI)
        got = {(m["name"], m["labels"]["side"]): m["value"]
               for m in registry.snapshot()["metrics"]
               if m["name"].startswith("als_plan_")}
    finally:
        obs.disable()
    for side in ("user", "item"):
        assert got[("als_plan_ratings", side)] == len(ru)
        # every rating has a slot; the padding is what the pow2 classes add
        assert (len(ru) <= got[("als_plan_padded_slots", side)]
                < 4 * len(ru))
        assert 1 <= got[("als_plan_buckets", side)] <= got[
            ("als_plan_chunks", side)]


def test_implicit_fit_publishes_its_pad_ratio_and_its_sweeps(null_obs):
    """``als_plan_pad_ratio{side}`` (padded slots over real entries) from
    any ``fit_device``; ``als_implicit_sweeps_total`` only with
    ``implicit_alpha`` set, one a sweep whatever the segment length."""
    _, (ru, ri, rv) = _ratings()
    strength = np.abs(rv) + 1.0

    def fitted(**kw):
        registry, _ = obs.enable()
        try:
            ALS(ALSConfig(num_factors=RANK, lambda_=0.05, iterations=3,
                          **kw)).fit_device(ru, ri, strength, NU, NI,
                                            checkpoint_every=2)
            return registry.snapshot()["metrics"]
        finally:
            obs.disable()

    implicit, explicit = fitted(implicit_alpha=4.0), fitted()
    for got in (implicit, explicit):
        by = {(m["name"], m["labels"].get("side")): m.get("value")
              for m in got}
        for side in ("user", "item"):
            assert by[("als_plan_pad_ratio", side)] == pytest.approx(
                by[("als_plan_padded_slots", side)]
                / by[("als_plan_ratings", side)])
            assert 1.0 <= by[("als_plan_pad_ratio", side)] < 4.0
    assert [m["value"] for m in implicit
            if m["name"] == "als_implicit_sweeps_total"] == [3.0]
    assert not [m for m in explicit
                if m["name"] == "als_implicit_sweeps_total"]


def test_the_shared_gram_is_a_scope_of_the_implicit_half_step():
    """``als/shared_gram`` is HLO metadata inside ``_full_gram`` (a named
    scope, not a seam: ``SEAMS`` keeps host spans), and the implicit
    ``als_rounds`` runs that program twice a sweep."""
    import jax.numpy as jnp

    from large_scale_recommendation_tpu.ops import als as als_ops

    assert not any("shared_gram" in name for name in SEAMS)
    text = als_ops._full_gram.lower(jnp.ones((6, 4))).as_text(
        debug_info=True)
    assert "als/shared_gram" in text
    calls = []
    real = als_ops._full_gram
    try:
        als_ops._full_gram = lambda F: calls.append(F.shape) or real(F)
        _, (ru, ri, rv) = _ratings()
        prep_u = als_ops.device_prepare_side(ru, ri, rv, NU,
                                             rank_for_chunking=RANK)
        prep_v = als_ops.device_prepare_side(ri, ru, rv, NI,
                                             rank_for_chunking=RANK)
        V = jnp.ones((NI, RANK), jnp.float32)
        als_ops.als_rounds(V, prep_u, prep_v, NU, NI, 0.1, 2, implicit=True)
        assert calls == [(NI, RANK), (NU, RANK)] * 2
        als_ops.als_rounds(V, prep_u, prep_v, NU, NI, 0.1, 1)
        assert len(calls) == 4  # the explicit objective runs none
    finally:
        als_ops._full_gram = real


def _sweep_text(collision, with_inv, debug_info=True):
    """The lowered text of ``sgd_block_sweep`` over two minibatches of
    four ratings (debug info on: the scopes are in its locations)."""
    import jax.numpy as jnp

    from large_scale_recommendation_tpu.core.updaters import (
        RegularizedSGDUpdater,
    )
    from large_scale_recommendation_tpu.ops import sgd

    e, updater = 8, RegularizedSGDUpdater()
    rows = jnp.arange(e, dtype=jnp.int32)
    inv = (jnp.ones(e),) * 2 if with_inv else (None, None)

    def sweep(U, V, omega_u, omega_v):
        return sgd.sgd_block_sweep(
            U, V, rows, rows, jnp.ones(e), jnp.ones(e), omega_u, omega_v,
            updater, 0, 4, collision=collision, inv_cu=inv[0],
            inv_cv=inv[1])

    return jax.jit(sweep).lower(
        jnp.ones((16, 4)), jnp.ones((12, 4)), jnp.ones(16),
        jnp.ones(12)).as_text(debug_info=debug_info)


@pytest.mark.parametrize("with_inv", [False, True])
def test_the_omega_gathers_are_a_scope_nested_in_the_gathers(with_inv):
    """``sgd/gather/omega`` names the two omega gathers (a 128-lane row of
    the lane view each, since PR 39) and their lane select, and no factor
    row gather: ``sweep_omega_gather_ms`` reads them, ``sweep_gather_ms``
    (which matches ``sgd/gather`` and what is nested in it) still holds
    all four gathers."""
    text = _sweep_text("mean", with_inv)
    assert text.count('"sgd/gather/omega/gather"') == 2
    for op in ("jit(_where)", "reduce_sum"):  # the lane select, a side each
        assert text.count(f'"sgd/gather/omega/{op}"') == 2, op
    assert text.count('"sgd/gather/gather"') == 2  # the row gathers
    assert "sgd/gather/sgd/gather" not in text  # nested, not a literal


@pytest.mark.parametrize("collision,with_inv,scatter_adds", [
    ("mean", False, 2), ("mean", True, 0), ("sum", False, 0)])
def test_the_count_vectors_are_a_scope_nested_in_the_update(
        collision, with_inv, scatter_adds):
    """``sgd/update/collision_counts`` names the runtime count vectors of
    ``collision="mean"`` (two scatter-adds, two gathers back); a sweep
    that is handed ``inv_cu`` (the DSGD fits) or sums runs none of it and
    holds no such name: ``online_count_ms`` is then left out."""
    text = _sweep_text(collision, with_inv)
    counts = "sgd/update/collision_counts/"
    assert text.count(f'"{counts}scatter-add"') == scatter_adds
    assert text.count(f'"{counts}gather"') == scatter_adds
    assert (counts in text) == bool(scatter_adds)
    assert '"sgd/update/residual/' in text  # the older nested scope


def test_the_scopes_are_metadata_and_no_part_of_the_computation(monkeypatch):
    """Without its debug info the sweep lowers to the same text with the
    scopes and without them. (It is also why JAX's persistent compile
    cache, which keys a program by that text, hands back an executable
    compiled before a scope was added: docs/OBSERVABILITY.md.)"""
    import contextlib

    scoped = _sweep_text("mean", False, debug_info=False)
    assert "collision_counts" not in scoped
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _sweep_text("mean", False)
    assert "sgd/" not in bare and "scatter-add" in bare
    assert _sweep_text("mean", False, debug_info=False) == scoped


def test_the_docs_list_the_scope_the_gauge_and_the_counter():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "docs", "OBSERVABILITY.md")) as f:
        text = f.read()
    rows = [line for line in text.splitlines() if line.startswith("| `")]
    for name in ("als_plan_pad_ratio{side}", "als_implicit_sweeps_total",
                 "eval_percentile_rank{source=}"):
        assert any(f"`{name}`" in r.split("|")[1] for r in rows), name
    assert "`als/shared_gram`" in text
    for scope in ("sgd/gather/omega", "sgd/update/collision_counts"):
        assert any(f"`{scope}`" in r.split("|")[1] for r in rows), scope


def test_unknown_seam_is_refused():
    with pytest.raises(ValueError, match="not a seam"):
        get_tracer().seam("serving/flush")


def test_enabled_tracer_exports_the_same_names(null_obs):
    _, tracer = obs.enable()
    try:
        _drive(lambda name: tracer.span(name))
        events = validate_chrome_trace(tracer.chrome_trace())
    finally:
        obs.disable()
    names = {e["name"] for e in events}
    assert set(SEAMS) <= names, sorted(set(SEAMS) - names)
    # the segment seam kept the tracer's compile/execute split
    cats = [e["cat"] for e in events if e["name"] == "fit/dsgd/segment"]
    assert cats == ["compile", "execute"]


def test_nothing_enabled_records_nothing(null_obs):
    import contextlib

    tracer = get_tracer()
    assert not tracer.enabled
    _drive(lambda name: contextlib.nullcontext())
    assert tracer.events() == []
    assert null_obs.names() == set()
    assert null_obs.snapshot()["metrics"] == []


def test_mesh_evaluator_fires_once_per_segment_with_its_tables():
    _, (ru, ri, rv) = _ratings()
    hook = _Hook()
    solver = MeshDSGD(MeshDSGDConfig(**_solver_kw()),
                      mesh=make_block_mesh(4))
    assert solver.evaluator is None
    solver.evaluator = hook
    model = solver.fit_device(ru, ri, rv, NU, NI, checkpoint_every=1)
    assert [c[3] for c in hook.calls] == list(range(1, SEGMENTS + 1))
    assert {c[2] for c in hook.calls} == {"mesh_dsgd_device_segment"}
    # the last call saw the final tables, the first one other tables
    np.testing.assert_array_equal(hook.calls[-1][0], np.asarray(model.U))
    np.testing.assert_array_equal(hook.calls[-1][1], np.asarray(model.V))
    assert not np.array_equal(hook.calls[0][0], hook.calls[-1][0])
    # one segment per call of the hook, sweeps as without it: the same
    # fit with no hook gives the same tables
    plain = MeshDSGD(MeshDSGDConfig(**_solver_kw()),
                     mesh=make_block_mesh(4)).fit_device(
        ru, ri, rv, NU, NI, checkpoint_every=1)
    np.testing.assert_array_equal(np.asarray(plain.U),
                                  np.asarray(model.U))


@pytest.mark.parametrize("live", [False, True], ids=["null", "tracer"])
def test_a_seam_whose_sink_raises_still_closes_its_annotation(
        null_obs, monkeypatch, live):
    from large_scale_recommendation_tpu.obs import trace

    open_now = []

    class Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            open_now.append(self.name)

        def __exit__(self, *exc):
            open_now.remove(self.name)

    def sink(name):
        raise RuntimeError("the ledger's fault")

    monkeypatch.setattr(trace, "TraceAnnotation", Ann)
    tracer = trace.Tracer() if live else trace.NullTracer()
    with pytest.raises(RuntimeError, match="ledger's fault"):
        with tracer.seam("serving/engine/form", sink=sink):
            assert open_now == ["serving/engine/form"]
    assert open_now == []
