"""The eight per-layer metrics of PR 38 (``benchmark/layer_metrics/
scoped.py``): each reads a name the program gave its own device code, a
``jax.named_scope`` or a jitted program's name. Every such name is pinned
to the program here, so a rename fails a test and not a ledger line; the
readers run on hand-made events through the real reduction and the
manifest's wiring (``test_seam_metrics``' helpers)."""

import importlib
import inspect
import re

import pytest

import test_seam_metrics as seams
from benchmark import harness, readers
from benchmark import trace_reduce as tr
from benchmark.layer_metrics import scoped

FIT, RING, BULK = seams.FIT, seams.RING, seams.BULK
ONLINE, STREAM = seams.ONLINE, seams.STREAM
SPECS = sorted(scoped.SPECS.items())
CELLS = {
    "sweep_omega_gather_ms": [FIT, RING],
    "online_count_ms": [STREAM],
    "blocking_counts_s": [FIT, RING],
    "blocking_permutation_s": [FIT, RING],
    "blocking_assign_s": [FIT, RING],
    "blocking_sort_s": [FIT, RING],
    "mesh_place_s": [RING],
    "stage1_topk_ms": [BULK, ONLINE],
}


def test_the_eight_metrics_and_their_cells():
    by_name = {m["name"]: m for m in harness.load_manifest()["per_layer"]}
    assert sorted(scoped.SPECS) == sorted(CELLS)
    for name, cells in CELLS.items():
        assert by_name[name]["workloads"] == cells
        assert by_name[name]["source"] == "device_trace"
        assert by_name[name]["better"] == "lower"
        spec = harness.load_json(f"{seams.ROOT}/benchmark/layer_metrics/"
                                 f"{name}.json")
        assert spec["reader"] == {"kind": "python", "file": name + ".py"}
        assert spec["holds"]
    # appended, in the issue's order, after the 41 the benchmark had
    names = [m["name"] for m in harness.load_manifest()["per_layer"]]
    assert names[41:49] == list(scoped.SPECS)


def _composed_of(scope: str, named: set) -> bool:
    """``scope`` is an argument of ``named_scope``, or arguments nested
    (``a/b`` opened as ``b`` inside ``a``)."""
    if scope in named:
        return True
    return any(scope[:i] in named and _composed_of(scope[i + 1:], named)
               for i, ch in enumerate(scope) if ch == "/")


def _jitted(name, spec):
    if "of_jax" in spec:
        return getattr(importlib.import_module(spec["of_jax"]), name)
    return seams._jitted(name)


@pytest.mark.parametrize("metric,spec", SPECS, ids=[n for n, _ in SPECS])
def test_every_name_a_spec_reads_is_one_the_program_gives(metric, spec):
    """Each program is jitted under the name the trace calls it by; each
    scope is the argument, or the nesting, of ``jax.named_scope``s in a
    module that holds one of the spec's programs."""
    assert spec["kind"] in ("scope_time", "program_time")
    for name in spec["programs"]:
        fn = _jitted(name, spec)
        assert fn.__name__ == name
        assert hasattr(fn, "lower"), f"{name} is not jitted"
        assert tr.program_name(f"jit_{name}(5)") == name
    if spec["kind"] == "program_time":
        assert "scopes" not in spec
        return
    holders = [m for m in seams._program_modules()
               if any(hasattr(m, p) for p in spec["programs"])]
    named = {s for m in holders for s in re.findall(
        r'named_scope\(\s*"([^"]+)"', inspect.getsource(m))}
    for scope in spec["scopes"]:
        assert _composed_of(scope, named), scope


def test_placement_on_a_mesh_runs_the_program_the_spec_names(monkeypatch):
    """``mesh_place_s`` reads ``_multi_slice``: what the installed JAX
    runs when ``Partitioner.place`` shards an array that lies on one
    device (a JAX that places otherwise fails here, and leaves the metric
    out of the line)."""
    import jax.numpy as jnp
    from jax._src.array import ArrayImpl

    from large_scale_recommendation_tpu.parallel import Partitioner

    slicer = _jitted("_multi_slice", scoped.SPECS["mesh_place_s"])
    calls = []
    monkeypatch.setattr(
        ArrayImpl, "_multi_slice",
        lambda self, *a: calls.append(len(a[0])) or slicer(self, *a))
    placed = Partitioner(num_devices=4).place(
        jnp.arange(24 * 7.0).reshape(24, 7), "users", None)
    assert len(placed.addressable_shards) == 4
    assert calls == [4]  # one program, a slice a chip


# -- the readers on hand-made events ------------------------------------------

SGD_OPS = [("gather.1", 0, 4, "sgd/gather"),
           ("gather.2", 4, 7, "sgd/gather/omega"),
           ("gather.3", 7, 10, "sgd/gather/omega"),
           ("sub.4", 10, 12, "sgd/update/residual"),
           ("scatter.5", 12, 14, "sgd/update/collision_counts"),
           ("divide.6", 14, 15, "sgd/update/collision_counts"),
           ("scatter.7", 15, 30, "sgd/scatter_u"),
           ("scatter.8", 30, 48, "sgd/scatter_v"), ("copy.9", 48, 50, "-")]
BUCKET_OPS = [("fusion.1", 0, 14, "bucket/assign"),
              ("sort.2", 14, 30, "bucket/permutation"),
              ("rng.3", 30, 32, "bucket/permutation/shuffle"),
              ("sort.4", 32, 40, "bucket/sort"),
              ("fusion.5", 40, 41, "bucket/sizes")]
STAGE1_OPS = seams.STAGE1_OPS + [("topk.5", 45, 48, "stage1/top_k")]
PLAIN = [("fusion.1", 0, 25, "-"), ("fusion.2", 25, 50, "-")]


@pytest.mark.parametrize("cell,program,spans_,ops,expected", [
    (FIT, "dsgd_train", [], SGD_OPS, {"sweep_omega_gather_ms": 6.0}),
    (RING, "run", [], SGD_OPS, {"sweep_omega_gather_ms": 6.0}),
    (STREAM, "online_train", [], SGD_OPS, {"online_count_ms": 3.0}),
    (FIT, "_bucket_entries", [], BUCKET_OPS,
     {"blocking_assign_s": 0.028, "blocking_permutation_s": 0.036,
      "blocking_sort_s": 0.016}),
    (RING, "_bucket_entries", [], BUCKET_OPS,
     {"blocking_assign_s": 0.028, "blocking_permutation_s": 0.036,
      "blocking_sort_s": 0.016}),
    (FIT, "_weighted_counts", [], PLAIN, {"blocking_counts_s": 0.100}),
    (RING, "_weighted_counts", [], PLAIN, {"blocking_counts_s": 0.100}),
    (RING, "_multi_slice", [], PLAIN, {"mesh_place_s": 0.100}),
    (FIT, "_multi_slice", [], PLAIN, {}),  # the ring's metric alone
    (BULK, "_stage1_flat", ["serving/flush"], STAGE1_OPS,
     {"stage1_topk_ms": 8.0}),
    (ONLINE, "_stage1_flat", ["serving/flush"], STAGE1_OPS,
     {"stage1_topk_ms": 8.0})])
def test_scoped_metric_on_hand_made_events(cell, program, spans_, ops,
                                           expected):
    """Two runs of the program: a time a run or a flush is one run's, a
    total both runs'. A scope takes what is nested in it along."""
    got = seams.values(cell, seams.scoped(program, spans_, ops))
    assert {k: v for k, v in got.items() if k in scoped.SPECS} == {
        k: pytest.approx(v) for k, v in expected.items()}


@pytest.mark.parametrize("cell,program,outer,inner", [
    (FIT, "dsgd_train", "sweep_gather_ms", "sweep_omega_gather_ms"),
    (RING, "run", "sweep_gather_ms", "sweep_omega_gather_ms"),
    (STREAM, "online_train", "online_sgd_update_ms", "online_count_ms")])
def test_the_enclosing_scope_metric_keeps_what_is_nested_in_it(
        cell, program, outer, inner):
    """The scopes are nested, not siblings: the metric the benchmark had
    reads what it read, and the new one is a part of it."""
    got = seams.values(cell, seams.scoped(program, [], SGD_OPS))
    assert got[outer] == pytest.approx(
        {"sweep_gather_ms": 10.0, "online_sgd_update_ms": 5.0}[outer])
    assert 0 < got[inner] < got[outer]


@pytest.mark.parametrize("metric,spec", SPECS, ids=[n for n, _ in SPECS])
def test_a_reader_that_finds_nothing_returns_none_and_never_zero(metric,
                                                                 spec):
    """No trace; a trace without the program; and, for a scope, the
    program from before the scope (the parent commit under this PR's
    benchmark files)."""
    metric_file = harness.load_json(
        f"{seams.ROOT}/benchmark/layer_metrics/{metric}.json")
    ctx = seams.scoped(spec["programs"][0], ["serving/flush"], PLAIN)
    if spec["kind"] == "scope_time":
        assert readers.read(metric_file, ctx) is None
    else:
        assert readers.read(metric_file, ctx) == pytest.approx(0.100)
    elsewhere = seams.scoped("another_program", ["serving/flush"], PLAIN)
    assert readers.read(metric_file, elsewhere) is None
    assert readers.read(metric_file, dict(ctx, trace=None)) is None


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(dsgd_train)/while/body/closed_call/while/body/closed_call/"
     "sgd/gather/omega/gather:", "sgd/gather/omega"),
    ("jit(dsgd_train)/while/body/closed_call/sgd/gather/gather:",
     "sgd/gather"),
    ("jit(online_train)/while/body/closed_call/sgd/update/"
     "collision_counts/scatter-add:", "sgd/update/collision_counts"),
    ("jit(run)/shard_map/while/body/closed_call/sgd/gather/omega/"
     "gather:", "sgd/gather/omega")])
def test_scope_of_reads_the_nested_scope_inside_the_minibatch_scan(tf_op,
                                                                   scope):
    assert tr.scope_of(tf_op) == scope
