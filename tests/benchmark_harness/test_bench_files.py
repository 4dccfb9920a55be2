"""A runner kind, a solver and a plain reference join the harness as files:
``run.py`` finds ``runners/<kind>.py``, the ``fit`` kind finds
``runners/solvers/<solver>.py`` and ``reference/<reference>.py``, by the
names the data files give, and a name with no file ends the run before any
timing. The files a later PR would add are written beside a copy of the
tree here (``bench_testlib.tree_with``); no file of the tree is edited.
This is ``benchmark/README.md``'s second worked example, rehearsed."""

import copy
import inspect
import json
import os

import pytest

import bench_testlib
from bench_testlib import run_toy
from benchmark import counts, harness, readers
from benchmark.peaks import load_peaks
from benchmark.run import RESULT_KEYS, run_cell
from benchmark.runners import fit as fit_runner

ROOT = bench_testlib.ROOT
FIT = "netflix100m-r128.fit"
RING = "netflix100m-r128-ring4.fit"
BULK = "syn10m1m-r512.serve-bulk"
ONLINE = "syn10m1m-r512.serve-online"

# -- a configuration of another solver: full-batch gradient descent -----------

GD_SOLVER = '''
"""Solver ``gd``: full-batch gradient steps, one a segment."""
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np

CONTROLS = {"bf16": {"dtype": "bfloat16"}}


@functools.partial(jax.jit, static_argnames=("nu", "ni", "dtype"))
def step(U, V, u, i, r, lr, *, nu, ni, dtype):
    e = r - jnp.sum(U[u] * V[i], axis=-1)
    cu = jnp.zeros(nu).at[u].add(1.0)
    ci = jnp.zeros(ni).at[i].add(1.0)
    gu = jnp.zeros_like(U).at[u].add(e[:, None] * V[i])
    gi = jnp.zeros_like(V).at[i].add(e[:, None] * U[u])
    U = U + lr * gu / jnp.maximum(cu, 1.0)[:, None]
    V = V + lr * gi / jnp.maximum(ci, 1.0)[:, None]
    return (U.astype(dtype).astype(jnp.float32),
            V.astype(dtype).astype(jnp.float32))


def make_fit(cfg, iterations, stamps, chips, dtype="float32"):
    nu, ni, rank = cfg["num_users"], cfg["num_items"], cfg["num_factors"]
    lr = jnp.float32(cfg["learning_rate"])

    def index(n):
        return types.SimpleNamespace(sorted_ids=np.arange(n),
                                     sorted_rows=np.arange(n))

    def fit(u, i, r):
        U = jnp.full((nu, rank), cfg["init_scale"], jnp.float32)
        V = jnp.full((ni, rank), cfg["init_scale"], jnp.float32)
        for _ in range(iterations):
            U, V = step(U, V, u, i, r, lr, nu=nu, ni=ni, dtype=dtype)
            stamps.on_segment(U, V)
        return types.SimpleNamespace(U=U, V=V, users=index(nu),
                                     items=index(ni))

    return fit


def sizes(cfg):
    return {}


def sweep_flops(sizes):
    return 8 * sizes["rank"] * sizes["nnz_train"]
'''

GD_REFERENCE = '''
"""Plain reference of the ``gd`` solver, in numpy."""
import numpy as np


def fit(u, i, r, cfg, sweeps, *, fault=None):
    u, i, r = (np.asarray(a) for a in (u, i, r))
    nu, ni, rank = cfg["num_users"], cfg["num_items"], cfg["num_factors"]
    U = np.full((nu, rank), cfg["init_scale"], np.float32)
    V = np.full((ni, rank), cfg["init_scale"], np.float32)
    cu = np.maximum(np.bincount(u, minlength=nu), 1)[:, None]
    ci = np.maximum(np.bincount(i, minlength=ni), 1)[:, None]
    out = {"init": (U.copy(), V.copy()), "sweeps": [],
           "seen": (np.bincount(u, minlength=nu) > 0,
                    np.bincount(i, minlength=ni) > 0),
           "notes": {"reference": "gd_ref"}}
    lr = np.float32(cfg["learning_rate"])
    for _ in range(sweeps):
        e = r - np.sum(U[u] * V[i], axis=-1)
        gu, gi = np.zeros_like(U), np.zeros_like(V)
        np.add.at(gu, u, e[:, None] * V[i])
        np.add.at(gi, i, e[:, None] * U[u])
        U, V = U + lr * gu / cu, V + lr * gi / ci
        out["sweeps"].append((U.astype(np.float32), V.astype(np.float32)))
    return out
'''

GD_CONFIG = {
    "name": "toy-gd", "solver": "gd", "reference": "gd_ref", "chips": 1,
    "runner_kinds": ["fit"], "num_users": 300, "num_items": 120,
    "nnz": 20000, "num_factors": 8, "learning_rate": 0.5,
    "init_scale": 0.3, "planted_rank": 4, "noise": 0.1, "skew_lam": 2.0,
    "target_rmse": 10.0, "toy": {}, "reduced": [],
    "limits": {"loss_gap": 1e-3, "first_update_gap": 1e-3,
               "update_gap": 1e-3, "table_diff": 1e-3}}
GD_METRIC = {
    "name": "gd_steps_done", "layer": "whole step, training", "unit": "steps",
    "better": "higher", "source": "program_counter",
    "moves": "train_ratings_per_s",
    "reader": {"kind": "python", "file": "gd_steps_done.py"}}
GD_READER = '''
def read(ctx):
    return ctx["counters"].get("sweeps_done")
'''
GD_CELL = "toy-gd.fit"

# -- a runner kind of its own -------------------------------------------------

ROWSUM_RUNNER = '''
"""Runner kind ``rowsum``: row sums of a table from the seed, back to back."""
import time

import numpy as np

from benchmark import compare, harness


def run(cell, seed, seconds, trace, device, control=None):
    import jax

    cfg = cell.config
    reference = harness.reference_for(cell, "rowsum_ref")
    x = jax.random.normal(jax.random.PRNGKey(seed % 2**31),
                          (cfg["rows"], cfg["width"]))
    step = jax.jit(lambda x: x.sum(axis=1))
    got = step(x).block_until_ready()
    window = harness.Window(trace, harness.trace_dir_for(cell.name),
                            strict=device["platform"] == "tpu")
    calls = 0
    with window.measure():
        while time.perf_counter() - window.t0 < seconds:
            got = step(x).block_until_ready()
            calls += 1
    numbers = {"sum_gap": float(np.max(np.abs(
        np.asarray(got) - reference.row_sums(np.asarray(x)))))}
    correct, compared = compare.judge(numbers, cfg["limits"])
    return {"correct": correct and window.compiles.count == 0,
            "compared": compared, "attempted": calls, "failed": 0,
            "fatal": None,
            "values": {"rows_per_s": calls * cfg["rows"] / window.wall,
                       "setup_s": window.setup_s},
            "ctx": {"trace": None, "series": {}, "counters": {},
                    "sizes": {}, "peaks": None, "chips": cell.chips,
                    "window_s": window.wall},
            "memory_peak_bytes": harness.memory_peak_bytes(),
            "reduced": None, "compiles_in_window": window.compiles.count,
            "notes": {"calls": calls}}
'''
ROWSUM_REFERENCE = '''
import numpy as np


def row_sums(x):
    return np.sum(x.astype(np.float64), axis=1)
'''
ROWSUM_CONFIG = {"name": "toy-rows", "chips": 1, "runner_kinds": ["rowsum"],
                 "rows": 512, "width": 64, "toy": {}, "reduced": [],
                 "limits": {"sum_gap": 1e-4}}
ROWSUM_CELL = "toy-rows.steady"


def new_entries(manifest):
    """What the two PRs would add to ``BENCHMARK.json``: entries only."""
    manifest["configs"] += [
        {"name": "toy-gd", "source": "a test", "reduced": [], "why": "-",
         "file": "benchmark/configs/toy-gd.json"},
        {"name": "toy-rows", "source": "a test", "reduced": [], "why": "-",
         "file": "benchmark/configs/toy-rows.json"}]
    manifest["workloads"] += [
        {"name": GD_CELL, "config": "toy-gd", "traffic": "fit", "chips": 1,
         "why": "-"},
        {"name": ROWSUM_CELL, "config": "toy-rows", "traffic": "steady",
         "chips": 1, "why": "-"}]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if FIT in m.get("workloads", []) and m["name"] != "blocking_s":
            m["workloads"].append(GD_CELL)
    manifest["end_to_end"].append(
        {"name": "rows_per_s", "unit": "rows/s", "better": "higher",
         "bound": 0.01, "source": "host_clock", "workloads": [ROWSUM_CELL]})
    manifest["per_layer"].append(
        {k: v for k, v in GD_METRIC.items() if k != "reader"}
        | {"workloads": [GD_CELL]})


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return bench_testlib.tree_with(tmp_path_factory.mktemp("tree"), {
        "runners/solvers/gd.py": GD_SOLVER,
        "reference/gd_ref.py": GD_REFERENCE,
        "configs/toy-gd.json": GD_CONFIG,
        "layer_metrics/gd_steps_done.json": GD_METRIC,
        "layer_metrics/gd_steps_done.py": GD_READER,
        "runners/rowsum.py": ROWSUM_RUNNER,
        "reference/rowsum_ref.py": ROWSUM_REFERENCE,
        "configs/toy-rows.json": ROWSUM_CONFIG,
        "traffic/steady.json": {"runner": "rowsum"},
    }, new_entries)


def test_a_solver_and_a_reference_added_as_files_run_through_fit(tree):
    line, out = run_toy(GD_CELL, root=tree)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"time_to_target_s",
                                    "train_ratings_per_s", "setup_s"}
    assert set(line["compared"]) == set(GD_CONFIG["limits"])
    assert set(out) == RESULT_KEYS
    # the reference's own note is on the line, the solver's own sizes and
    # its own count of a sweep's work are what the readers get
    assert line["notes"]["reference"] == "gd_ref"
    sizes = out["ctx"]["sizes"]
    assert "num_blocks" not in sizes
    assert out["ctx"]["sweep_flops"] == 8 * 8 * sizes["nnz_train"]
    assert out["ctx"]["sweep_flops"] != counts.sweep_flops(
        sizes["nnz_train"], sizes["rank"])


def test_the_added_solvers_control_is_found_in_its_file_and_fails(tree):
    line, _ = run_toy(GD_CELL, root=tree, control="bf16")
    assert line["correct"] is False
    with pytest.raises(SystemExit, match="has no control 'int8'"):
        run_toy(GD_CELL, root=tree, control="int8")


def test_the_added_metrics_own_reader_is_read_from_the_cells_tree(tree):
    line, _ = run_toy(GD_CELL, root=tree, trace=True)
    assert line["metrics"]["gd_steps_done"] == {"value": 4.0,
                                                "unit": "steps"}
    assert line["metrics"]["sweeps_to_target"]["value"] == 1.0


def test_a_runner_kind_added_as_a_file_runs_through_run_cell(tree):
    line, out = run_toy(ROWSUM_CELL, root=tree, seconds=0.2)
    assert line["correct"] is True and line["attempted"] >= 1
    assert set(line["metrics"]) == {"rows_per_s", "setup_s"}
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared" and set(out) == RESULT_KEYS
    json.dumps(line)


def test_a_runner_that_returns_less_than_the_contract_is_refused(tree):
    path = os.path.join(tree, "benchmark", "runners", "short.py")
    with open(path, "w") as f:
        f.write("def run(cell, seed, seconds, trace, device, control=None):"
                "\n    return {'correct': True}\n")
    cell = bench_testlib.toy_cell(ROWSUM_CELL, tree)
    cell.traffic["runner"] = "short"
    with pytest.raises(SystemExit, match="short.py: run.. returned no "):
        run_cell(cell.name, 1, 0.1, False, require_tpu=False, cell=cell)


def _never(*a, **k):
    raise AssertionError("the run went on past the missing file")


@pytest.mark.parametrize("cell,where,key,missing", [
    (FIT, "traffic", "runner", "benchmark/runners/nope.py"),
    (FIT, "config", "solver", "benchmark/runners/solvers/nope.py"),
    (RING, "config", "reference", "benchmark/reference/nope.py"),
    (BULK, "config", "reference", "benchmark/reference/nope.py"),
])
def test_a_name_with_no_file_ends_the_run_naming_the_file(
        monkeypatch, cell, where, key, missing):
    from benchmark import datagen

    # the run ends before any input is made, so before the window; a
    # missing kind even before the look for a chip (require_tpu is on)
    monkeypatch.setattr(datagen, "planted_ratings", _never)
    monkeypatch.setattr(datagen, "serving_factors", _never)
    c = bench_testlib.toy_cell(cell)
    getattr(c, where)[key] = "nope"
    with pytest.raises(SystemExit) as e:
        run_cell(c.name, 1, 1.0, False, require_tpu=(key == "runner"),
                 cell=c)
    assert os.path.join(ROOT, missing) in str(e.value)
    assert e.value.code not in (0, None)


@pytest.mark.parametrize("cell,runner,solver,reference", [
    (FIT, "fit.py", "dsgd.py", "dsgd_ref.py"),
    (RING, "fit.py", "mesh_dsgd.py", "dsgd_ref.py"),
    (BULK, "serve.py", None, "topk_ref.py"),
    (ONLINE, "serve.py", None, "topk_ref.py"),
])
def test_each_lookup_of_the_four_cells_returns_the_file_it_did(
        cell, runner, solver, reference):
    c = harness.resolve_cell(cell)
    run = harness.runner_for(c).run
    bench = os.path.join(ROOT, "benchmark")
    assert inspect.getsourcefile(run) == os.path.join(
        bench, "runners", runner)
    module = inspect.getmodule(run)
    assert module.__name__ == "benchmark.runners." + runner[:-3]
    assert harness.reference_for(c, module.REFERENCE).__file__ == (
        os.path.join(bench, "reference", reference))
    assert "reference" not in c.config  # the kind's own default
    if solver:
        got = fit_runner.solver_for(c)
        assert got.__file__ == os.path.join(bench, "runners", "solvers",
                                            solver)
        assert got.__name__ == "benchmark.runners.solvers." + solver[:-3]
        assert got.sizes(c.config) == {"num_blocks": c.config["num_blocks"]}
        assert got.CONTROLS == {"bf16": {"factor_dtype": "bfloat16"}}


@pytest.mark.parametrize("cell", [FIT, RING])
def test_train_step_mfu_through_the_solvers_count_is_counts_sweep_flops(
        cell):
    _, out = run_toy(cell)
    ctx = copy.copy(out["ctx"])
    sizes = ctx["sizes"]
    assert ctx["sweep_flops"] == counts.sweep_flops(sizes["nnz_train"],
                                                    sizes["rank"])
    # the arithmetic alone, at a v5e's peak: a share of a chip's peak is
    # never reported from a CPU run (peaks is None there, the reader too)
    spec = harness.load_json(os.path.join(
        ROOT, "benchmark", "layer_metrics", "train_step_mfu.json"))
    assert readers.read(spec, ctx) is None
    ctx["peaks"] = load_peaks("TPU v5 lite")
    assert readers.read(spec, ctx) == (
        100.0 * 4 * counts.sweep_flops(sizes["nnz_train"], sizes["rank"])
        / (ctx["window_s"] * ctx["chips"] * 197e12))
