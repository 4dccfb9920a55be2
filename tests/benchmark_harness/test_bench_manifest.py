"""``BENCHMARK.json`` against the files it names and against its contract's
shape rules, so that a later PR that adds a cell as files finds the harness
ready for it."""

import ast
import inspect
import json
import os
import re

import pytest

import bench_testlib
from benchmark import harness, readers
from benchmark.runners import fit as fit_runner

ROOT = bench_testlib.ROOT
MANIFEST = harness.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in MANIFEST["workloads"]]
E2E = {m["name"]: m for m in MANIFEST["end_to_end"]}
LAYER = [m["name"] for m in MANIFEST["per_layer"]]
RING = "netflix100m-r128-ring4.fit"


def reports(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def test_top_level_keys_and_command():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["command"] == ["python3", "benchmark/run.py"]
    assert MANIFEST["paths"] == ["benchmark", "tests/benchmark_harness"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    budget = ((2 + 14 * 24) * (MANIFEST["run_seconds"] + 60)
              + 24 * 2 * 90 + 1200)
    assert budget <= 43200


@pytest.mark.parametrize("cell", CELLS + [RING])
def test_cell_resolves_to_files_that_exist(cell):
    c = harness.resolve_cell(cell)
    runner = harness.runner_for(c)  # runners/<kind>.py, and it has run()
    assert c.traffic["runner"] in c.config["runner_kinds"]
    if c.traffic["runner"] == "fit":
        solver = fit_runner.solver_for(c)
        for name in ("make_fit", "sizes", "sweep_flops"):
            assert callable(getattr(solver, name)), name
        assert isinstance(solver.CONTROLS, dict)
    default = getattr(inspect.getmodule(runner.run), "REFERENCE", None)
    assert default or "reference" in c.config
    reference = harness.reference_for(c, default)
    assert os.path.dirname(reference.__file__) == os.path.join(
        ROOT, "benchmark", "reference")
    assert c.config["toy"], "no toy sizes for the CPU rehearsal"
    assert c.config["limits"], "the comparison has no limit to hold"
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and len(c.per_layer) >= 1


@pytest.mark.parametrize("entry", MANIFEST["workloads"],
                         ids=lambda w: w["name"])
def test_workload_entry_shape(entry):
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert entry["name"] == f"{entry['config']}.{entry['traffic']}"
    assert entry["chips"] in (1, 4)
    assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    for key in ("name", "config", "traffic"):
        assert NAME.match(entry[key])


@pytest.mark.parametrize("entry", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_config_entry_and_file(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"].startswith("benchmark/configs/")
    cfg = harness.load_json(os.path.join(ROOT, entry["file"]))
    assert cfg["name"] == entry["name"]
    assert cfg["reduced"] == entry["reduced"]
    for key in entry["reduced"]:
        assert NAME.match(key)
        assert not re.search(r"(_dim|_rank|factors|rank)$", key)
    assert any(w["config"] == entry["name"] for w in MANIFEST["workloads"])
    assert 1 <= len(entry["source"]) <= 200
    assert 1 <= len(entry["why"]) <= 200


@pytest.mark.parametrize("name", sorted(E2E))
def test_end_to_end_metric(name):
    m = E2E[name]
    assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                      "source"}
    assert NAME.match(name) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.1
    assert all(c in CELLS for c in m.get("workloads", []))


def test_every_cell_reports_setup_and_another_metric():
    assert "workloads" not in E2E["setup_s"]
    for cell in CELLS:
        assert sum(reports(m, cell) for m in E2E.values()) >= 2


@pytest.mark.parametrize("name", LAYER)
def test_per_layer_metric_moves_a_metric_its_cells_report(name):
    m = next(x for x in MANIFEST["per_layer"] if x["name"] == name)
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert NAME.match(name) and UNIT.match(m["unit"])
    assert m["source"] in SOURCES and m["better"] in ("lower", "higher")
    assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    moved = E2E[m["moves"]]
    assert m["workloads"], "a metric nobody reports"
    for cell in m["workloads"]:
        assert cell in CELLS and reports(moved, cell)


@pytest.mark.parametrize("name", LAYER)
def test_per_layer_metric_file_agrees_and_names_a_reader(name):
    m = next(x for x in MANIFEST["per_layer"] if x["name"] == name)
    spec = harness.load_json(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".json"))
    # the cells that report it live in BENCHMARK.json alone: a later PR
    # adds a cell there and may not edit this file
    assert "workloads" not in spec
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == m[key], key
    kind = spec["reader"]["kind"]
    assert kind in readers.KINDS or kind == "python"


def test_roofline_and_mfu_names():
    for m in MANIFEST["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%" and m["better"] == "higher"
    moved_by_mfu = {m["moves"] for m in MANIFEST["per_layer"]
                    if "mfu" in m["name"].split("_")}
    moved_by_roofline = {m["moves"] for m in MANIFEST["per_layer"]
                         if m["name"].endswith("_roofline")}
    assert moved_by_roofline <= moved_by_mfu


def test_at_most_a_quarter_of_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(CELLS) // 4)


def test_every_file_under_paths_has_an_allowed_name():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in MANIFEST["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert ok.match(rel), rel


def _imports(path):
    tree = ast.parse(open(path).read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            yield node.module


def test_the_yardstick_imports_no_script_and_the_reference_no_program():
    bench = os.path.join(ROOT, "benchmark")
    for base, dirs, files in os.walk(bench):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(base, f)
            mods = list(_imports(path))
            assert not any(m.split(".")[0] in ("scripts", "bench")
                           for m in mods), path
            assert not any("obs.introspect" in m for m in mods), path
            if os.path.basename(base) == "reference" or f in (
                    "counts.py", "peaks.py", "trace_reduce.py",
                    "loadgen.py", "datagen.py", "compare.py"):
                assert not any(m.startswith("large_scale_recommendation")
                               for m in mods), path


def test_traffic_files_are_data():
    for f in os.listdir(os.path.join(ROOT, "benchmark", "traffic")):
        assert f.endswith(".json")
        json.load(open(os.path.join(ROOT, "benchmark", "traffic", f)))
