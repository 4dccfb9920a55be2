"""``scripts/setup_report.py``: the report of a cell's set-up and memory
from the planes the program has (the introspector's records, the live
tracer's seams, ``sample_device_memory``), rehearsed at the toy size."""

import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
spec = importlib.util.spec_from_file_location(
    "setup_report", os.path.join(ROOT, "scripts", "setup_report.py"))
setup_report = importlib.util.module_from_spec(spec)
spec.loader.exec_module(setup_report)


def test_records_are_summed_by_program_and_keep_the_largest_sizes():
    records = [
        {"key": "a", "module": "jit_f", "compiles": 2, "compile_wall_s": 1.5,
         "memory": {"temp_size_in_bytes": 10, "argument_size_in_bytes": 4,
                    "output_size_in_bytes": 1}},
        {"key": "b", "module": "jit_f", "compiles": 1, "compile_wall_s": 0.5,
         "memory": {"temp_size_in_bytes": 30, "argument_size_in_bytes": 2,
                    "output_size_in_bytes": 1}},
        {"key": "jit_g", "module": "jit_g", "compiles": 1,
         "compile_wall_s": 3.0, "memory": None}]
    rows = setup_report.by_program(records)
    assert [r["program"] for r in rows] == ["jit_g", "jit_f"]  # by wall
    assert rows[1] == {"program": "jit_f", "compiles": 3,
                       "compile_wall_s": 2.0, "temp_size_in_bytes": 30,
                       "argument_size_in_bytes": 4,
                       "output_size_in_bytes": 1}
    assert rows[0]["temp_size_in_bytes"] == 0


def test_seam_walls_count_complete_events_of_the_prefix():
    events = [{"ph": "X", "name": "fit/als/plan", "dur": 2e6},
              {"ph": "X", "name": "fit/als/plan", "dur": 1e6},
              {"ph": "i", "name": "fit/als/marker"},
              {"ph": "X", "name": "serving/flush", "dur": 5e6}]
    assert setup_report.seam_walls(events) == {"fit/als/plan": [2, 3.0]}


@pytest.mark.parametrize("cell,seams,programs", [
    ("syn10m1m-r512-online.ingest-replay",
     {"fit/online/source", "fit/online/prepare", "fit/online/update",
      "fit/online/stamp"}, {"jit_online_train"}),
    ("msd34m-ials-r128.fit-rank",
     {"fit/als/plan", "fit/als/init", "fit/als/segment"},
     {"jit__solve_bucket", "jit__device_plan_keys"})])
def test_report_of_a_toy_cell(cell, seams, programs, null_obs):
    """The runner runs under the live planes: the warm-up's seams and the
    programs it compiled are in the report, nothing compiles once the
    window has opened, and the planes are off again afterwards (the
    fit_rank runner turns the registry on and off itself: the report
    hands it its own)."""
    from large_scale_recommendation_tpu import obs
    from large_scale_recommendation_tpu.obs.introspect import (
        get_introspector,
    )

    import jax

    # a program that an earlier test of this process compiled at the same
    # toy shapes is not compiled again: the funnel would see none of it
    jax.clear_caches()
    doc = setup_report.report(cell, 3000000000, 2.0, off_chip=True)
    assert doc["correct"]
    assert seams <= set(doc["warmup_seams"])
    assert programs <= {r["program"] for r in doc["programs"]}
    assert doc["compile_entries"] >= len(programs)
    assert doc["compiled_inside_window"] == 0
    for when in ("start", "end"):
        sample = doc[f"memory_at_window_{when}"]
        assert sample["live_arrays"]["bytes"] > 0
        assert sample["largest_live_arrays"]
    assert get_introspector() is None and not obs.get_tracer().enabled
    setup_report.show(doc)
