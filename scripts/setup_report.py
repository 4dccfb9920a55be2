#!/usr/bin/env python3
"""What a benchmark cell's set-up and memory are made of, by the planes
the program already has (no metric: a per-layer metric can only read what
a runner puts in ``ctx``, and the trace covers the window alone):

    python3 scripts/setup_report.py <cell> --seed <n> [--seconds 51]

``obs.enable()``, ``obs.enable_introspection(start=False)``, then the
cell's runner in this process, untraced. Prints, and writes as JSON under
``chiprun_out/setup_report/``:

(i)   by program, from ``Introspector.records()``: how often the compile
      funnel was entered for it (a persistent-cache read is an entry too),
      the wall inside it, and the executable's ``temp_size_in_bytes``,
      ``argument_size_in_bytes`` and ``output_size_in_bytes``;
(ii)  the walls of the ``fit/...`` seams that closed before the window
      opened (the warm-up fit), from the live tracer;
(iii) ``sample_device_memory(publish=False)`` at the window's start and
      end, with the largest live arrays by shape.

Needs the chip (``benchmark/run.py``'s guard); ``--off-chip`` rehearses
at the cell's toy size on the CPU. The live tracer and registry make the
program stamp what it otherwise skips (the stream blocks on its tables
for its gauges): the window's rate is not the benchmark's.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT_DIR = os.path.join(ROOT, "chiprun_out", "setup_report")
MEMORY_FIELDS = ("temp_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes")


def by_program(records: list[dict]) -> list[dict]:
    """The introspector's records (one a compile key and module) summed by
    module; sizes are the largest executable's of that name (a program
    compiled at several shapes has several)."""
    out: dict[str, dict] = {}
    for rec in records:
        row = out.setdefault(rec["module"], {
            "program": rec["module"], "compiles": 0, "compile_wall_s": 0.0,
            **{f: 0 for f in MEMORY_FIELDS}})
        row["compiles"] += rec["compiles"]
        row["compile_wall_s"] += rec["compile_wall_s"]
        for f in MEMORY_FIELDS:
            row[f] = max(row[f], (rec["memory"] or {}).get(f, 0))
    return sorted(out.values(), key=lambda r: -r["compile_wall_s"])


def seam_walls(events: list[dict], prefix: str = "fit/") -> dict:
    """``{seam: [count, seconds]}`` over the tracer's complete events."""
    out: dict[str, list] = {}
    for e in events:
        if e.get("ph") == "X" and e["name"].startswith(prefix):
            row = out.setdefault(e["name"], [0, 0.0])
            row[0] += 1
            row[1] += e["dur"] / 1e6
    return out


def memory_sample(introspector, top: int = 12) -> dict:
    import jax

    sample = introspector.sample_device_memory(publish=False)
    shapes: dict[tuple, list] = {}
    for arr in jax.live_arrays():
        row = shapes.setdefault((str(arr.dtype), tuple(arr.shape)), [0, 0])
        row[0] += 1
        row[1] += int(arr.nbytes)
    largest = sorted(shapes.items(), key=lambda kv: -kv[1][1])[:top]
    sample["largest_live_arrays"] = [
        {"dtype": dt, "shape": list(shape), "count": n, "bytes": b}
        for (dt, shape), (n, b) in largest]
    return sample


def report(cell_name: str, seed: int, seconds: float,
           off_chip: bool = False) -> dict:
    from benchmark import harness
    from benchmark.run import run_cell
    from large_scale_recommendation_tpu import obs

    t_start = time.perf_counter()
    registry, tracer = obs.enable()
    introspector = obs.enable_introspection(start=False)
    if not introspector.installed:
        raise SystemExit("setup_report: the compile funnel could not be "
                         "hooked on this JAX (obs/introspect.py::install)")
    # a runner that turns the program's registry on for its warm-up fit
    # (fit_rank) gets this one, so the warm-up's seams stay readable
    enable = obs.enable
    obs.enable = lambda r=None, t=None: enable(r or registry, t or tracer)
    at_window: dict = {}
    measure = harness.Window.measure

    @contextlib.contextmanager
    def sampled(self):
        at_window["events"] = len(tracer.events())
        at_window["records"] = introspector.records()
        at_window["entries"] = introspector.compile_count
        at_window["setup_wall_s"] = time.perf_counter() - t_start
        at_window["start"] = memory_sample(introspector)
        with measure(self):
            yield self
        at_window["compiled_inside"] = (introspector.compile_count
                                        - at_window["entries"])
        at_window["end"] = memory_sample(introspector)

    harness.Window.measure = sampled
    try:
        cell = None
        if off_chip:
            sys.path.insert(0, os.path.join(ROOT, "tests",
                                            "benchmark_harness"))
            import bench_testlib

            cell = bench_testlib.toy_cell(cell_name)
        line, out = run_cell(cell_name, seed, seconds, False,
                             require_tpu=not off_chip, cell=cell)
    finally:
        harness.Window.measure = measure
        obs.enable = enable
    result = json.loads(line)
    doc = {
        "cell": cell_name, "seed": seed,
        "cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
        "correct": result["correct"],
        "end_to_end": result["notes"]["end_to_end"],
        "memory_peak_bytes": result["device"]["memory_peak_bytes"],
        "setup_wall_in_process_s": at_window["setup_wall_s"],
        "compile_entries": at_window["entries"],
        "compile_wall_s": sum(r["compile_wall_s"]
                              for r in at_window["records"]),
        "programs": by_program(at_window["records"]),
        "compiled_inside_window": at_window["compiled_inside"],
        "warmup_seams": seam_walls(tracer.events()[:at_window["events"]]),
        "memory_at_window_start": at_window["start"],
        "memory_at_window_end": at_window["end"],
    }
    obs.disable()
    return doc


def show(doc: dict) -> None:
    gb = 1e9
    print(f"== {doc['cell']} seed {doc['seed']} cache {doc['cache_dir']}: "
          f"setup_s {doc['end_to_end'].get('setup_s')}, in this process "
          f"{doc['setup_wall_in_process_s']:.2f} s to the window; "
          f"{doc['compile_entries']} entries of the compile funnel, "
          f"{doc['compile_wall_s']:.2f} s inside it; peak "
          f"{(doc['memory_peak_bytes'] or 0) / gb:.3f} GB")
    print("-- (i) by program: entries, wall s, temp / argument / output GB")
    for r in doc["programs"][:40]:  # the JSON has them all
        print(f"{r['program']:<42} {r['compiles']:>3} "
              f"{r['compile_wall_s']:>8.2f} "
              + " ".join(f"{r[f] / gb:>7.3f}" for f in MEMORY_FIELDS))
    print("-- (ii) seams closed before the window: count, wall s")
    for name, (n, wall) in sorted(doc["warmup_seams"].items(),
                                  key=lambda kv: -kv[1][1]):
        print(f"{name:<42} {n:>5} {wall:>9.3f}")
    for when in ("start", "end"):
        sample = doc[f"memory_at_window_{when}"]
        print(f"-- (iii) memory at the window's {when}: live arrays "
              f"{sample['live_arrays']['bytes'] / gb:.3f} GB in "
              f"{sample['live_arrays']['count']}")
        for d in sample["devices"]:
            stats = d["stats"] or {}
            print(f"   {d['device']}: in use "
                  f"{stats.get('bytes_in_use', 0) / gb:.3f} GB, peak "
                  f"{stats.get('peak_bytes_in_use', 0) / gb:.3f} GB")
        for a in sample["largest_live_arrays"]:
            print(f"   {a['dtype']}{a['shape']} x {a['count']}: "
                  f"{a['bytes'] / gb:.3f} GB")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("cell")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--off-chip", action="store_true")
    ap.add_argument("--tag", default="", help="suffix of the JSON's name")
    args = ap.parse_args(argv)
    doc = report(args.cell, args.seed, args.seconds, args.off_chip)
    show(doc)
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, f"{args.cell}{args.tag}.json")
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"setup_report: wrote {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
