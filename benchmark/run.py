"""The benchmark's command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Resolves the cell to ``benchmark/configs/<config>.json`` and
``benchmark/traffic/<traffic>.json`` by the names in ``BENCHMARK.json``; the
traffic file names the runner kind, which is ``benchmark/runners/<kind>.py``
(``README.md`` has the contract of its ``run``). Exits non-zero, printing no
result, when the kind has no such file, when JAX finds no TPU or fewer chips
than the cell asks for, or when the program is not beside it. The last
line of standard output is the result.
``--control`` (not for the driver) puts the cell's low-precision control in
the program's place, to show that the comparison fails it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# what a runner kind's run(cell, seed, seconds, trace, device, control=None)
# returns (README.md, "A runner kind", says what each holds)
RESULT_KEYS = {"correct", "compared", "attempted", "failed", "fatal",
               "values", "ctx", "memory_peak_bytes", "reduced",
               "compiles_in_window", "notes"}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, control: str | None = None,
             cell=None) -> tuple[str, dict]:
    """One run of one cell: ``(result line, everything collected)``.
    ``cell`` (tests): a ``harness.Cell`` built by hand, at a toy size."""
    from benchmark import compare, harness

    cell = cell or harness.resolve_cell(workload)
    runner = harness.runner_for(cell)
    device = (harness.start_on_chip(cell.chips) if require_tpu
              else harness.device_summary())
    out = runner.run(cell, seed, seconds, trace, device, control=control)
    missing = RESULT_KEYS - set(out)
    if missing:
        raise SystemExit(f"benchmark: {runner.__file__}: run() returned no "
                         f"{sorted(missing)}")
    if out["compiles_in_window"]:
        print(f"benchmark: {out['compiles_in_window']} program(s) were "
              "lowered inside the measured window: the run is not correct",
              file=sys.stderr)
    if out["fatal"]:
        print(f"benchmark: {out['fatal']}", file=sys.stderr)
        raise SystemExit(1)
    device = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    breakdown = None
    if trace:
        metrics = harness.layer_metrics(cell, out["ctx"])
        reduced = out["reduced"]
        if reduced:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
            out["notes"]["device_programs_s"] = dict(sorted(
                reduced["program_s"].items(), key=lambda kv: -kv[1])[:10])
    else:
        metrics = {m["name"]: {"value": float(out["values"][m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    notes = dict(out["notes"], end_to_end=out["values"])
    line = harness.result_line(
        correct=out["correct"], attempted=out["attempted"],
        failed=out["failed"], metrics=metrics, device=device,
        compared=out["compared"], breakdown=breakdown, notes=notes)
    compare.print_compared(out["compared"], out["correct"])
    return line, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None)
    args = ap.parse_args(argv)
    line, _ = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), control=args.control)
    json.loads(line)
    sys.stderr.flush()
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
