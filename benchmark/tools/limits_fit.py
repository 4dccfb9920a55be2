"""Read the fit cell's correctness control and faults on the chip, at the
cell's own size, on several seeds in one process (set-up is long):

    python3 benchmark/tools/limits_fit.py --workload netflix100m-r128.fit --seeds 1,2,3

The solver and the reference are the files the configuration names
(``runners/solvers/<solver>.py``, ``reference/<reference>.py``). Per seed it
makes the data, runs the reference, then puts in the program's place (a) the
program under the solver's ``bf16`` control (``factor_dtype="bfloat16"``:
the nearest precision below the float32 the configuration states, a path
the program has), (b) the reference with half of every minibatch left out, and
prints each compared number. A state left unchanged reads 1 by the measure
and needs no run. The limits in the configuration file were set from these
readings and from the runs' own (PERF.md, section 2).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--sweeps", type=int, default=None)
    args = ap.parse_args()

    from benchmark import compare, datagen, harness
    from benchmark.runners import fit as fit_runner
    from benchmark.spans import Spans

    cell = harness.resolve_cell(args.workload)
    solver = fit_runner.solver_for(cell)
    reference = harness.reference_for(cell, fit_runner.REFERENCE)
    holdout_rmse = fit_runner.holdout_rmse
    harness.start_on_chip(cell.chips)
    cfg = cell.config
    n = args.sweeps or int(cell.traffic["reference_sweeps"])
    for seed in (int(s) for s in args.seeds.split(",")):
        (u, i, r), (hu, hi, hr) = datagen.planted_ratings(
            seed, num_users=cfg["num_users"], num_items=cfg["num_items"],
            nnz=cfg["nnz"], rank=cfg["planted_rank"], noise=cfg["noise"],
            skew_lam=cfg["skew_lam"])
        ref = reference.fit(u, i, r, cfg, n)
        ref_rmse = [float(holdout_rmse(U, V, *ref["seen"], hu, hi, hr))
                    for U, V in ref["sweeps"]]

        def report(kind, tables, seen):
            rmse = [float(holdout_rmse(U, V, *seen, hu, hi, hr))
                    for U, V in tables]
            numbers = compare.fit_numbers(tables, rmse, ref, ref_rmse)
            print(kind, json.dumps({"seed": seed, "rmse": rmse,
                                    "ref_rmse": ref_rmse, **numbers}),
                  flush=True)

        stamps = fit_runner.SegmentStamps(Spans())
        model = solver.make_fit(
            cfg, n, stamps, cell.chips,
            **fit_runner.control_overrides(solver, "bf16"))(u, i, r)
        tables, seen = fit_runner.id_space(
            model, stamps.tables, cfg["num_users"], cfg["num_items"])
        report("control_bf16", tables, seen)
        del model, stamps, tables
        gc.collect()
        fault = reference.fit(u, i, r, cfg, n, fault="half_batch")
        report("fault_half_batch", fault["sweeps"], fault["seen"])
        del ref, fault, u, i, r, hu, hi, hr
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
