"""Read a fit cell's own numbers on the chip on many seeds in one process
(a run of the cell pays its set-up, its warm-up fit and its window for
every seed; this pays the data, one fit and the reference):

    python3 benchmark/tools/readings_fit.py --workload netflix100m-als-r128.fit --seeds 1,2,3

Per seed it makes the data, runs the program through the cell's solver file
for the traffic file's sweeps, scores every sweep on the holdout, runs the
reference and prints each compared number beside its limit, the holdout
RMSE of every sweep and the sweep that first meets the target. Readings of
correctness and of the target's room only: the fit compiles as it goes, so
nothing here is a time. The target and the limits in a configuration file
are set from these lines and from the runs' own (PERF.md, sections 2 and 4).
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
    args = ap.parse_args()

    from benchmark import compare, datagen, harness
    from benchmark.runners import fit as fit_runner
    from benchmark.spans import Spans

    cell = harness.resolve_cell(args.workload)
    solver = fit_runner.solver_for(cell)
    reference = harness.reference_for(cell, fit_runner.REFERENCE)
    holdout_rmse = fit_runner.holdout_rmse
    harness.start_on_chip(cell.chips)
    cfg, traffic = cell.config, cell.traffic
    sweeps, n_ref = int(traffic["sweeps"]), int(traffic["reference_sweeps"])
    for seed in (int(s) for s in args.seeds.split(",")):
        (u, i, r), hold = datagen.planted_ratings(
            seed, num_users=cfg["num_users"], num_items=cfg["num_items"],
            nnz=cfg["nnz"], rank=cfg["planted_rank"], noise=cfg["noise"],
            skew_lam=cfg["skew_lam"])
        stamps = fit_runner.SegmentStamps(Spans())
        model = solver.make_fit(cfg, sweeps, stamps, cell.chips)(u, i, r)
        tables = stamps.tables
        if cell.chips > 1:
            tables = fit_runner.gather_to_one_device(tables)
        tables, seen = fit_runner.id_space(
            model, tables, cfg["num_users"], cfg["num_items"])
        del model, stamps
        rmse = [float(holdout_rmse(U, V, *seen, *hold)) for U, V in tables]
        tables = tables[:n_ref]
        gc.collect()
        ref = reference.fit(u, i, r, cfg, n_ref)
        ref_rmse = [float(holdout_rmse(U, V, *ref["seen"], *hold))
                    for U, V in ref["sweeps"]]
        numbers = compare.fit_numbers(tables, rmse, ref, ref_rmse)
        correct, compared = compare.judge(
            {k: v for k, v in numbers.items() if k in cfg["limits"]},
            cfg["limits"])
        hit = next((j + 1 for j, x in enumerate(rmse)
                    if x <= float(cfg["target_rmse"])), None)
        print("program", json.dumps({
            "seed": seed, "correct": correct, "sweeps_to_target": hit,
            "rmse": rmse, "ref_rmse": ref_rmse,
            "compared": compared}), flush=True)
        del ref, tables, u, i, r, hold
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
