"""Read a ``fit_rank`` cell's own numbers on the chip on many seeds in one
process, as ``readings_fit.py`` and ``limits_fit.py`` do for the ``fit``
cells (they make ratings and score an RMSE; this makes interactions and
ranks the held-out ones):

    python3 benchmark/tools/readings_rank.py --workload msd34m-ials-r128.fit-rank --seeds 1,2,3
    python3 benchmark/tools/readings_rank.py --workload ... --seeds 1 --what control,fault
    python3 benchmark/tools/readings_rank.py --workload ... --seeds 1 --what choose --alpha 1,10,40 --lambda 1,10,100

Per seed it makes the data and, by ``--what``:

- ``program``: runs the program through the cell's solver file for the
  traffic file's sweeps, ranks the held-out interactions after every sweep,
  runs the reference and prints each compared number beside its limit and
  the sweep that first meets the target;
- ``control``: the same with the solver's ``bf16`` control in the program's
  place (the nearest precision below the float32 the configuration states);
- ``fault``: the reference with every second interaction of each row left
  out, compared with the reference;
- ``choose``: the program alone under each ``--alpha`` x ``--lambda``, the
  rank after every sweep (how the configuration's two were chosen).

Readings of correctness, of the target's room and of the plan's shape only:
the fit compiles as it goes, so nothing here is a time. The target and the
limits in a configuration file are set from these lines and from the runs'
own (PERF.md, sections 2 and 4).
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--what", default="program")
    ap.add_argument("--alpha", default=None)
    ap.add_argument("--lambda", dest="lam", default=None)
    ap.add_argument("--sweeps", type=int, default=None)
    ap.add_argument("--off-chip", action="store_true",
                    help="a rehearsal at the configuration's toy size")
    args = ap.parse_args(argv)
    what = args.what.split(",")

    import numpy as np

    from benchmark import compare, harness
    from benchmark.runners import fit as fit_runner
    from benchmark.runners import fit_rank
    from benchmark.spans import Spans

    cell = harness.resolve_cell(args.workload)
    solver = fit_runner.solver_for(cell)
    reference = harness.reference_for(cell, fit_rank.REFERENCE)
    cfg, traffic = cell.config, cell.traffic
    if args.off_chip:
        cfg.update(cfg["toy"])
    else:
        harness.start_on_chip(cell.chips)
    sweeps = args.sweeps or int(traffic["sweeps"])
    n_ref = min(sweeps, int(traffic["reference_sweeps"]))

    def program(u, i, r, hold, cfg, n, **overrides):
        stamps = fit_runner.SegmentStamps(Spans())
        model = solver.make_fit(cfg, n, stamps, cell.chips,
                                **overrides)(u, i, r)
        tables, seen = fit_runner.id_space(
            model, stamps.tables, cfg["num_users"], cfg["num_items"])
        rank = [reference.expected_percentile_rank(U, V, *seen, *hold)
                for U, V in tables]
        return tables, rank

    for seed in (int(s) for s in args.seeds.split(",")):
        (u, i, r), hold = fit_rank.planted_interactions(seed, cfg)
        if "shape" in what:
            du = np.bincount(np.asarray(u), minlength=cfg["num_users"])
            di = np.bincount(np.asarray(i), minlength=cfg["num_items"])
            rr = np.asarray(r)
            print("shape", json.dumps({
                "seed": seed, "train": int(u.shape[0]),
                "held_out": int(hold[0].shape[0]),
                "user_entries": [int(du.min()), float(np.median(du)),
                                 int(du.max())],
                "item_entries": [int(di.min()), float(np.median(di)),
                                 int(di.max())],
                "count_is_1": float((rr == 1).mean()),
                "count_mean": float(rr.mean()),
                "count_max": float(rr.max())}), flush=True)
        if "choose" in what:
            for alpha in (float(a) for a in args.alpha.split(",")):
                for lam in (float(x) for x in args.lam.split(",")):
                    trial = dict(cfg, alpha=alpha)
                    trial["lambda"] = lam
                    _, rank = program(u, i, r, hold, trial, sweeps)
                    print("choose", json.dumps({
                        "seed": seed, "alpha": alpha, "lambda": lam,
                        "rank": rank}), flush=True)
                    gc.collect()
        if not {"program", "control", "fault"} & set(what):
            continue
        ref = reference.fit(u, i, r, cfg, n_ref)
        ref_rank = [reference.expected_percentile_rank(U, V, *ref["seen"],
                                                       *hold)
                    for U, V in ref["sweeps"]]

        def report(kind, tables, rank):
            numbers = compare.fit_numbers(tables[:n_ref], rank, ref,
                                          ref_rank)
            correct, compared = compare.judge(
                {k: v for k, v in numbers.items() if k in cfg["limits"]},
                cfg["limits"])
            hit = next((j + 1 for j, x in enumerate(rank)
                        if x <= float(cfg["target_rank"])), None)
            print(kind, json.dumps({
                "seed": seed, "correct": correct, "sweeps_to_target": hit,
                "rank": rank, "ref_rank": ref_rank,
                "compared": compared}), flush=True)

        if "program" in what:
            report("program", *program(u, i, r, hold, cfg, sweeps))
            gc.collect()
        if "control" in what:
            report("control_bf16", *program(
                u, i, r, hold, cfg, n_ref,
                **fit_runner.control_overrides(solver, "bf16")))
            gc.collect()
        if "fault" in what:
            fault = reference.fit(u, i, r, cfg, n_ref, fault="half_batch")
            report("fault_half_batch", fault["sweeps"], [
                reference.expected_percentile_rank(U, V, *fault["seen"],
                                                   *hold)
                for U, V in fault["sweeps"]])
            del fault
        del ref, u, i, r, hold
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
