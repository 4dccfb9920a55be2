"""Read a BPR ``fit_rank`` cell's own numbers on the chip on many seeds in
one process, as ``readings_rank.py`` does for the implicit ALS cell, with
the BPR cell's own fault and its own settings to choose:

    python3 benchmark/tools/readings_bpr.py --workload mpd66m-bpr-r128.fit-rank --seeds 1,2,3
    python3 benchmark/tools/readings_bpr.py --workload ... --seeds 1 --what control,fault
    python3 benchmark/tools/readings_bpr.py --workload ... --seeds 1 --what choose --blocks 8,16 --lr 0.05,0.1 --lambda 0.01

Per seed it makes the data and, by ``--what``:

- ``shape``: the entries a user and an item hold, the items never seen in
  training;
- ``program``: runs the program through the cell's solver file for the
  traffic file's sweeps, ranks the held-out interactions after every sweep,
  runs the reference and prints each compared number beside its limit and
  the sweep that first meets the target;
- ``control``: the same with the solver's ``bf16`` control in the program's
  place (the nearest precision below the float32 the configuration states);
- ``fault``: the reference with the negative's ``-g u`` left out of its
  delta (``bpr_ref``'s ``no_negative_step``), compared with the reference;
- ``choose``: the program alone under each ``--blocks`` x ``--minibatch`` x
  ``--lr`` x ``--lambda``, the rank after every sweep and the wall between
  sweep ends after the first (the first compiles).

Readings of correctness and of the target's room; ``choose``'s walls are a
guide to the block count, not the cell's time.
"""

from __future__ import annotations

import argparse
import gc
import itertools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _floats(text, default):
    return [default] if text is None else [float(x) for x in text.split(",")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--what", default="program")
    ap.add_argument("--blocks", default=None)
    ap.add_argument("--minibatch", default=None)
    ap.add_argument("--lr", default=None)
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
        walls = list(np.diff(stamps.ends))
        tables = []
        while stamps.tables:  # a sweep at a time: 1.67 GB a pair at full size
            got, seen = fit_runner.id_space(
                model, stamps.tables[:1], cfg["num_users"], cfg["num_items"])
            tables += got
            del stamps.tables[0]
        del model, stamps
        rank = [reference.expected_percentile_rank(U, V, *seen, *hold)
                for U, V in tables]
        return tables, rank, walls

    def ranked(fit, hold):
        return [reference.expected_percentile_rank(U, V, *fit["seen"], *hold)
                for U, V in fit["sweeps"]]

    for seed in (int(s) for s in args.seeds.split(",")):
        (u, i, r), hold = fit_rank.planted_interactions(seed, cfg)
        if "shape" in what:
            du = np.bincount(np.asarray(u), minlength=cfg["num_users"])
            di = np.bincount(np.asarray(i), minlength=cfg["num_items"])
            print("shape", json.dumps({
                "seed": seed, "train": int(u.shape[0]),
                "held_out": int(hold[0].shape[0]),
                "user_entries": [int(du.min()), float(np.median(du)),
                                 float(du.mean()), int(du.max())],
                "users_over_250": int((du > 250).sum()),
                "item_entries": [int(di.min()), float(np.median(di)),
                                 int(di.max())],
                "items_unseen": int((di == 0).sum()),
                "items_once": int((di == 1).sum())}), flush=True)
        if "choose" in what:
            grid = itertools.product(
                _floats(args.blocks, cfg["num_blocks"]),
                _floats(args.minibatch, cfg["minibatch_size"]),
                _floats(args.lr, cfg["learning_rate"]),
                _floats(args.lam, cfg["lambda"]))
            for k, mb, lr, lam in grid:
                trial = dict(cfg, num_blocks=int(k), minibatch_size=int(mb),
                             learning_rate=lr)
                trial["lambda"] = lam
                rank, walls = program(u, i, r, hold, trial, sweeps)[1:]
                print("choose", json.dumps({
                    "seed": seed, "num_blocks": int(k), "minibatch": int(mb),
                    "lr": lr, "lambda": lam, "rank": rank,
                    "sweep_walls_s": walls}), flush=True)
                gc.collect()
        if not {"program", "control", "fault"} & set(what):
            continue
        ref = reference.fit(u, i, r, cfg, n_ref)
        ref_rank = ranked(ref, hold)

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
            report("program", *program(u, i, r, hold, cfg, sweeps)[:2])
            gc.collect()
        if "control" in what:
            report("control_bf16", *program(
                u, i, r, hold, cfg, n_ref,
                **fit_runner.control_overrides(solver, "bf16"))[:2])
            gc.collect()
        if "fault" in what:
            fault = reference.fit(u, i, r, cfg, n_ref,
                                  fault="no_negative_step")
            report("fault_no_negative_step", fault["sweeps"],
                   ranked(fault, hold))
            del fault
        del ref, u, i, r, hold
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
