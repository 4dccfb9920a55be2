"""Read an ``ingest`` cell's compared numbers on the chip, at the cell's own
size, on several seeds in one process:

    python3 benchmark/tools/limits_ingest.py \
        --workload syn10m1m-r512-online.ingest-replay --seeds 1,2,3 \
        --what program,control,fault

``program``: the cell as the driver runs it (``runners/ingest.py::run``),
its compared numbers, the holdout RMSE at the stamps (batch 1, the last
compared batch) and after the last batch, its rate. ``control``: the same with the ``bf16`` control in the
program's place (the update formed from rows rounded to bfloat16). ``fault``:
the reference with every second minibatch left out, in the program's place
against the reference itself. ``choose``: the program under each
``--lr`` (the holdout RMSE at the end decides the configuration's
``learning_rate``). ``--batches`` cuts the window (a multiple of 64);
``--off-chip`` rehearses at the configuration's toy size on the CPU. The
limits in the configuration file were set from these readings (PERF.md,
section 2).
"""

from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def fault_numbers(cell, seed: int, fault: str):
    """The compared numbers of the reference with ``fault`` planted, held
    against the reference itself: ``(numbers, correct)``."""
    from benchmark import compare, harness
    from benchmark.runners import ingest

    reference = harness.reference_for(cell, ingest.REFERENCE)
    warm = int(ingest.param(cell, "warmup_batches"))
    n_ref = int(ingest.param(cell, "reference_batches"))
    stream, hold = ingest.make_stream(cell, seed, warm + n_ref)
    hold = tuple(x[:int(ingest.param(cell, "stamp_holdout"))] for x in hold)
    tables = ingest.starting_tables(cell, seed)
    ids = ingest.compared_ids(cell, seed, stream, warm, n_ref)
    sides = [ingest.reference_side(cell, reference, tables, stream, hold,
                                   ids, warm, n_ref, fault=f)
             for f in (None, fault)]
    (ref, ref_rmse), (bad, bad_rmse) = sides
    numbers = compare.fit_numbers(bad["sweeps"], bad_rmse, ref, ref_rmse)
    correct, _ = compare.judge(numbers, cell.config["limits"])
    return numbers, correct


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--what", default="program,control,fault")
    ap.add_argument("--lr", default="0.01,0.03,0.1")
    ap.add_argument("--batches", type=int, default=None)
    ap.add_argument("--off-chip", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import harness
    from benchmark.runners import ingest

    cell = harness.resolve_cell(args.workload)
    if args.off_chip:
        cell.config.update(cell.config["toy"])
        device = harness.device_summary()
    else:
        device = harness.start_on_chip(cell.chips)
    if args.batches:
        cell.config["batches"] = args.batches
    seconds = float(ingest.param(cell, "full_at_seconds"))

    def program(kind, seed, control=None, **overrides):
        mine = copy.deepcopy(cell)
        mine.config.update(overrides)
        out = ingest.run(mine, seed, seconds, False, device, control=control)
        print(kind, json.dumps({
            "seed": seed, **overrides, "correct": out["correct"],
            "compared": out["compared"],
            "train_ratings_per_s": out["values"]["train_ratings_per_s"],
            "setup_s": out["values"]["setup_s"],
            "memory_peak_bytes": out["memory_peak_bytes"],
            "compiles_in_window": out["compiles_in_window"],
            **{k: out["notes"][k] for k in (
                "batches", "stamp_rmse", "end_rmse", "reference_rmse")}}),
            flush=True)
        gc.collect()

    what = args.what.split(",")
    for seed in (int(s) for s in args.seeds.split(",")):
        if "program" in what:
            program("program", seed)
        if "control" in what:
            program("control_bf16", seed, control="bf16")
        if "fault" in what:
            numbers, correct = fault_numbers(cell, seed, "half_batch")
            print("fault_half_batch", json.dumps(
                {"seed": seed, "correct": correct, **numbers}), flush=True)
            gc.collect()
        if "choose" in what:
            for lr in (float(x) for x in args.lr.split(",")):
                program("choose", seed, learning_rate=lr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
