"""Read a ring fit cell's own control and faults on four chips, at the
cell's size, judged by the cell's limits (``limits_fit.py`` reads the
one-chip program, another program: ``DSGD``, not ``MeshDSGD``'s shard_map):

    python3 benchmark/tools/limits_ring.py --workload netflix100m-r128-ring4.fit --seeds 1

Per seed it makes the data and puts in the program's place, in turn,

- ``fault_no_exchange``: the program with the exchange between chips left
  out (``lax.ppermute`` replaced by the identity while the step is built:
  every chip keeps its first item shard for the whole sweep);
- ``control_bf16``: the program with ``factor_dtype="bfloat16"`` (the
  nearest precision below the float32 the configuration states);
- ``fault_half_batch``: the reference, at the cell's block count, with half
  of every minibatch left out;

and prints each compared number beside its limit and the verdict
``compare.judge`` gives. The program's runs come first and the reference
after them, as in the cell's own run, so chip 0 holds no more than there.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

KINDS = ("fault_no_exchange", "control_bf16", "fault_half_batch")


@contextlib.contextmanager
def exchange_left_out():
    """While open, a mesh step that is built rotates nothing: the item
    shards stay on the chips they started on."""
    import jax

    from large_scale_recommendation_tpu.parallel import dsgd_mesh

    real = jax.lax.ppermute
    dsgd_mesh._build_mesh_dsgd_step.cache_clear()
    jax.lax.ppermute = lambda x, axis_name, perm: x
    try:
        yield
    finally:
        jax.lax.ppermute = real
        dsgd_mesh._build_mesh_dsgd_step.cache_clear()


def program_tables(cell, data, holdout, n: int, control=None):
    """``n`` one-sweep segments of the cell's program (under one of its
    solver's controls, if named): the tables after each in id space (on
    the host) and their holdout RMSEs."""
    import numpy as np

    from benchmark.runners import fit as fit_runner
    from benchmark.spans import Spans

    cfg = cell.config
    solver = fit_runner.solver_for(cell)
    stamps = fit_runner.SegmentStamps(Spans())
    model = solver.make_fit(
        cfg, n, stamps, cell.chips,
        **fit_runner.control_overrides(solver, control))(*data)
    tables = stamps.tables
    if cell.chips > 1:
        tables = fit_runner.gather_to_one_device(tables)
    tables, seen = fit_runner.id_space(model, tables, cfg["num_users"],
                                       cfg["num_items"])
    rmse = [float(fit_runner.holdout_rmse(U, V, *seen, *holdout))
            for U, V in tables]
    return [tuple(np.asarray(x) for x in t) for t in tables], rmse


def readings(cell, seed: int, n: int, kinds=KINDS):
    """Yields ``(kind, line)`` for each kind: the compared numbers beside
    their limits, the verdict, the RMSEs."""
    import jax.numpy as jnp

    from benchmark import compare, datagen, harness
    from benchmark.runners import fit as fit_runner

    cfg = cell.config
    reference = harness.reference_for(cell, fit_runner.REFERENCE)
    holdout_rmse = fit_runner.holdout_rmse
    data, holdout = datagen.planted_ratings(
        seed, num_users=cfg["num_users"], num_items=cfg["num_items"],
        nnz=cfg["nnz"], rank=cfg["planted_rank"], noise=cfg["noise"],
        skew_lam=cfg["skew_lam"])
    got = {}
    if "fault_no_exchange" in kinds:
        with exchange_left_out():
            got["fault_no_exchange"] = program_tables(cell, data, holdout, n)
        gc.collect()
    if "control_bf16" in kinds:
        got["control_bf16"] = program_tables(cell, data, holdout, n,
                                             control="bf16")
        gc.collect()
    ref = reference.fit(*data, cfg, n)
    ref_rmse = [float(holdout_rmse(U, V, *ref["seen"], *holdout))
                for U, V in ref["sweeps"]]

    def judged(kind, tables, rmse):
        numbers = compare.fit_numbers(tables, rmse, ref, ref_rmse)
        numbers = {k: v for k, v in numbers.items() if k in cfg["limits"]}
        correct, compared = compare.judge(numbers, cfg["limits"])
        return kind, {"seed": seed, "rmse": rmse, "ref_rmse": ref_rmse,
                      "correct": correct, "compared": compared}

    for kind, (tables, rmse) in got.items():
        yield judged(kind, [tuple(jnp.asarray(x) for x in t)
                            for t in tables], rmse)
    if "fault_half_batch" in kinds:
        fault = reference.fit(*data, cfg, n, fault="half_batch")
        rmse = [float(holdout_rmse(U, V, *fault["seen"], *holdout))
                for U, V in fault["sweeps"]]
        yield judged("fault_half_batch", fault["sweeps"], rmse)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--kinds", default=",".join(KINDS))
    args = ap.parse_args()

    from benchmark import harness

    cell = harness.resolve_cell(args.workload)
    if cell.config["solver"] != "mesh_dsgd":
        raise SystemExit("limits_ring: a cell of the mesh_dsgd solver")
    harness.start_on_chip(cell.chips)
    n = int(cell.traffic["reference_sweeps"])
    for seed in (int(s) for s in args.seeds.split(",")):
        for kind, line in readings(cell, seed, n,
                                   tuple(args.kinds.split(","))):
            print(kind, json.dumps(line), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
