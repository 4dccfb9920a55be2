"""Solver ``als``: ``ALS.fit_device`` on one chip, in one-sweep segments.
Sweep ends are stamped through its ``evaluator.on_segment`` hook."""

from __future__ import annotations

import inspect

from benchmark.layer_metrics.als_sweep_roofline import als_sweep_flops

# --control <name> -> the overrides that put the control in the program's
# place: bf16 Gram inputs are the nearest precision below the float32 the
# configuration states, a path the program has
CONTROLS = {"bf16": {"gram_dtype": "bf16"}}


def make_fit(cfg: dict, iterations: int, stamps, chips: int, **overrides):
    from large_scale_recommendation_tpu.models.als import ALS, ALSConfig

    if "checkpoint_every" not in inspect.signature(
            ALS.fit_device).parameters:
        raise SystemExit("solver als: this program's ALS.fit_device runs "
                         "its sweeps in one segment (no checkpoint_every): "
                         "it cannot run the cell")
    nu, ni = cfg["num_users"], cfg["num_items"]
    kw = dict(num_factors=cfg["num_factors"], lambda_=cfg["lambda"],
              iterations=iterations, reg_mode=cfg["reg_mode"],
              seed=cfg["solver_seed"], min_pad=cfg["min_pad"],
              init_scale=cfg["init_scale"], gram_dtype=cfg["gram_dtype"])
    kw.update(overrides)
    solver = ALS(ALSConfig(**kw))
    solver.evaluator = stamps
    return lambda u, i, r: solver.fit_device(
        u, i, r, nu, ni, checkpoint_every=1)


def sizes(cfg: dict) -> dict:
    """What this solver's counts need beside the sizes every fit has."""
    return {}


def sweep_flops(sizes: dict) -> int:
    return als_sweep_flops(sizes["nnz_train"], sizes["num_users"],
                           sizes["num_items"], sizes["rank"])
