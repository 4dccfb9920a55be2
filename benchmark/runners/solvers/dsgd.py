"""Solver ``dsgd``: ``DSGD.fit_device`` on one chip. Sweep ends are stamped
through its ``evaluator.on_segment`` hook."""

from __future__ import annotations

from benchmark import counts

# --control <name> -> the overrides that put the control in the program's
# place: bf16 is the nearest precision below the float32 the configurations
# state, a path the program has
CONTROLS = {"bf16": {"factor_dtype": "bfloat16"}}


def solver_config(cfg: dict, iterations: int, **overrides) -> dict:
    kw = dict(num_factors=cfg["num_factors"], lambda_=cfg["lambda"],
              iterations=iterations, learning_rate=cfg["learning_rate"],
              lr_schedule=cfg["lr_schedule"], seed=cfg["solver_seed"],
              minibatch_size=cfg["minibatch_size"],
              init_scale=cfg["init_scale"],
              collision_mode=cfg["collision_mode"],
              minibatch_sort=cfg["minibatch_sort"],
              factor_dtype=cfg["factor_dtype"])
    kw.update(overrides)
    return kw


def make_fit(cfg: dict, iterations: int, stamps, chips: int, **overrides):
    from large_scale_recommendation_tpu.models.dsgd import DSGD, DSGDConfig

    nu, ni = cfg["num_users"], cfg["num_items"]
    kw = solver_config(cfg, iterations, **overrides)
    solver = DSGD(DSGDConfig(num_blocks=cfg["num_blocks"], **kw))
    solver.evaluator = stamps
    return lambda u, i, r: solver.fit_device(
        u, i, r, nu, ni, checkpoint_every=1)


def sizes(cfg: dict) -> dict:
    """What this solver's counts need beside the sizes every fit has."""
    return {"num_blocks": cfg["num_blocks"]}


def sweep_flops(sizes: dict) -> int:
    return counts.sweep_flops(sizes["nnz_train"], sizes["rank"])
