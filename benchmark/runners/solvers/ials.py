"""Solver ``ials``: ``ALS.fit_device`` with ``implicit_alpha`` set (weighted
ALS for implicit feedback: Hu, Koren and Volinsky, ICDM 2008) on one chip,
in one-sweep segments. Sweep ends are stamped through its
``evaluator.on_segment`` hook. The entry point, the plan, the chunked solve
and the segment loop are the ``als`` solver's; the objective is not."""

from __future__ import annotations

from benchmark.layer_metrics.als_sweep_roofline import als_sweep_flops

# --control <name> -> the overrides that put the control in the program's
# place: bf16 Gram inputs are the nearest precision below the float32 the
# configuration states, a path the program has
CONTROLS = {"bf16": {"gram_dtype": "bf16"}}


def make_fit(cfg: dict, iterations: int, stamps, chips: int, **overrides):
    from large_scale_recommendation_tpu.models.als import ALS, ALSConfig

    nu, ni = cfg["num_users"], cfg["num_items"]
    kw = dict(num_factors=cfg["num_factors"], lambda_=cfg["lambda"],
              iterations=iterations, reg_mode=cfg["reg_mode"],
              implicit_alpha=cfg["alpha"], seed=cfg["solver_seed"],
              min_pad=cfg["min_pad"], init_scale=cfg["init_scale"],
              gram_dtype=cfg["gram_dtype"])
    kw.update(overrides)
    solver = ALS(ALSConfig(**kw))
    solver.evaluator = stamps
    return lambda u, i, r: solver.fit_device(
        u, i, r, nu, ni, checkpoint_every=1)


def sizes(cfg: dict) -> dict:
    """What this solver's counts need beside the sizes every fit has."""
    return {}


def sweep_flops(sizes: dict) -> int:
    """The explicit solver's count over real entries, rows and rank (the
    confidence-weighted Gram matrix and right-hand side cost what the
    plain ones do), plus the two shared Gram matrices of a sweep:
    2·rank² a row of either table, under 1% of the rest at
    msd34m-ials-r128 (0.02 of 2.56 TFLOP)."""
    rows = sizes["num_users"] + sizes["num_items"]
    return (als_sweep_flops(sizes["nnz_train"], sizes["num_users"],
                            sizes["num_items"], sizes["rank"])
            + 2 * rows * sizes["rank"] ** 2)


def counters(metrics: list) -> dict:
    """From a snapshot of the program's registry after a fit: the plan's
    padded slots over its real entries, both sides together (the gauge
    ``als_plan_pad_ratio{side}``; the sides hold the same entries, so the
    mean). A program that publishes no such gauge gives nothing."""
    ratios = [m["value"] for m in metrics
              if m["name"] == "als_plan_pad_ratio"]
    if not ratios:
        return {}
    return {"als_plan_pad_ratio": sum(ratios) / len(ratios)}
