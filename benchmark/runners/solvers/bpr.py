"""Solver ``bpr``: ``DSGD.fit_device`` with ``loss="bpr"`` (Bayesian
Personalized Ranking, Rendle et al., UAI 2009: a negative drawn on the
device for every positive, inside the DSGD sweep) on one chip, in one-sweep
segments. Sweep ends are stamped through its ``evaluator.on_segment`` hook.
The entry point, the blocking, the block visits and the segment loop are
the ``dsgd`` solver's; the objective is not."""

from __future__ import annotations

from benchmark.runners.solvers import dsgd

# --control <name> -> the overrides that put the control in the program's
# place: bf16 tables are the nearest precision below the float32 the
# configuration states, a path the program has
CONTROLS = {"bf16": {"factor_dtype": "bfloat16"}}


def _require_the_loss():
    """A program from before BPR ends the run here, when the runner loads
    this file: before any data is made."""
    from large_scale_recommendation_tpu.models.dsgd import DSGDConfig

    if "loss" not in DSGDConfig.__dataclass_fields__:
        raise SystemExit("bpr: this program's DSGDConfig has no `loss`")


_require_the_loss()


def make_fit(cfg: dict, iterations: int, stamps, chips: int, **overrides):
    from large_scale_recommendation_tpu.models.dsgd import DSGD, DSGDConfig

    nu, ni = cfg["num_users"], cfg["num_items"]
    kw = dsgd.solver_config(cfg, iterations, loss="bpr", **overrides)
    solver = DSGD(DSGDConfig(num_blocks=cfg["num_blocks"], **kw))
    solver.evaluator = stamps
    return lambda u, i, r: solver.fit_device(
        u, i, r, nu, ni, checkpoint_every=1)


def sizes(cfg: dict) -> dict:
    """What this solver's counts need beside the sizes every fit has."""
    return {"num_blocks": cfg["num_blocks"]}


def sweep_flops(sizes: dict) -> int:
    """FLOP one sweep needs: 10·rank a triple (the difference
    ``v_i - v_j``, the dot with ``u``, and the three deltas at 2·rank
    each); the sigmoid and the collision scales are per triple, not per
    rank. The program's own roofline model counts the same
    (``ops.sgd.dsgd_flops_per_sweep(..., loss="bpr")``), written out here
    so that the yardstick does not move with the program. At
    mpd66m-bpr-r128 that is 80.7 GFLOP a sweep, 0.4 ms at 197 TFLOP/s."""
    return sizes["nnz_train"] * 10 * sizes["rank"]


def counters(metrics: list) -> dict:
    """From a snapshot of the program's registry after the warm-up fit:
    the negatives it drew (the counter ``dsgd_negatives_total``, one a
    real entry a sweep). A program that publishes no such counter gives
    nothing."""
    drawn = [m["value"] for m in metrics
             if m["name"] == "dsgd_negatives_total"]
    return {"dsgd_negatives_total": sum(drawn)} if drawn else {}
