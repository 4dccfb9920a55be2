"""Full north-star DSGD training through the Pallas kernel, on-device A/B.

A per-block-visit probe can favour the VMEM-staged Pallas kernel at one
shape; this script answers the question that matters before any default
flips: does a kernel win survive the FULL north-star training run
(convergence to the pre-registered RMSE target included)? Not yet run on
today's code (ROADMAP S4).

Both arms share one blocked layout (NS_BLOCKS × NS_MB, item-sorted) and
the bench's exact hyperparameters (warm_boost lr 0.3, λ=0.1, target
0.155), so the only variable is the kernel. The bench headline (k=8,
mb 32768, XLA) is the production reference point.

Prints one JSON line. Runs on the current device; nothing but a PRNG key
crosses the host link. PROBE_CPU=1 is the explicit CPU rehearsal (the
Pallas arm is then interpreted).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    on_cpu = os.environ.get("PROBE_CPU") == "1"
    if on_cpu:
        from large_scale_recommendation_tpu.utils.platform import force_cpu

        force_cpu()
    import jax
    import jax.numpy as jnp

    from large_scale_recommendation_tpu.core.updaters import warm_boost_lr
    from large_scale_recommendation_tpu.data.device_blocking import (
        device_block_problem,
        init_factors_device,
        synthetic_like_device,
    )
    from large_scale_recommendation_tpu.models.dsgd import DSGD, DSGDConfig
    from large_scale_recommendation_tpu.ops import sgd as sgd_ops
    from large_scale_recommendation_tpu.ops.pallas_sgd import (
        dsgd_train_pallas,
    )
    from large_scale_recommendation_tpu.utils.platform import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    dev = jax.devices()[0]

    nnz = int(os.environ.get("BENCH_NNZ", 25_000_095))
    rank = int(os.environ.get("BENCH_RANK", 128))
    k = int(os.environ.get("NS_BLOCKS", 16))
    mb = int(os.environ.get("NS_MB", 2048))
    target = float(os.environ.get("BENCH_RMSE_TARGET", 0.155))
    max_sweeps = int(os.environ.get("BENCH_ITERS", 12))
    variants = [v.strip() for v in
                os.environ.get("NS_VARIANTS", "pallas,xla").split(",")]
    bad = [v for v in variants if v not in ("pallas", "xla")]
    if bad:
        # fail LOUDLY before the long run: a typo'd variant
        # would otherwise run the XLA arm under the wrong label and emit
        # a plausible-looking but wrong A/B
        raise SystemExit(f"NS_VARIANTS must be pallas|xla, got {bad}")
    out: dict = {"device": str(dev.device_kind) + str(dev.id), "rank": rank,
                 "blocks": k, "minibatch": mb, "nnz": nnz,
                 "rmse_target": target}

    from large_scale_recommendation_tpu.data.movielens import (
        vocab_overrides_from_env,
    )

    num_users, num_items = vocab_overrides_from_env()
    (du, di, dr), (dhu, dhi, dhv), (nu, ni) = synthetic_like_device(
        "ml-25m", nnz=nnz, rank=16, noise=0.1, seed=0, skew_lam=2.0,
        num_users=num_users, num_items=num_items)
    jax.block_until_ready(dr)
    t0 = time.perf_counter()
    p = device_block_problem(du, di, dr, nu, ni, num_blocks=k,
                             minibatch_multiple=mb, seed=0,
                             minibatch_sort="item")
    jax.block_until_ready(p.su)
    out["blocking_wall_s"] = round(time.perf_counter() - t0, 1)
    out["max_pad_ratio"] = round(p.max_pad_ratio, 3)
    train_nnz = int(du.shape[0])

    cfg = DSGDConfig(num_factors=rank, lambda_=0.1, iterations=1,
                     learning_rate=0.3, lr_schedule="warm_boost", seed=0,
                     minibatch_size=mb, init_scale=0.08,
                     collision_mode="mean")
    solver = DSGD(cfg)
    schedule = warm_boost_lr()  # the bench default: 2.5x for 2 sweeps
    hur_d, hir_d, hmask = p.holdout_rows(dhu, dhi)
    n_eval = float(np.asarray(hmask).sum())

    def rmse(U, V):
        sse = sgd_ops.sse_rows(U, V, hur_d, hir_d, dhv, hmask)
        return float(np.sqrt(float(sse) / n_eval))

    args = (p.su, p.si, p.sv, p.sw, p.omega_u, p.omega_v, p.icu, p.icv)

    for variant in variants:
        U, V = init_factors_device(p, rank, scale=cfg.init_scale)

        if variant == "pallas":
            def sweep(U, V, t):
                return dsgd_train_pallas(
                    U, V, *args, lr=cfg.learning_rate, lam=cfg.lambda_,
                    minibatch=mb, num_blocks=k, iterations=1,
                    schedule=schedule, t0=t, interpret=on_cpu)
        else:
            kw = dict(updater=solver.updater, minibatch=mb, num_blocks=k,
                      iterations=1, collision="mean")

            def sweep(U, V, t):
                return sgd_ops.dsgd_train(U, V, *args, **kw, t0=t)

        try:
            t0 = time.perf_counter()
            Uw, Vw = sweep(U, V, 0)
            jax.block_until_ready((Uw, Vw))
            out[f"{variant}_compile_wall_s"] = round(
                time.perf_counter() - t0, 1)
            del Uw, Vw
        except Exception as ex:
            out[f"{variant}_error"] = f"{type(ex).__name__}: {ex}"[:500]
            continue

        wall = 0.0
        curve = [round(rmse(U, V), 4)]
        tt = st = None
        for it in range(max_sweeps):
            t0 = time.perf_counter()
            U, V = sweep(U, V, it)
            jax.block_until_ready((U, V))
            wall += time.perf_counter() - t0
            curve.append(round(rmse(U, V), 4))
            if tt is None and curve[-1] <= target:
                tt, st = wall, it + 1
                break
        sweeps = st or max_sweeps
        out[f"{variant}_rmse_curve"] = curve
        out[f"{variant}_time_to_target_s"] = (None if tt is None
                                              else round(tt, 2))
        out[f"{variant}_ratings_per_s"] = round(
            train_nnz * sweeps / wall, 1)

    print(json.dumps(out))


if __name__ == "__main__":
    main()
