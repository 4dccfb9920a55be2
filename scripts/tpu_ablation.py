"""Kernel-lever ablation harness: one command, one table.

Runs the DSGD kernel levers documented in docs/PERF.md (minibatch size,
intra-minibatch locality sort, collision mode, precomputed scales) on the
CURRENT default device over the device-pipeline workload, and prints
per-sweep wall + convergence after N sweeps for each combination — the
tool for turning PERF.md's "levers" section into measured numbers on real
hardware (CPU runs give relative-convergence signal only).

Usage:
    python scripts/tpu_ablation.py                 # default grid
    ABL_NNZ=4000000 ABL_SWEEPS=3 python scripts/tpu_ablation.py
    ABL_CPU=1 python scripts/tpu_ablation.py       # force the CPU backend

Output: one row per combination —
    mb=32768 sort=none  collision=mean  sweep_s=...  rmse@N=...
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    from large_scale_recommendation_tpu.utils.platform import (
        enable_compilation_cache,
        force_cpu,
    )

    if os.environ.get("ABL_CPU") == "1":
        force_cpu()

    import numpy as np
    import jax

    enable_compilation_cache()

    from large_scale_recommendation_tpu.core.updaters import (
        RegularizedSGDUpdater,
        warm_boost_lr,
    )
    from large_scale_recommendation_tpu.data.device_blocking import (
        device_block_problem,
        init_factors_device,
        synthetic_like_device,
    )
    from large_scale_recommendation_tpu.ops import sgd as sgd_ops

    nnz = int(os.environ.get("ABL_NNZ", 25_000_095))
    rank = int(os.environ.get("ABL_RANK", 128))
    k = int(os.environ.get("ABL_BLOCKS", 8))
    sweeps = int(os.environ.get("ABL_SWEEPS", 3))
    mbs = [int(x) for x in os.environ.get("ABL_MBS", "16384,32768").split(",")]
    sorts = os.environ.get("ABL_SORTS", "none,item").split(",")

    print(f"# device={jax.devices()[0]} nnz={nnz} rank={rank} k={k} "
          f"sweeps={sweeps}", flush=True)
    (u, i, r), (hu, hi, hr), (nu, ni) = synthetic_like_device(
        "ml-25m", nnz=nnz, rank=16, noise=0.1, seed=0, skew_lam=2.0)
    train_nnz = int(u.shape[0])  # 95% split — ratings visited per sweep
    upd = RegularizedSGDUpdater(0.3, 0.1, warm_boost_lr())

    for mb in mbs:
        for sort in sorts:
            sort_arg = None if sort in ("none", "") else sort
            p = device_block_problem(u, i, r, nu, ni, num_blocks=k,
                                     minibatch_multiple=mb, seed=0,
                                     minibatch_sort=sort_arg)
            hur, hir, hmask = p.holdout_rows(hu, hi)
            n_eval = float(np.asarray(hmask).sum())
            U, V = init_factors_device(p, rank, scale=0.08)
            kw = dict(updater=upd, minibatch=mb, num_blocks=k,
                      iterations=1, collision="mean")
            args = (p.su, p.si, p.sv, p.sw, p.omega_u, p.omega_v,
                    p.icu, p.icv)
            Uw, Vw = sgd_ops.dsgd_train(U, V, *args, **kw, t0=0)
            jax.block_until_ready((Uw, Vw))  # compile warm-up
            del Uw, Vw
            walls = []
            for t in range(sweeps):
                t0 = time.perf_counter()
                U, V = sgd_ops.dsgd_train(U, V, *args, **kw, t0=t)
                jax.block_until_ready((U, V))
                walls.append(time.perf_counter() - t0)
            sse = sgd_ops.sse_rows(U, V, hur, hir, hr, hmask)
            rmse = float(np.sqrt(float(sse) / n_eval))
            rate = train_nnz / (sum(walls) / len(walls))
            print(f"mb={mb:6d} sort={sort:5s} "
                  f"sweep_s={sum(walls)/len(walls):7.3f} "
                  f"ratings_per_s={rate:12.0f} "
                  f"rmse@{sweeps}={rmse:.4f}", flush=True)


if __name__ == "__main__":
    main()
