"""A/B probe for the round-5 ALS gather levers on the CURRENT device.

Mirrors bench.py's ALS line exactly (same workload, plans, warm-up and
timing protocol) and measures, at each probed rank:

  f32       — the production path (partner-lexsorted plans as of r5)
  bf16      — ALSConfig(gram_dtype="bf16"): half-width fixed-side gather
              + native-MXU bf16 einsum inputs, f32 accumulation/solve

The pre-lever baseline is the in-bench line recorded by the LAST run of
the old code on the same chip (BENCH JSON `als_rank128_rows_per_s`) —
compare against that for the partner-sort effect, and f32-vs-bf16 here
for the dtype effect. Prints one JSON line.

Usage: python scripts/als_probe.py  [ALS_PROBE_RANKS=64,128,256]
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    if os.environ.get("PROBE_CPU") == "1":
        from large_scale_recommendation_tpu.utils.platform import force_cpu

        force_cpu()
    import jax
    import jax.numpy as jnp

    from large_scale_recommendation_tpu.core.initializers import (
        PseudoRandomFactorInitializer,
    )
    from large_scale_recommendation_tpu.data.device_blocking import (
        synthetic_like_device,
    )
    from large_scale_recommendation_tpu.ops import als as als_ops
    from large_scale_recommendation_tpu.utils.platform import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    dev = jax.devices()[0]
    out: dict = {"device": str(dev.device_kind) + str(dev.id)}

    from large_scale_recommendation_tpu.data.movielens import (
        vocab_overrides_from_env,
    )

    als_nnz = int(os.environ.get("BENCH_ALS_NNZ", 2_000_000))
    num_users, num_items = vocab_overrides_from_env()
    (au, ai, ar), _, (anu, ani) = synthetic_like_device(
        "ml-25m", nnz=int(als_nnz / 0.95) + 1, rank=16, noise=0.1, seed=1,
        skew_lam=2.0, num_users=num_users, num_items=num_items)
    t0 = time.perf_counter()
    prep_u = als_ops.device_prepare_side(au, ai, ar, anu,
                                         rank_for_chunking=256)
    prep_v = als_ops.device_prepare_side(ai, au, ar, ani,
                                         rank_for_chunking=256)
    jax.block_until_ready((prep_u, prep_v))
    out["plan_wall_s"] = round(time.perf_counter() - t0, 2)

    ranks = [int(r) for r in os.environ.get(
        "ALS_PROBE_RANKS", "64,128,256").split(",")]
    for rank in ranks:
        iters = 1 if rank >= 256 else 2
        init = PseudoRandomFactorInitializer(rank, scale=0.1)
        V0 = init(np.arange(ani, dtype=np.int32))
        for label, dt in (("f32", None), ("bf16", jnp.bfloat16)):
            def rounds(V, n):
                return als_ops.als_rounds(V, prep_u, prep_v, anu, ani,
                                          0.01, n, gram_dtype=dt)

            jax.block_until_ready(rounds(V0, 1))  # warm-up both sides
            t0 = time.perf_counter()
            U, V = rounds(V0, iters)
            jax.block_until_ready((U, V))
            wall = time.perf_counter() - t0
            out[f"als_rank{rank}_{label}_rows_per_s"] = round(
                (anu + ani) * iters / wall, 1)
        # quality guard at the FIRST probed rank only: one extra round per
        # mode suffices (tests/test_als.py pins f32/bf16 parity across the
        # surface) and chip-window seconds are the binding resource
        if rank == ranks[0]:
            U32, _ = als_ops.als_rounds(V0, prep_u, prep_v, anu, ani,
                                        0.01, 1)
            U16, _ = als_ops.als_rounds(V0, prep_u, prep_v, anu, ani,
                                        0.01, 1, gram_dtype=jnp.bfloat16)
            num = float(jnp.abs(U16 - U32).max())
            den = float(jnp.abs(U32).max())
            out[f"als_rank{rank}_bf16_rel_err"] = round(
                num / max(den, 1e-9), 5)

    print(json.dumps(out))


if __name__ == "__main__":
    main()
