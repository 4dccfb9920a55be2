"""Pallas vs XLA DSGD kernel: the gather-ceiling experiment, measured.

Round-3 verdict: the claim "a Pallas kernel has no physics headroom" was
argued from an XLA gather microbenchmark, not from a pipelined kernel —
and the host CPU within 2x of the TPU kernel says headroom exists. This
script MEASURES the question on the current device:

  xla    — ops.sgd.sgd_block_sweep (the production kernel) on one
           realistic (stratum, block) visit;
  take   — ops.pallas_sgd.pallas_block_sweep, VMEM-staged factor slices,
           vectorized jnp.take gather (Mosaic dynamic-gather);
  loop   — same staging, per-entry fori_loop gather (guaranteed lowering).

The Pallas kernels stage the block's CONTIGUOUS factor-row ranges in VMEM
(one big DMA each way) and do all row access VMEM-side — the structural
lever the XLA gather cannot express (its every row access is an HBM
latency round trip, measured ~0.6% of HBM peak, docs/PERF.md).

A Mosaic lowering failure is itself a result: it prints as
``variant=... FAILED <error>`` — record it, don't hide it.

Usage:
    python scripts/pallas_probe.py                    # current device
    PROBE_RANK=64 PROBE_MB=1024 python scripts/pallas_probe.py
    PROBE_CPU=1 python scripts/pallas_probe.py        # CPU, interpreted
                                                      # (explicit; off a
                                                      # TPU without it
                                                      # the probe raises)

Defaults model one ML-25M block visit at k=32 (rpb_u 5080, rpb_v 1848,
~24K ratings) — the production operating point since the k=16 visit
OOM'd under the pipeline's 2× stream buffering (docs/MOSAIC_AOT.json);
VMEM-sized for v5e at rank 128.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    from large_scale_recommendation_tpu.utils.platform import (
        enable_compilation_cache,
        force_cpu,
    )

    on_cpu = os.environ.get("PROBE_CPU") == "1"
    if on_cpu:
        force_cpu()

    import jax

    enable_compilation_cache()
    dev = jax.devices()[0]
    rank = int(os.environ.get("PROBE_RANK", 128))
    mb = int(os.environ.get("PROBE_MB", 2048))
    rpb_u = int(os.environ.get("PROBE_RPB_U", 5080))
    rpb_v = int(os.environ.get("PROBE_RPB_V", 1848))
    e = int(os.environ.get("PROBE_NNZ", 24576))
    e -= e % mb
    reps = int(os.environ.get("PROBE_REPS", 5))
    lr, lam = 0.1, 0.1

    print(f"# device={dev} rank={rank} mb={mb} rpb_u={rpb_u} "
          f"rpb_v={rpb_v} nnz={e}", flush=True)

    from large_scale_recommendation_tpu.ops import sgd as sgd_ops
    from large_scale_recommendation_tpu.ops.pallas_sgd import probe_variants

    res = probe_variants(rank=rank, mb=mb, rpb_u=rpb_u, rpb_v=rpb_v,
                         nnz=e, reps=reps,
                         sort=os.environ.get("PROBE_SORT") == "1",
                         interpret=on_cpu)
    summary = {
        "device": str(dev), "tpu": dev.platform == "tpu",
        "interpreted": on_cpu, "rank": rank, "mb": mb,
        "rpb_u": rpb_u, "rpb_v": rpb_v, "nnz": e, "reps": reps,
    }
    for label, val in res.items():
        if isinstance(val, str):
            print(f"{label:12s} {val}", flush=True)
            summary[label] = val
        else:
            kern = "pallas" if label.startswith("pallas") else "xla"
            bpv = sgd_ops.dsgd_bytes_per_sweep(
                e, rank, kernel=kern, num_blocks=1,
                rows_u=rpb_u, rows_v=rpb_v)
            gbs = round(val / e * bpv / 1e9, 1)
            print(f"{label:12s} ratings_per_s={val:14.0f} "
                  f"effective_hbm_gbs={gbs:8.1f}", flush=True)
            summary[f"{label}_ratings_per_s"] = val
            summary[f"{label}_effective_hbm_gbs"] = gbs

    # machine-readable contract: flush stderr FIRST so a 2>&1-merging
    # wrapper still sees the JSON summary as the genuinely last line,
    # diffable across rounds like BENCH artifacts
    sys.stderr.flush()
    print(json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()
