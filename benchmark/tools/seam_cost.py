"""What one seam opening costs with nothing enabled and no profiler session
(every ``--trace 0`` run): nanoseconds per ``with get_tracer().seam(name)``
on the null tracer, beside an empty ``with`` block for scale.

    python3 benchmark/tools/seam_cost.py

Host code only: it needs no chip, but the number is the host's, so run it
on the machine whose flushes it is compared with (PERF.md, Findings).
"""

from __future__ import annotations

import contextlib
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def ns_per_open(make, n: int = 200_000, repeats: int = 5) -> float:
    best = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(n):
            with make():
                pass
        best.append((time.perf_counter() - t0) / n * 1e9)
    return statistics.median(best)


def main() -> int:
    from large_scale_recommendation_tpu.obs.trace import get_tracer

    tracer = get_tracer()
    if tracer.enabled:
        raise SystemExit("seam_cost: a live tracer is installed")
    null = contextlib.nullcontext()
    out = {
        "seam_open_ns": ns_per_open(
            lambda: tracer.seam("serving/engine/form")),
        "seam_open_with_sink_ns": ns_per_open(
            lambda: tracer.seam("serving/engine/form",
                                sink=lambda name: None)),
        "empty_with_ns": ns_per_open(lambda: null),
    }
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
