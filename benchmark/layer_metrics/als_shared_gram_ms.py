"""``als_shared_gram_ms``: device milliseconds of the implicit objective's
shared Gram matrices (``ops/als.py::_full_gram``, the scope
``als/shared_gram``: the fixed table's ``F^T F``, once a half-step) per
one-sweep segment of the fit (the program's seam ``fit/als/segment``). The
work is 2 x rank^2 FLOP a table row, under 1% of what ``als_sweep_flops``
counts, so ``als_sweep_roofline`` leaves it out of its count and keeps its
time. An explicit fit runs no such program and reports nothing."""

from benchmark import readers

# named here and not under the metric file's ``reader.programs``:
# tests/benchmark_harness/test_seam_metrics.py pins that list
SPEC = {"programs": ["_full_gram"], "per": "span", "span": "fit/als/segment",
        "scale": 1000.0}


def read(ctx):
    return readers.program_time(SPEC, ctx)
