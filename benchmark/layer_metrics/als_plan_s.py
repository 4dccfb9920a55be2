"""``als_plan_s``: seconds from the call of ``fit_device`` to the first
start of the solve program on the device: counting the ratings, both sides'
plans (``device_prepare_side``, the class-size read-backs included) and the
initial table."""

from benchmark import readers
from benchmark.layer_metrics.als_sweep_roofline import SOLVE

SPEC = {"span": "fit/fit_device", "programs": [SOLVE]}


def read(ctx):
    return readers.program_start_after_span(SPEC, ctx)
