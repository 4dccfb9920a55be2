"""``als_sweep_device_ms``: device milliseconds of the solve programs per
one-sweep segment of the fit (the program's seam ``fit/als/segment``)."""

from benchmark import readers
from benchmark.layer_metrics.als_sweep_roofline import PROGRAMS

SPEC = {"programs": list(PROGRAMS), "per": "span", "span": "fit/als/segment",
        "scale": 1000.0}


def read(ctx):
    return readers.program_time(SPEC, ctx)
