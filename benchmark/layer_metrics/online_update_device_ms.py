"""``online_update_device_ms``: device milliseconds of the update program a
micro-batch (a run of the program is a micro-batch)."""

from benchmark import readers
from benchmark.layer_metrics.online_update_hbm_roofline import PROGRAMS

SPEC = {"programs": list(PROGRAMS), "per": "run", "scale": 1000.0}


def read(ctx):
    return readers.program_time(SPEC, ctx)
