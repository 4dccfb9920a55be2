"""``flush_prepare_idle_ms``: milliseconds per flush in which chip 0 ran
nothing while the host built the exclusions and dispatched the user-row
gather of each micro-batch."""

from benchmark.layer_metrics import seam_idle


def read(ctx):
    return seam_idle.read("flush_prepare_idle_ms", ctx)
