"""``flush_dispatch_idle_ms``: milliseconds per flush in which chip 0 ran
nothing while the host dispatched stage 1 and stage 2 of each micro-batch."""

from benchmark.layer_metrics import seam_idle


def read(ctx):
    return seam_idle.read("flush_dispatch_idle_ms", ctx)
