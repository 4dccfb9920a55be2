"""``flush_drain_idle_ms``: milliseconds per flush in which chip 0 ran nothing
while the host was inside the result drains (the wait for the device and the
copy back)."""

from benchmark.layer_metrics import seam_idle


def read(ctx):
    return seam_idle.read("flush_drain_idle_ms", ctx)
