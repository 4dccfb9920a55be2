"""``flush_form_idle_ms``: milliseconds per flush in which chip 0 ran nothing
while the host formed the batch (``rows_for`` per request, packing)."""

from benchmark.layer_metrics import seam_idle


def read(ctx):
    return seam_idle.read("flush_form_idle_ms", ctx)
