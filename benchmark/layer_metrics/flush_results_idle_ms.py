"""``flush_results_idle_ms``: milliseconds per flush in which chip 0 ran
nothing while the host assembled the results (``_assemble_topk``, stats,
meter, SLO, admission)."""

from benchmark.layer_metrics import seam_idle


def read(ctx):
    return seam_idle.read("flush_results_idle_ms", ctx)
