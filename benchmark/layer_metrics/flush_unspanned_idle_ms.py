"""``flush_unspanned_idle_ms``: the honest remainder — the mean host share
of a flush (chip 0 idle inside ``serving/flush``) that no seam covers."""

from benchmark.layer_metrics import seam_idle


def read(ctx):
    return seam_idle.read("flush_unspanned_idle_ms", ctx)
