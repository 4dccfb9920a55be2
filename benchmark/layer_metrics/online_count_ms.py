"""``online_count_ms``: milliseconds of a micro-batch under the scope
``sgd/update/collision_counts``: the runtime count vectors of
``collision="mean"``."""

from benchmark.layer_metrics import scoped


def read(ctx):
    return scoped.read("online_count_ms", ctx)
