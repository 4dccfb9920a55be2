"""``blocking_counts_s``: seconds of the program ``_weighted_counts`` in total: the two
scatter-adds that count every user's and every item's ratings."""

from benchmark.layer_metrics import scoped


def read(ctx):
    return scoped.read("blocking_counts_s", ctx)
