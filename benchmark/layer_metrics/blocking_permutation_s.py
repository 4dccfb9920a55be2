"""``blocking_permutation_s``: seconds under the scope ``bucket/permutation`` of ``_bucket_entries`` in
total: the seeded shuffle and the sort that inverts it."""

from benchmark.layer_metrics import scoped


def read(ctx):
    return scoped.read("blocking_permutation_s", ctx)
