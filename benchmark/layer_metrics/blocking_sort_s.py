"""``blocking_sort_s``: seconds under the scope ``bucket/sort`` of ``_bucket_entries`` in total:
the two-key sort that carries the six columns into bucket order."""

from benchmark.layer_metrics import scoped


def read(ctx):
    return scoped.read("blocking_sort_s", ctx)
