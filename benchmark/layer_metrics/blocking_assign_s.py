"""``blocking_assign_s``: seconds under the scope ``bucket/assign`` of ``_bucket_entries`` in total:
every entry's rows (two gathers through the row maps) and its bucket key."""

from benchmark.layer_metrics import scoped


def read(ctx):
    return scoped.read("blocking_assign_s", ctx)
