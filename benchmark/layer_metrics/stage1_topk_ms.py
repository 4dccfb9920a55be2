"""``stage1_topk_ms``: milliseconds a flush under the scopes ``stage1/top_k`` and
``stage1/group_top_k`` of ``_stage1_flat``: the two small top-ks."""

from benchmark.layer_metrics import scoped


def read(ctx):
    return scoped.read("stage1_topk_ms", ctx)
