"""``mesh_place_s``: seconds of the program ``_multi_slice`` in total: JAX's own name
for the slicing that ``Partitioner.place`` starts inside ``fit/mesh/place``."""

from benchmark.layer_metrics import scoped


def read(ctx):
    return scoped.read("mesh_place_s", ctx)
