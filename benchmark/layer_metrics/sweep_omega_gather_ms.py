"""``sweep_omega_gather_ms``: milliseconds of a sweep under the scope ``sgd/gather/omega``: the two
4-byte omega gathers of every minibatch step."""

from benchmark.layer_metrics import scoped


def read(ctx):
    return scoped.read("sweep_omega_gather_ms", ctx)
