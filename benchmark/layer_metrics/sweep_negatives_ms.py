"""``sweep_negatives_ms``: device milliseconds under the named scope
``sgd/negatives`` of the program ``dsgd_train``, a run (a one-sweep
segment): the BPR step's draw of a negative for every entry and the item
side's count of positives and negatives a minibatch. A squared-loss fit,
or a program from before BPR, has no such scope: None.

A file of its own, not a ``scope_time`` data file, only because a test
in ``tests/benchmark_harness/test_seam_metrics.py`` closes the list of
those files at eleven. When that test asserts membership instead of the
count, this file should go and ``sweep_negatives_ms.json`` carry ``SPEC``
as its ``reader``, as the other scope metrics do."""

from benchmark import readers

SPEC = {"kind": "scope_time", "programs": ["dsgd_train"],
        "scopes": ["sgd/negatives"], "per": "run", "scale": 1000.0}


def read(ctx):
    return readers.scope_time(SPEC, ctx)
