"""What the eight readers of PR 38 share: each is device time that the
program has already named, either by a ``jax.named_scope`` of its device
code or by the name of a jitted program, handed to ``readers.scope_time``
or ``readers.program_time`` as it is. The specs are what a data file's
``reader`` would hold; they live here because
``tests/benchmark_harness/test_seam_metrics.py`` closes the list of
``scope_time`` files (eleven) and of programs that data files name (nine).

A reader returns None where there is no trace, or where the trace lacks
the scope or the program (a program from before the scope; a run that
never reached the code; a JAX that names its placement otherwise): the
metric is then left out of the line, never 0."""

from benchmark import readers

SGD = ["dsgd_train", "run"]   # the one-chip sweep and the ring's step
BUCKET = ["_bucket_entries"]
A_RUN = {"per": "run", "scale": 1000.0}

# ``of_jax``: the program is a jitted function of JAX itself, not of this
# repository (the tests look for it there)
SPECS = {
    "sweep_omega_gather_ms": {
        "kind": "scope_time", "programs": SGD,
        "scopes": ["sgd/gather/omega"], **A_RUN},
    "online_count_ms": {
        "kind": "scope_time", "programs": ["online_train"],
        "scopes": ["sgd/update/collision_counts"], **A_RUN},
    "blocking_counts_s": {
        "kind": "program_time", "programs": ["_weighted_counts"]},
    "blocking_permutation_s": {
        "kind": "scope_time", "programs": BUCKET,
        "scopes": ["bucket/permutation"]},
    "blocking_assign_s": {
        "kind": "scope_time", "programs": BUCKET,
        "scopes": ["bucket/assign"]},
    "blocking_sort_s": {
        "kind": "scope_time", "programs": BUCKET,
        "scopes": ["bucket/sort"]},
    "mesh_place_s": {
        "kind": "program_time", "programs": ["_multi_slice"],
        "of_jax": "jax._src.numpy.array_methods"},
    "stage1_topk_ms": {
        "kind": "scope_time", "programs": ["_stage1_flat"],
        "scopes": ["stage1/top_k", "stage1/group_top_k"],
        "per": "span", "span": "serving/flush", "scale": 1000.0},
}


def read(metric, ctx):
    spec = SPECS[metric]
    return readers.KINDS[spec["kind"]](spec, ctx)
