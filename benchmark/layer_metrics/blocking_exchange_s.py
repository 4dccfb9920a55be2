"""``blocking_exchange_s``: device seconds under the named scope
``bucket/exchange`` of the program ``_bucket_entries``, in total, on the chip
where they are most: the one ``all_to_all`` by which the ring's blocking
(``data/device_blocking.py::mesh_block_problem``) hands every entry to the
chip that owns its user block. A program from before the exchange (the
one-chip blocking, or the ring's before PR 40) has no such scope: None."""

from benchmark import readers

SPEC = {"kind": "scope_time", "programs": ["_bucket_entries"],
        "scopes": ["bucket/exchange"]}


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    # the busiest chip's seconds in place of the sum over chips
    busiest = dict(trace, scope_s=trace["scope_per_chip_s"])
    return readers.scope_time(SPEC, dict(ctx, trace=busiest))
