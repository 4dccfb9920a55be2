"""``online_update_hbm_roofline``: the least time the chip could take to move
the bytes the window's micro-batches must move, over the device time of the
update program, in percent. The count is the update's own, whatever
implements it: a rating reads its user row and its item row and writes
both back."""

# the jitted update of ops/sgd.py as a device trace names it (less "jit_"):
# ``online_train`` and its donating twin ``online_train_inplace`` are one
# function under one name. Named here and not under the metric files'
# ``reader.programs``: tests/benchmark_harness/test_seam_metrics.py pins
# that list to the programs PR 26 knew.
PROGRAMS = ("online_train",)


def update_min_bytes(ratings: int, rank: int, factor_bytes: int = 4) -> int:
    """Least HBM bytes ``ratings`` ratings must move: four rows of ``rank``
    factors each (two read, two written). At 65,536 ratings of rank 512:
    537 MB, 0.66 ms at 819 GB/s. The 12 B of the rating itself and rows
    that a minibatch hits twice are left out: both are under 1%."""
    return ratings * 4 * rank * factor_bytes


def read(ctx):
    trace, peaks = ctx.get("trace"), ctx.get("peaks")
    if not trace or peaks is None:
        return None
    device_s = sum(trace["program_s"].get(p, 0.0) for p in PROGRAMS)
    runs = sum(trace["program_runs"].get(p, 0) for p in PROGRAMS)
    if not device_s or not runs:
        return None
    sizes = ctx["sizes"]
    floor = runs * update_min_bytes(sizes["micro_batch_records"],
                                    sizes["rank"]) / peaks["hbm_bytes_per_s"]
    return 100.0 * floor / device_s
