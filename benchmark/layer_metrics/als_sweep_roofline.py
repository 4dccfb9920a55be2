"""``als_sweep_roofline``: the least time the chip could take for the ALS
sweeps the window ran, over the device time of the solve programs, in
percent. The counts are ALS's own, whatever implements it, over real
ratings and not padded slots."""

# the jitted functions of ops/als.py that a sweep runs, as a device trace
# names them (less "jit_"): one solve a bucket, and the implicit mode's
# whole-table Gram. They are named here and not under the metric files'
# ``reader.programs``: tests/benchmark_harness/test_seam_metrics.py pins
# that list to the programs PR 26 knew.
SOLVE = "_solve_bucket"
PROGRAMS = (SOLVE, "_full_gram")


def als_sweep_flops(nnz: int, num_users: int, num_items: int,
                    rank: int) -> int:
    """FLOP one sweep needs. Per side and rating 2·rank² for the Gram
    matrix and 2·rank for the right-hand side; per row solved rank³/3 for
    the Cholesky factorization and 2·rank² for the two triangular solves.
    At netflix100m-als-r128: 6.31 TFLOP of Gram products + 0.36 TFLOP of
    solves = 6.67 TFLOP, 33.9 ms at 197 TFLOP/s."""
    per_rating = 2 * rank * rank + 2 * rank
    per_row = rank ** 3 // 3 + 2 * rank * rank
    return 2 * nnz * per_rating + (num_users + num_items) * per_row


def als_sweep_min_bytes(nnz: int, num_users: int, num_items: int,
                        rank: int, factor_bytes: int = 4) -> int:
    """Least HBM bytes one sweep must move: each side's rating stream
    (row, partner, value: 12 B a rating) read once, and each table read
    once (as the fixed side) and written once (as the solved side). At
    netflix100m-als-r128: 2.29 GB + 0.51 GB = 2.8 GB, 3.4 ms at 819 GB/s:
    the arithmetic binds, not the bytes."""
    stream = 2 * nnz * 12
    tables = 2 * (num_users + num_items) * rank * factor_bytes
    return stream + tables


def floor_s(sizes: dict, peaks: dict) -> float:
    """The larger of the arithmetic bound and the bytes bound of one sweep.
    The peak is the table's bfloat16 one: a float32 program reads low by
    construction."""
    args = (sizes["nnz_train"], sizes["num_users"], sizes["num_items"],
            sizes["rank"])
    return max(als_sweep_flops(*args) / peaks["bf16_flops_per_s"],
               als_sweep_min_bytes(*args) / peaks["hbm_bytes_per_s"])


def read(ctx):
    trace, peaks = ctx.get("trace"), ctx.get("peaks")
    sweeps = ctx["counters"].get("sweeps_done")
    if not trace or peaks is None or not sweeps:
        return None
    device_s = sum(trace["program_s"].get(p, 0.0) for p in PROGRAMS)
    if not device_s:
        return None
    return 100.0 * sweeps * floor_s(ctx["sizes"], peaks) / device_s
