"""Operation and byte counts behind the roofline and MFU shares: small pure
functions of a configuration's sizes. They count the WORK, whatever
implements it, so replacing a kernel leaves the measure standing."""

from __future__ import annotations


def sweep_min_bytes(nnz: int, num_users: int, num_items: int, rank: int,
                    num_blocks: int, factor_bytes: int = 4) -> int:
    """Least HBM bytes one DSGD sweep must move: the blocked rating stream
    (user row, item row, value: 12 B a rating) read once, and U and V read
    and written once per stratum, ``num_blocks`` strata a sweep. At
    netflix100m-r128 (k=8): 1.1 GB of stream + 4.1 GB of tables = 5.2 GB,
    6.4 ms at 819 GB/s. The sweep has no matmul: its floor is bytes, and
    this one is far below what a per-rating row gather moves."""
    stream = nnz * 12
    tables = num_blocks * 2 * (num_users + num_items) * rank * factor_bytes
    return stream + tables


def sweep_flops(nnz: int, rank: int) -> int:
    """FLOP one sweep needs: 6·rank a rating (2·rank for the prediction
    dot, 4·rank for the two factor deltas). At rank 128 that is 73 GFLOP a
    sweep, 0.4 ms at 197 TFLOP/s: the ops bound never binds training."""
    return nnz * 6 * rank


def stage1_ops(rows: int, items: int, rank: int) -> int:
    """int8 multiply-adds of stage 1, counted as 2 ops each: every query
    row against every catalog row."""
    return 2 * rows * items * rank


def stage1_min_bytes(items: int, rank: int) -> int:
    """One read of the int8 catalog."""
    return items * rank


def stage1_floor_s(rows: int, items: int, rank: int, peaks: dict) -> float:
    """The larger of the ops bound and the bytes bound for one stage-1
    call. At 1,048,576 x 512 the catalog read takes 0.66 ms and the ops
    take 2.73 us a row, so bytes bind below 240 rows and ops above: the
    256-row bucket is ops-bound (0.70 ms), every smaller one bytes-bound."""
    return max(stage1_ops(rows, items, rank) / peaks["int8_ops_per_s"],
               stage1_min_bytes(items, rank) / peaks["hbm_bytes_per_s"])


def serve_ops(users: int, items: int, rank: int) -> int:
    """Ops the answers of a window stand for: one full-catalog score per
    user answered (pad rows of a bucket do not count)."""
    return 2 * users * items * rank
