"""The operation and byte counts behind the rooflines and the MFU shares,
each hand-checked at one small shape, and which bound binds at the cells'
own sizes."""

import pytest

import bench_testlib  # noqa: F401
from benchmark import counts
from benchmark.peaks import load_peaks

V5E = load_peaks("TPU v5 lite")


def test_peaks_table_and_unknown_device():
    assert V5E == {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                   "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
    with pytest.raises(KeyError, match="not in benchmark/peaks.json"):
        load_peaks("cpu")


def test_sweep_min_bytes_by_hand():
    # 10 ratings x 12 B + 2 strata x 2 (read+write) x (3 + 2) rows x 4 x 4 B
    assert counts.sweep_min_bytes(10, 3, 2, 4, 2) == 120 + 2 * 2 * 5 * 16


def test_sweep_flops_by_hand():
    assert counts.sweep_flops(10, 4) == 10 * 6 * 4


def test_stage1_counts_by_hand():
    assert counts.stage1_ops(2, 3, 4) == 2 * 2 * 3 * 4
    assert counts.stage1_min_bytes(3, 4) == 12
    assert counts.serve_ops(5, 3, 4) == 2 * 5 * 3 * 4


def test_stage1_floor_takes_the_larger_bound():
    peaks = {"int8_ops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.stage1_floor_s(1, 3, 4, peaks) == pytest.approx(12 / 10.0)
    assert counts.stage1_floor_s(10, 3, 4, peaks) == pytest.approx(240 / 100)


def test_which_bound_binds_at_the_cells_sizes():
    # fit: bytes bind by far (no matmul in the sweep)
    nnz, nu, ni, rank, k = 95_456_482, 480_189, 17_770, 128, 8
    bytes_s = counts.sweep_min_bytes(nnz, nu, ni, rank, k) / 819e9
    flops_s = counts.sweep_flops(nnz, rank) / 197e12
    assert bytes_s == pytest.approx(6.38e-3, rel=0.01)
    assert flops_s == pytest.approx(0.372e-3, rel=0.01)
    # serving: the catalog read binds under 240 rows, the ops above
    items, rank = 1_048_576, 512
    read_s = counts.stage1_min_bytes(items, rank) / 819e9
    assert read_s == pytest.approx(0.6555e-3, rel=1e-3)
    assert counts.stage1_floor_s(128, items, rank, V5E) == pytest.approx(
        read_s)
    assert counts.stage1_floor_s(256, items, rank, V5E) == pytest.approx(
        2 * 256 * items * rank / 393e12)
    assert counts.stage1_floor_s(256, items, rank, V5E) > read_s
