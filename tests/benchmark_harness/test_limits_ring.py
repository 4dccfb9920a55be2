"""``benchmark/tools/limits_ring.py`` at a toy size on four virtual CPU
devices: the ring's control and both faults come out not correct by the
cell's own limits, and the exchange is put back afterwards."""

import jax
import pytest

import bench_testlib
from benchmark.tools import limits_ring

RING = "netflix100m-r128-ring4.fit"


@pytest.fixture(scope="module")
def toy_readings():
    cell = bench_testlib.toy_cell(RING)
    real = jax.lax.ppermute
    got = dict(limits_ring.readings(cell, 5, 2))
    assert jax.lax.ppermute is real
    return got


@pytest.mark.parametrize("kind", limits_ring.KINDS)
def test_ring_control_and_faults_are_not_correct(toy_readings, kind):
    line = toy_readings[kind]
    assert line["correct"] is False
    over = [k for k, c in line["compared"].items()
            if not c["value"] <= c["limit"]]
    assert over, line["compared"]
    assert set(line["compared"]) == set(
        bench_testlib.toy_cell(RING).config["limits"])


def test_the_exchange_left_out_moves_the_tables(toy_readings):
    # every chip kept its first item shard: far from a rounding difference
    compared = toy_readings["fault_no_exchange"]["compared"]
    assert compared["table_diff"]["value"] > 0.1


def test_the_program_itself_still_agrees_after_the_fault():
    # the step cache holds no step built without the exchange
    line, _ = bench_testlib.run_toy(RING)
    assert line["correct"] is True
