"""The serving hot path compiled for the real chip at the benchmark's
widths, without the chip.

``exact_top_k`` reads the ``f32[b, n]`` score matrix through a view that
is the order a TPU already holds it in; whether the TPU's compiler takes
that view for free or copies the gigabyte is not something a CPU run can
see, and the whole gain of the two-level selection rests on it. The
compiler is installed here and compiles for a chip that is described and
not attached, so these tests read it off the compiled program. Nothing
runs: no time, no result.

The topology is described inside a fixture, never at import: one process
at a time may load the TPU's library, and every xdist worker imports
every test file.
"""

import math
import os
import re
from functools import partial

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from large_scale_recommendation_tpu.serving import retrieval


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("b", [256, 8])
def test_stage1_flat_holds_the_scores_once(one_chip, b):
    """At ``[b, 1048576]``, rank 512, 40 candidates: the program's
    temporaries are the score matrix once, and no ``copy`` moves it (a
    relaid-out copy for the group maxima or for the gather would do
    both)."""
    n, rank, kc = 1 << 20, 512, 40
    f32, i32 = jnp.float32, jnp.int32
    sds = partial(jax.ShapeDtypeStruct, sharding=one_chip)
    compiled = jax.jit(partial(retrieval._stage1_flat, kc=kc)).lower(
        sds((b, rank), jnp.int8), sds((b,), f32),
        sds((n, rank), jnp.int8), sds((n,), f32), sds((n,), f32),
        sds((8,), i32), sds((8,), i32), sds((8,), f32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1.25 * b * n * 4
    for line in compiled.as_text().splitlines():
        shape = re.search(r"= f32\[([\d,]+)\]\S* copy\(", line)
        if shape:
            assert math.prod(map(int, shape[1].split(","))) < b * n, line
