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

import numpy as np

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from large_scale_recommendation_tpu.serving import retrieval


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
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


def _inside_loops(hlo: str, nested: bool = False) -> str:
    """The text of every computation a ``while`` of the module reaches
    (its body and condition, and what they call, fuse or loop over);
    ``nested``: only of the ``while``s that lie inside another loop."""
    comps = dict(re.findall(r"^(?:ENTRY )?%?([\w.\-]+) [^\n]*\{\n(.*?)^\}",
                            hlo, re.S | re.M))
    calls = re.compile(
        r"(?:body|condition|calls|to_apply)=%?([\w.\-]+)")
    loops = {name: [c for line in body.splitlines() if " while(" in line
                    for c in calls.findall(line)]
             for name, body in comps.items()}

    def reach(todo):
        seen = set()
        while todo:
            name = todo.pop()
            if name not in seen and name in comps:
                seen.add(name)
                todo += calls.findall(comps[name])
        return seen

    seen = reach([c for cs in loops.values() for c in cs])
    if nested:
        seen = reach([c for name in seen for c in loops[name]])
    assert seen, "no loop found in the compiled program"
    return "\n".join(comps[name] for name in seen)


@pytest.fixture(scope="module")
def dsgd_fit_hlo(one_chip):
    """``ops.sgd.dsgd_train`` compiled at the fit cell's widths (U
    ``f32[480192,128]``, V ``f32[17792,128]``, ``k = 8``, minibatch 32768;
    two minibatches a block are enough for the program's shape)."""
    from large_scale_recommendation_tpu.core.updaters import (
        RegularizedSGDUpdater,
        warm_boost_lr,
    )
    from large_scale_recommendation_tpu.ops.sgd import dsgd_train

    k, mb, rank, nu, nv = 8, 32768, 128, 480192, 17792
    f32, i32 = jnp.float32, jnp.int32
    sds = partial(jax.ShapeDtypeStruct, sharding=one_chip)
    blocks = (k, k, 2 * mb)
    return dsgd_train.lower(
        sds((nu, rank), f32), sds((nv, rank), f32),
        sds(blocks, i32), sds(blocks, i32), sds(blocks, f32),
        sds(blocks, f32), sds((nu,), f32), sds((nv,), f32),
        sds(blocks, f32), sds(blocks, f32),
        updater=RegularizedSGDUpdater(learning_rate=0.3, lambda_=0.1,
                                      schedule=warm_boost_lr(0.75, 2)),
        minibatch=mb, num_blocks=k, iterations=1).compile().as_text()


def test_dsgd_train_scatters_into_the_visited_blocks(dsgd_fit_hlo):
    """Both scatter-adds of the fit cell's ``dsgd_train`` land in one row
    block (a k-th of the table), and the block's slice and write-back do
    not bring a copy of the whole U into the loops. On the chip the
    scatter into the whole 246 MB table cost nine times what it costs
    into a shard (PERF.md Findings, PR 32)."""
    rank, nu, nv, k = 128, 480192, 17792, 8
    hlo = dsgd_fit_hlo
    scatters = re.findall(r"= (\w+\[[\d,]*\])\S* scatter\(", hlo)
    assert sorted(set(scatters)) == [f"f32[{nv // k},{rank}]",
                                     f"f32[{nu // k},{rank}]"], scatters
    loops = _inside_loops(hlo)
    assert " scatter(" in loops
    assert not re.search(rf"= f32\[{nu},{rank}\]\S* copy\(", loops)


def test_dsgd_train_reads_the_omegas_a_lane_row_at_a_time(dsgd_fit_hlo):
    """The fit cell's two omega gathers (scope ``sgd/gather/omega``) each
    take a 128-lane row of the block's lane view, ``f32[469,128]`` and
    ``f32[18,128]`` (60,024 and 2,224 rows padded), not one float32 element
    (7 ns an index against 1.5 ns on the chip: PERF.md Findings, PR 39).
    The views are built once a block visit: no pad and no copy of an
    omega table runs in the minibatch loop."""
    gathers = [line for line in dsgd_fit_hlo.splitlines()
               if " gather(" in line and "sgd/gather/omega/" in line]
    assert len(gathers) == 2, gathers
    assert all("slice_sizes={1,128}" in g for g in gathers), gathers
    minibatch = _inside_loops(dsgd_fit_hlo, nested=True)
    assert " gather(" in minibatch and " scatter(" in minibatch
    assert " pad(" not in minibatch
    assert not re.search(
        r"= f32\[(60024|2224|469,128|18,128)\]\S* (copy|pad|fusion)\(",
        minibatch)


def test_bpr_applies_its_item_side_in_row_order(one_chip):
    """``dsgd_train(loss="bpr")`` at reduced widths whose item block
    (``f32[5000,128]``) is taller than a minibatch's ``2 x 512`` item rows:
    inside the minibatch loop the scatter into the item block is told its
    rows are sorted, and no scatter into a 1-D ``f32`` vector as tall as
    the block (a count scattered row by row) is left. At the Million
    Playlist widths the unsorted scatter cost 44-46 ns a row on the chip,
    four times a sorted one (PERF.md, Findings)."""
    from large_scale_recommendation_tpu.core.updaters import (
        RegularizedSGDUpdater,
        constant_lr,
    )
    from large_scale_recommendation_tpu.ops.sgd import dsgd_train

    k, mb, rank, nu, nv = 2, 512, 128, 6000, 10000
    f32, i32 = jnp.float32, jnp.int32
    sds = partial(jax.ShapeDtypeStruct, sharding=one_chip)
    blocks = (k, k, 2 * mb)
    key = jax.random.PRNGKey(0)
    hlo = dsgd_train.lower(
        sds((nu, rank), f32), sds((nv, rank), f32),
        sds(blocks, i32), sds(blocks, i32), sds(blocks, f32),
        sds(blocks, f32), sds((nu,), f32), sds((nv,), f32),
        sds(blocks, f32), None, sds((k,), i32),
        sds(key.shape, key.dtype),
        updater=RegularizedSGDUpdater(learning_rate=0.5, lambda_=0.01,
                                      schedule=constant_lr),
        minibatch=mb, num_blocks=k, iterations=1,
        loss="bpr").compile().as_text()
    minibatch = _inside_loops(hlo, nested=True)
    h = nv // k
    item = [line for line in minibatch.splitlines()
            if re.search(rf"= f32\[{h},{rank}\]\S* scatter\(", line)]
    assert item and all("indices_are_sorted=true" in line
                        for line in item), item
    assert not re.search(rf"= f32\[({h}|{-(-h // 128) * 128})\]\S* "
                         r"scatter\(", minibatch)


@pytest.mark.parametrize("shared_gram", [False, True],
                         ids=["explicit", "implicit"])
def test_solve_bucket_solves_in_the_lanes_kernel(one_chip, shared_gram):
    """``ops.als._solve_bucket`` at the ALS cells' chunk (512 rows at rank
    128, the 256-slot class; three chunks are enough for the program's
    shape), with and without the implicit objective's shared Gram term:
    lowered for the TPU, ``solve_normal_eq`` takes the Pallas kernel, and
    XLA's per-matrix ``Cholesky`` and ``InvertDiagBlocksLowerTriangular``
    (46.5 s of the explicit cell's 65.8 s window: PERF.md, Findings, PR
    34) are not in the program. Its temporaries stay under one chunk's
    gather (64 MiB) plus its Gram matrices (32 MiB) plus 1 MiB for the
    right-hand side, the solution and the write: the relayout to one
    system a lane holds no second copy of the Gram matrices (the program
    reads 64.1 MiB here, as it did with XLA's solve)."""
    from large_scale_recommendation_tpu.ops import als as als_ops

    k, rc, pad, chunks, n_other, n_rows = 128, 512, 256, 3, 17792, 480192
    f32, i32 = jnp.float32, jnp.int32
    sds = partial(jax.ShapeDtypeStruct, sharding=one_chip)
    slots = (chunks, rc, pad)
    compiled = als_ops._solve_bucket.lower(
        sds((n_other, k), f32), sds((n_rows + 1, k), f32),
        sds((chunks, rc), i32), sds(slots, i32), sds(slots, f32),
        sds(slots, f32), sds((chunks, rc), f32), sds((), f32),
        sds((k, k), f32) if shared_gram else None).compile()
    hlo = compiled.as_text()
    calls = set(re.findall(r'custom_call_target="([^"]+)"', hlo))
    assert "tpu_custom_call" in calls and "als_solve_lanes" in hlo
    assert not calls & {"Cholesky", "InvertDiagBlocksLowerTriangular"}
    gather, gram = rc * pad * k * 4, rc * k * k * 4
    assert (compiled.memory_analysis().temp_size_in_bytes
            < gather + gram + (1 << 20))


def test_ring_blocking_holds_no_array_of_every_entry(topo):
    """The ring's blocking (``mesh_block_problem``'s per-chip programs)
    compiled for the four chips of a ``v5e:2x2`` host, at an entry count
    no other size shares (40,003: a chip's share 10,001, the zero-filled
    whole 40,004): no instruction but what enters a program (a parameter,
    an iota) holds an array of every entry, the seeded shuffle's sorts
    among them (the chips sort it in shares); the exchange under
    ``bucket/exchange`` is one ``all-to-all``, and the shuffle under
    ``bucket/permutation`` one a round and one for its inversion."""
    from large_scale_recommendation_tpu.data import device_blocking as db
    from large_scale_recommendation_tpu.parallel import Partitioner

    n, nu, ni, k, mb = 40003, 300, 200, 4, 256
    part = Partitioner(devices=list(topo.devices)[:k])
    q = -(-n // k)
    rpb_u, rpb_v = db.rows_per_block(nu, k), db.rows_per_block(ni, k)
    c = db.exchange_slots(np.zeros((k, k), int), q, k)
    s = db.even_slots(q, k)
    shard, rep = part.sharding("ratings"), part.replicated()
    i32, f32 = jnp.int32, jnp.float32
    entries = partial(jax.ShapeDtypeStruct, (k * q,), sharding=shard)
    key = jax.random.PRNGKey(0)
    hlo = {
        "counts": db._mesh_counts(part, n, q, nu, ni).lower(
            entries(i32), entries(i32), entries(f32)),
        "bucket": db._mesh_bucket(part, n, q, c, s, rpb_u, rpb_v).lower(
            jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=rep),
            entries(i32), entries(i32), entries(f32), entries(f32),
            jax.ShapeDtypeStruct((nu,), i32, sharding=rep),
            jax.ShapeDtypeStruct((ni,), i32, sharding=rep)),
        "layout": db._mesh_layout(part, 4 * mb, mb, "item", rpb_u,
                                  rpb_v).lower(
            *(jax.ShapeDtypeStruct((k * k * c,), dt, sharding=shard)
              for dt in (i32, i32, f32, f32)),
            jax.ShapeDtypeStruct((k, k), i32, sharding=rep)),
    }
    for name, lowered in hlo.items():
        text = lowered.compile().as_text()
        whole = []
        for line in text.splitlines():
            m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) (\S+)\(", line)
            if not m:
                continue
            dims = {int(d) for shape in re.findall(r"\[([\d,]+)\]",
                                                   m[1])
                    for d in shape.split(",") if d}
            if dims & {n, k * q} and m[2] not in ("parameter", "iota"):
                whole.append(line)
        assert not whole, whole[:3]
        if name == "bucket":
            scopes = re.findall(r' all-to-all(?:-start)?\(.*op_name="[^"]*'
                                r'(bucket/\w+)/', text)
            assert sorted(scopes) == ["bucket/exchange"] + [
                "bucket/permutation"] * (db.shuffle_rounds(n) + 1), scopes


def test_bucket_assign_gathers_lane_rows_a_chunk_at_a_time(one_chip):
    """``_bucket_entries`` compiled for one v5e chip at an entry count no
    other size shares (200,003: three chunks and a tail), tables of 3,001
    and 1,777 ids: the two id→row lookups (scope ``bucket/assign``) gather
    128-lane rows of the tables' lane views, ``_LOOKUP_CHUNK`` ids at a
    time. No buffer holds 128 words an entry (every entry at once is 49 GB
    at the fit's 95.5M entries, and does not compile), and no pad or copy
    of a table runs in the chunk loop: the views are built once."""
    from large_scale_recommendation_tpu.data import device_blocking as db

    n, nu, ni, k = 200003, 3001, 1777, 8
    i32, f32 = jnp.int32, jnp.float32
    sds = partial(jax.ShapeDtypeStruct, sharding=one_chip)
    key = jax.random.PRNGKey(0)
    hlo = db._bucket_entries.lower(
        sds(key.shape, key.dtype), sds((n,), i32), sds((n,), i32),
        sds((n,), f32), sds((n,), f32), sds((nu,), i32), sds((ni,), i32),
        k, db.rows_per_block(nu, k),
        db.rows_per_block(ni, k)).compile().as_text()
    assert not re.search(rf"s32\[{n},128\]", hlo)
    gathers = [line for line in hlo.splitlines()
               if " gather(" in line and "bucket/assign/" in line]
    assert len(gathers) == 2, gathers
    assert all("slice_sizes={1,128}" in g and
               f"= s32[{db._LOOKUP_CHUNK},128]" in g for g in gathers), gathers
    views = f"{-(-nu // 128)},128|{-(-ni // 128)},128"
    assert not re.search(rf"= s32\[({nu}|{ni}|{views})\]\S* (copy|pad|fusion)\(",
                         _inside_loops(hlo))
