"""The ring's blocking as programs over the mesh
(``data/device_blocking.py::mesh_block_problem``) on the virtual CPU mesh:
its device-major layout, omegas and row maps equal ``device_block_problem``
followed by the transposes to device-major, bit for bit."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from large_scale_recommendation_tpu import obs
from large_scale_recommendation_tpu.data import device_blocking as db
from large_scale_recommendation_tpu.parallel import Partitioner


def _ids(rng, n, n_ids, skew):
    if skew is None:
        return rng.integers(0, n_ids, n)
    return np.minimum((rng.exponential(skew, n) * n_ids).astype(np.int64),
                      n_ids - 1)


def _bits(a):
    return np.asarray(a).tobytes()


def _one_chip_device_major(u, i, r, nu, ni, k, **kw):
    p = db.device_block_problem(u, i, r, nu, ni, num_blocks=k, **kw)
    t = lambda a: jnp.transpose(a, (1, 0, 2))  # noqa: E731
    return p, {"ru": t(p.su) % p.rows_per_block_u,
               "ri": t(p.si) % p.rows_per_block_v,
               "rv": t(p.sv), "rw": t(p.sw), "icu": t(p.icu), "icv": t(p.icv)}


def _assert_equal(m, p, want):
    for name, a in want.items():
        got = getattr(m, name)
        assert got.shape == a.shape, name
        assert _bits(got) == _bits(a), name
    for name in ("omega_u", "omega_v", "row_of_user", "row_of_item",
                 "id_of_user_row", "id_of_item_row"):
        assert _bits(getattr(m, name)) == _bits(getattr(p, name)), name
    assert (m.nnz, m.max_pad_ratio, m.minibatch) == (
        p.nnz, p.max_pad_ratio, p.minibatch)


# n, users, items, k, minibatch, seed, skew, weight-0 entries, sort side;
# user counts that k does not divide leave the last user block short
CASES = [
    (4099, 100, 80, 4, 64, 3, None, 0, None),
    (5000, 57, 33, 2, 32, 1, 0.3, 0, "item"),
    (20001, 301, 120, 4, 128, 7, 0.3, 37, "user"),
    (12000, 1003, 51, 4, 256, 11, 0.2, 0, "item"),
    (3001, 90, 7, 2, 16, 5, None, 300, None),
    (3, 5, 4, 4, 8, 0, None, 0, "item"),
    (777, 41, 39, 4, 32, 2, 0.5, 1, "user"),
]


@pytest.mark.parametrize("n,nu,ni,k,mb,seed,skew,pads,sort_side", CASES)
def test_equals_one_chip_blocking_bit_for_bit(n, nu, ni, k, mb, seed, skew,
                                              pads, sort_side):
    rng = np.random.default_rng(seed)
    u, i = _ids(rng, n, nu, skew), _ids(rng, n, ni, skew)
    r = rng.normal(0, 1, n).astype(np.float32)
    w = None
    if pads:
        w = np.ones(n, np.float32)
        w[rng.choice(n, pads, replace=False)] = 0.0
    kw = dict(minibatch_multiple=mb, seed=seed, minibatch_sort=sort_side,
              weights=w)
    m = db.mesh_block_problem(u, i, r, nu, ni, Partitioner(num_devices=k),
                              **kw)
    p, want = _one_chip_device_major(u, i, r, nu, ni, k, **kw)
    _assert_equal(m, p, want)
    # each chip holds its own row of the layout, nothing else
    assert m.ru.sharding.spec[0] is not None
    assert {s.data.shape for s in m.ru.addressable_shards} == {
        (1,) + m.ru.shape[1:]}


def test_a_share_of_one_hot_user_sends_unevenly_and_still_agrees():
    """The first half of the entries are one user's: the first two chips
    send all of theirs to one chip, the exchange's slots grow past the
    even share to the largest pair's count, and nothing is lost."""
    rng = np.random.default_rng(4)
    n, nu, ni, k = 6000, 200, 60, 4
    u = np.concatenate([np.zeros(n // 2, np.int64),
                        rng.integers(1, nu, n - n // 2)])
    i = rng.integers(0, ni, n)
    r = rng.normal(0, 1, n).astype(np.float32)
    m = db.mesh_block_problem(u, i, r, nu, ni, Partitioner(num_devices=k),
                              minibatch_multiple=32, seed=2)
    p, want = _one_chip_device_major(u, i, r, nu, ni, k,
                                     minibatch_multiple=32, seed=2)
    _assert_equal(m, p, want)
    even = db.exchange_slots(np.zeros((k, k), int), n // k, k)
    assert m.exchange_bytes == (k - 1) * (n // k) * 6 * 4 > (
        (k - 1) * even * 6 * 4)


# n, k: one, two and three rounds of the shuffle; n that k does not divide
SHUFFLES = [(1000, 4), (1001, 2), (100003, 4), (100003, 2), (3000001, 4)]


@pytest.mark.parametrize("n,k", SHUFFLES)
def test_sharded_shuffle_equals_permutation_bit_for_bit(n, k):
    """Each chip's places from ``_shuffle_places``, run as the per-chip
    program over the mesh, are its share of the inverse of
    ``jax.random.permutation`` with the blocking's key, and its rounds
    are the sorts ``permutation`` runs."""
    part = Partitioner(num_devices=k)
    q = -(-n // k)
    s = db.even_slots(q, k)
    key = jax.random.fold_in(jax.random.PRNGKey(5), 12)
    jaxpr = str(jax.make_jaxpr(
        lambda key: jax.random.permutation(key, n))(key))
    assert jaxpr.count(" sort[") == db.shuffle_rounds(n) == {
        1000: 1, 1001: 1, 100003: 2, 3000001: 3}[n]

    def places(key):
        rank, need = db._shuffle_places(key, n, q, s, part.data_axis, k)
        return rank, need[None]

    rank, need = jax.jit(jax.shard_map(
        places, mesh=part.mesh, in_specs=part.spec(),
        out_specs=(part.spec("ratings"),) * 2))(key)
    assert rank.shape == (k * q,)
    assert len(set(np.asarray(need))) == 1 and need[0] <= s
    perm = np.asarray(jax.random.permutation(key, n))
    inverse = np.empty(n, np.int32)
    inverse[perm] = np.arange(n, dtype=np.int32)
    for shard in rank.addressable_shards:
        lo = shard.index[0].start or 0
        got = np.asarray(shard.data)
        assert got.shape == (q,)
        assert _bits(got[:max(min(q, n - lo), 0)]) == _bits(
            inverse[lo:lo + q]), lo


def test_a_shuffle_round_that_outgrows_its_slots_runs_again():
    """Slots too small for a round lose entries; the bucket program runs
    again with twice the slots, the layout is the one-chip layout bit for
    bit, and the registry counts the rerun."""
    rng = np.random.default_rng(9)
    n, nu, ni, k = 5000, 80, 60, 4
    u, i = rng.integers(0, nu, n), rng.integers(0, ni, n)
    r = rng.normal(0, 1, n).astype(np.float32)
    registry, _ = obs.enable()
    try:
        # a pair's count is about n / k^2 = 312 in every round
        m = db.mesh_block_problem(u, i, r, nu, ni, Partitioner(num_devices=k),
                                  minibatch_multiple=32, seed=4,
                                  _shuffle_slots=256)
        got = _blocking_metrics(registry)
    finally:
        obs.disable()
    p, want = _one_chip_device_major(u, i, r, nu, ni, k,
                                     minibatch_multiple=32, seed=4)
    _assert_equal(m, p, want)
    assert got["blocking_shuffle_retries_total"] == {None: 1}
    # both passes looked every entry's two rows up
    assert got["blocking_lane_lookups_total"] == {None: 2 * 2 * n}


def test_entries_already_sharded_stay_where_they_are():
    part = Partitioner(num_devices=4)
    rng = np.random.default_rng(8)
    n, nu, ni = 4000, 90, 70
    u, i = rng.integers(0, nu, n), rng.integers(0, ni, n)
    r = rng.normal(0, 1, n).astype(np.float32)
    placed = [part.place(np.asarray(x, dt), "ratings") for x, dt in
              ((u, np.int32), (i, np.int32), (r, np.float32))]
    m = db.mesh_block_problem(*placed, nu, ni, part, minibatch_multiple=64,
                              seed=1, minibatch_sort="item")
    p, want = _one_chip_device_major(u, i, r, nu, ni, 4,
                                     minibatch_multiple=64, seed=1,
                                     minibatch_sort="item")
    _assert_equal(m, p, want)


def _blocking_metrics(registry):
    """``{name: {chip label (or None): value}}`` of the ``blocking_*``
    metrics on the registry."""
    got = {}
    for metric in registry.snapshot()["metrics"]:
        if metric["name"].startswith("blocking_"):
            got.setdefault(metric["name"], {})[
                metric["labels"].get("chip")] = metric["value"]
    return got


def test_exchange_on_the_registry():
    rng = np.random.default_rng(3)
    n, nu, ni = 5000, 80, 60
    u, i = rng.integers(0, nu, n), rng.integers(0, ni, n)
    r = rng.normal(0, 1, n).astype(np.float32)
    registry, _ = obs.enable()
    try:
        m = db.mesh_block_problem(u, i, r, nu, ni, Partitioner(num_devices=4),
                                  minibatch_multiple=32, seed=1)
        got = _blocking_metrics(registry)
    finally:
        obs.disable()
    # the shuffle's rounds fit their even slots: no rerun
    assert got.pop("blocking_shuffle_retries_total") == {None: 0}
    chips = ["0", "1", "2", "3"]
    # every chip sent (k - 1) pairs of slots of six 4-byte words
    assert got["blocking_exchange_bytes_total"] == {
        c: m.exchange_bytes for c in chips}
    assert m.exchange_bytes == 3 * db.exchange_slots(
        np.zeros((4, 4), int), n // 4, 4) * 6 * 4
    # after the exchange the chips hold every entry between them, each
    # its user block's: what its row of the layout has in real slots
    held = got["blocking_shard_entries"]
    assert sum(held.values()) == n
    rw = np.asarray(m.rw)
    assert held == {c: float((rw[int(c)] > 0).sum()) for c in chips}


def test_lane_lookups_on_the_registry():
    """Two id→row lookups an entry a chip's share holds, the zero-filled
    tail past ``n`` among them: ``k * q`` entries."""
    rng = np.random.default_rng(4)
    n, nu, ni, k = 4001, 70, 50, 4
    u, i = rng.integers(0, nu, n), rng.integers(0, ni, n)
    r = rng.normal(0, 1, n).astype(np.float32)
    registry, _ = obs.enable()
    try:
        db.mesh_block_problem(u, i, r, nu, ni, Partitioner(num_devices=k),
                              minibatch_multiple=32, seed=2)
        got = _blocking_metrics(registry)
    finally:
        obs.disable()
    assert got["blocking_lane_lookups_total"] == {None: 2 * k * 1001}


def test_mesh_fit_device_blocks_on_every_chip():
    """``MeshDSGD.fit_device`` hands the ring the sharded layout: no array
    of the fit is laid out on one chip first (no ``device_block_problem``
    call)."""
    from large_scale_recommendation_tpu.parallel import MeshDSGD
    from large_scale_recommendation_tpu.parallel.dsgd_mesh import (
        MeshDSGDConfig,
    )

    rng = np.random.default_rng(5)
    n, nu, ni = 3000, 60, 40
    u, i = rng.integers(0, nu, n), rng.integers(0, ni, n)
    r = rng.normal(0, 1, n).astype(np.float32)
    called = []
    real = db.device_block_problem
    db.device_block_problem = lambda *a, **k: called.append(1) or real(
        *a, **k)
    try:
        model = MeshDSGD(MeshDSGDConfig(num_factors=4, iterations=1,
                                        minibatch_size=64),
                         partitioner=Partitioner(num_devices=4)).fit_device(
            u, i, r, nu, ni)
    finally:
        db.device_block_problem = real
    assert not called
    assert len(model.U.sharding.device_set) == 4
    assert np.isfinite(np.asarray(model.U)).all()


def test_sharded_init_equals_the_one_chip_init():
    from large_scale_recommendation_tpu.parallel.dsgd_mesh import (
        sharded_init,
    )

    rng = np.random.default_rng(6)
    n, nu, ni = 2000, 50, 30
    u, i = rng.integers(0, nu, n), rng.integers(0, ni, n)
    r = rng.normal(0, 1, n).astype(np.float32)
    part = Partitioner(num_devices=4)
    m = db.mesh_block_problem(u, i, r, nu, ni, part, minibatch_multiple=32)
    U, V, ou, ov = sharded_init(part, m.id_of_user_row, m.id_of_item_row,
                                m.omega_u, m.omega_v, 8, 0.1)
    p = db.device_block_problem(u, i, r, nu, ni, num_blocks=4,
                                minibatch_multiple=32)
    U1, V1 = db.init_factors_device(p, 8, 0.1)
    assert _bits(U) == _bits(U1) and _bits(V) == _bits(V1)
    assert _bits(ou) == _bits(p.omega_u)
    assert len(U.sharding.device_set) == 4 and jax.device_count() >= 4
