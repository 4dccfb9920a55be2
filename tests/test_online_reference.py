"""The online stream against its plain reference, at a toy size on the CPU:
the program through ``StreamingDriver`` over a written ``EventLog`` against
``benchmark/reference/online_ref.py`` on seeded tables and ratings; the
in-place update against the copying one, bit for bit; and the tables'
rules that the deployment of PR 35 stands on (capacity, bounded installs,
who may hold a table across an update)."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import online_ref
from large_scale_recommendation_tpu import obs
from large_scale_recommendation_tpu.core.initializers import (
    PseudoRandomFactorInitializer,
)
from large_scale_recommendation_tpu.core.types import Ratings
from large_scale_recommendation_tpu.core.updaters import SGDUpdater
from large_scale_recommendation_tpu.data import tables
from large_scale_recommendation_tpu.data.tables import (
    GrowableFactorTable,
    capacity_for,
)
from large_scale_recommendation_tpu.models.online import (
    OnlineMF,
    OnlineMFConfig,
)
from large_scale_recommendation_tpu.ops import sgd as sgd_ops
from large_scale_recommendation_tpu.streams import (
    EventLog,
    StreamingDriver,
    StreamingDriverConfig,
)

NU, NI, RANK, MBR, MB = 300, 120, 8, 200, 32
CFG = {"learning_rate": 0.05, "minibatch_size": MB, "collision_mode": "mean"}


def _data(seed=0, batches=8):
    rng = np.random.default_rng(seed)
    n = batches * MBR
    # few ids, so that rows collide inside a minibatch
    u = rng.integers(0, NU, n).astype(np.int32)
    i = rng.integers(0, NI, n).astype(np.int32)
    r = rng.normal(size=n).astype(np.float32)
    U0 = (0.3 * rng.normal(size=(NU, RANK))).astype(np.float32)
    V0 = (0.3 * rng.normal(size=(NI, RANK))).astype(np.float32)
    return (u, i, r), (U0, V0)


def _model(tables0, collision="mean"):
    model = OnlineMF(OnlineMFConfig(
        num_factors=RANK, learning_rate=CFG["learning_rate"],
        minibatch_size=MB, collision_mode=collision))
    for table, host in zip((model.users, model.items), tables0):
        table.load_rows(table.ensure(np.arange(len(host))), host)
    return model


def _in_id_order(table, n):
    rows, found = table.rows_for(np.arange(n))
    assert found.all()
    with table.borrowed() as arr:
        return np.asarray(arr)[rows]


@pytest.fixture(scope="module")
def streamed(tmp_path_factory):
    """The program through the driver, with the tables after micro-batches
    1 and 8 (the driver's ``on_batch`` hook), and the reference's."""
    (u, i, r), tables0 = _data()
    directory = str(tmp_path_factory.mktemp("stream"))
    log = EventLog(os.path.join(directory, "log"), fsync=False)
    log.append_arrays(0, u, i, r)
    model = _model(tables0)
    seen = {}

    def on_batch(batch):
        n = len(seen) + 1
        seen[n] = (_in_id_order(model.users, NU),
                   _in_id_order(model.items, NI),
                   dict(model.consumed_offsets))

    driver = StreamingDriver(
        model, log, os.path.join(directory, "ckpt"),
        config=StreamingDriverConfig(batch_records=MBR,
                                     checkpoint_every=None),
        on_batch=on_batch)
    applied = driver.run()
    head = log.end_offset(0)
    log.close()
    ref = {}
    online_ref.follow(
        jnp.asarray(tables0[0]), jnp.asarray(tables0[1]),
        ((u[a:a + MBR], i[a:a + MBR], r[a:a + MBR])
         for a in range(0, len(u), MBR)), CFG,
        lambda b, U, V: ref.__setitem__(b, (np.asarray(U), np.asarray(V))))
    return {"applied": applied, "head": head, "driver": driver,
            "model": model, "seen": seen, "ref": ref, "tables0": tables0}


@pytest.mark.parametrize("batch", [1, 8])
@pytest.mark.parametrize("side", [0, 1])
def test_the_stream_matches_the_reference(streamed, batch, side):
    prog, ref = streamed["seen"][batch][side], streamed["ref"][batch][side]
    moved = np.linalg.norm(ref - streamed["tables0"][side])
    assert moved > 0.01
    # float32 rounding of another order of summation, nothing more
    assert np.linalg.norm(prog - ref) / moved < 1e-5


def test_offsets_and_count_are_the_logs(streamed):
    assert streamed["applied"] == 8
    assert streamed["driver"].records_processed == 8 * MBR
    assert streamed["head"] == 8 * MBR
    assert streamed["model"].consumed_offsets == {0: streamed["head"]}
    # stamped batch by batch, in log order, only once applied
    assert [streamed["seen"][b][2] for b in (1, 2, 8)] == [
        {0: MBR}, {0: 2 * MBR}, {0: 8 * MBR}]
    assert not os.listdir(streamed["driver"].manager.directory)


def test_the_reference_plants_its_fault_and_refuses_another():
    (u, i, r), (U0, V0) = _data(batches=1)

    def end(fault):
        U, V = online_ref.follow(jnp.asarray(U0), jnp.asarray(V0),
                                 [(u, i, r)], CFG, fault=fault)
        return np.asarray(U)

    whole, half = end(None), end("half_batch")
    assert np.linalg.norm(whole - half) > 0.2 * np.linalg.norm(whole - U0)
    with pytest.raises(ValueError, match="unknown fault"):
        end("nope")


@pytest.mark.parametrize("collision", ["mean", "sum"])
def test_the_reference_states_the_collision_rules(collision):
    """One minibatch by hand: a row hit three times moves by the mean (or
    the sum) of its three steps, each from the row as the minibatch found
    it."""
    U0 = np.array([[1.0, 2.0], [0.5, -1.0]], np.float32)
    V0 = np.array([[0.5, 0.25], [2.0, 1.0]], np.float32)
    u = np.array([0, 0, 0, 1], np.int32)
    i = np.array([0, 1, 1, 0], np.int32)
    r = np.array([1.0, -2.0, 0.5, 3.0], np.float32)
    cfg = dict(CFG, minibatch_size=4, collision_mode=collision)
    U, V = online_ref.follow(jnp.asarray(U0), jnp.asarray(V0), [(u, i, r)],
                             cfg)
    e = r - np.sum(U0[u] * V0[i], axis=1)
    steps = cfg["learning_rate"] * e[:, None] * V0[i]
    want = U0[0] + (steps[:3].mean(0) if collision == "mean"
                    else steps[:3].sum(0))
    np.testing.assert_allclose(np.asarray(U)[0], want, rtol=1e-6)


# -- in place against copying ------------------------------------------------


def _padded(seed=3):
    (u, i, r), (U0, V0) = _data(seed, batches=1)
    ur, ir, vals, w = sgd_ops.pad_minibatches(u, i, r, MB)
    return (U0, V0), tuple(jnp.asarray(x) for x in (ur, ir, vals, w))


@pytest.mark.parametrize("collision", ["mean", "sum"])
def test_the_in_place_update_is_the_copying_update_bit_for_bit(collision):
    (U0, V0), staged = _padded()
    kw = dict(updater=SGDUpdater(learning_rate=0.05), minibatch=MB,
              iterations=1, collision=collision)
    U1, V1 = sgd_ops.online_train(jnp.asarray(U0), jnp.asarray(V0),
                                  *staged, **kw)
    Ud, Vd = jnp.asarray(U0), jnp.asarray(V0)
    U2, V2 = sgd_ops.online_train_inplace(Ud, Vd, *staged, **kw)
    assert np.array_equal(np.asarray(U1), np.asarray(U2))
    assert np.array_equal(np.asarray(V1), np.asarray(V2))
    assert not np.array_equal(np.asarray(U1), U0)
    # the donated tables are gone: the update took them
    assert Ud.is_deleted() and Vd.is_deleted()


def test_partial_fit_updates_in_place_and_a_snapshot_is_a_copy(null_obs):
    reg, _ = obs.enable()
    try:
        (u, i, r), tables0 = _data(5, batches=4)
        model = _model(tables0)
        twin = _model(tables0)

        def batch(k):
            a = slice(k * MBR, (k + 1) * MBR)
            return Ratings.from_arrays(u[a], i[a], r[a])

        model.partial_fit(batch(0), emit_updates=False)
        with model.users.borrowed() as U, model.items.borrowed() as V:
            live = (U, V)                  # kept past the block: must die
        held = model.users.array           # a poller's snapshot
        before = np.asarray(held).copy()
        model.partial_fit(batch(1), emit_updates=False)
        # the update took the live arrays themselves ...
        assert live[0].is_deleted() and live[1].is_deleted()
        # ... the snapshot is whole and unchanged, the live table moved on
        assert np.array_equal(np.asarray(held), before)
        with model.users.borrowed() as now:
            assert not np.array_equal(np.asarray(now), before)
        # scoring the live model reads in place and breaks nothing
        model.rmse(batch(3))
        model.predict(u[:5], i[:5])
        model.partial_fit(batch(2), emit_updates=False)
        model.partial_fit(batch(3), emit_updates=False)
        assert np.array_equal(np.asarray(held), before)
        assert reg.gauge("online_table_bytes", side="users").value == (
            model.users.capacity * RANK * 4)
        # a model polled between all its batches ends the same bit for bit
        for k in range(4):
            twin.users.array, twin.items.array
            twin.partial_fit(batch(k), emit_updates=False)
        assert np.array_equal(_in_id_order(model.users, NU),
                              _in_id_order(twin.users, NU))
        assert np.array_equal(_in_id_order(model.items, NI),
                              _in_id_order(twin.items, NI))
    finally:
        obs.disable()


def test_pollers_on_other_threads_never_see_a_donated_table():
    """Readers snapshot ``.array`` and score the live model while the
    stream applies batches: no reader ever touches a deleted buffer, every
    snapshot it holds stays whole, and the tables end as a run without
    readers leaves them."""
    import sys
    import threading

    (u, i, r), tables0 = _data(7, batches=24)
    model, alone = _model(tables0), _model(tables0)
    stop, errors, polls = threading.Event(), [], [0]

    def poll():
        try:
            while not stop.is_set():
                snap = model.users.array
                total = float(jnp.sum(snap))        # the buffer is alive
                model.rmse(Ratings.from_arrays(u[:50], i[:50], r[:50]))
                assert float(jnp.sum(snap)) == total  # and unchanged
                polls[0] += 1
        except BaseException as e:  # noqa: BLE001 — reported below
            errors.append(e)
            raise

    readers = [threading.Thread(target=poll) for _ in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in readers:
            t.start()
        for k in range(24):
            a = slice(k * MBR, (k + 1) * MBR)
            batch = Ratings.from_arrays(u[a], i[a], r[a])
            model.partial_fit(batch, emit_updates=False)
            alone.partial_fit(batch, emit_updates=False)
    finally:
        stop.set()
        for t in readers:
            t.join(timeout=60)
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in readers)
    assert not errors, errors
    assert polls[0] > 0
    assert np.array_equal(_in_id_order(model.users, NU),
                          _in_id_order(alone.users, NU))
    assert np.array_equal(_in_id_order(model.items, NI),
                          _in_id_order(alone.items, NI))


# -- the tables' rules -------------------------------------------------------


def _init(rank=4):
    return PseudoRandomFactorInitializer(rank, scale=0.1)


@pytest.mark.parametrize("need,rank,current,want", [
    (1000, 10, 0, 1024),              # small: the next power of two
    (5, 10, 0, 8),
    (1 << 20, 16, 0, 1 << 20),        # 64 MiB exactly: still a power of two
    (2_500_000, 512, 0, 2_500_000),   # large: the rows asked for
    (2_500_001, 512, 0, 2_500_008),   # ... to the sublane tile
    (1_048_576, 512, 0, 1_048_576),
    (2_500_100, 512, 2_500_000, 2_812_504),  # a trickle: an eighth over
    (4_000_000, 512, 2_500_000, 4_000_000),  # a bulk need: exactly
])
def test_capacity_for(need, rank, current, want):
    assert capacity_for(need, rank, current) == want


def test_2500_ids_take_their_tile_rounded_rows_once_the_rule_applies(
        monkeypatch):
    small = GrowableFactorTable(_init(), capacity=8)
    small.ensure(np.arange(2500))
    assert small.capacity == 4096          # a small table: a power of two
    monkeypatch.setattr(tables, "STEP_BYTES", 4096)  # now 2,500 x 16 B is large
    for table in (GrowableFactorTable(_init(), capacity=2500),
                  GrowableFactorTable(_init(), capacity=8)):
        rows = table.ensure(np.arange(2500))
        assert table.capacity == 2504 and table.device_bytes == 2504 * 16
        assert np.array_equal(rows, np.arange(2500))
        # same id, same vector, however the install was cut up
        assert np.array_equal(table.lookup(np.arange(2500)),
                              small.lookup(np.arange(2500)))


def test_a_bulk_registration_stages_a_step_at_a_time(monkeypatch):
    monkeypatch.setattr(tables, "STEP_BYTES", 4096)
    table = GrowableFactorTable(_init(), capacity=8)
    staged = []
    install = table._install
    monkeypatch.setattr(table, "_install", lambda fresh, base: (
        staged.append(tuple(fresh.shape)), install(fresh, base)))
    ids = np.random.default_rng(0).permutation(5000)[:1000].astype(np.int64)
    rows = table.ensure(ids)
    assert staged == [(256, 4)] * 3 + [(232, 4)]   # 4096 B a step, the tail exact
    assert np.array_equal(rows, np.arange(1000))   # first-seen order
    assert table.capacity == 1000 and table.num_rows == 1000
    ref = GrowableFactorTable(_init(), capacity=8)
    monkeypatch.setattr(tables, "STEP_BYTES", 64 << 20)
    ref.ensure(ids)
    assert np.array_equal(table.lookup(ids), ref.lookup(ids))
    # a later trickle grows by an eighth, not to the next power of two
    monkeypatch.setattr(tables, "STEP_BYTES", 4096)
    table.ensure(np.array([9999]))
    assert table.capacity == capacity_for(1000 + 8, 4, 1000) == 1128


@pytest.mark.parametrize("spread", [1, 1000])
def test_a_large_lookup_equals_the_small_ones(spread):
    """One call of ``rows_for`` gives the rows and the misses of one search
    an id, by address (``spread`` 1: dense ids) and by the sorted index
    (1,000: sparse ones)."""
    table = GrowableFactorTable(_init(), capacity=8)
    rng = np.random.default_rng(4)
    table.ensure(rng.permutation(50_000)[:40_000] * spread)
    assert (table._direct_index() is not None) == (spread == 1)
    ids = rng.integers(-5, 60_000, 5000) * spread   # hits, misses, repeats
    rows, found = table.rows_for(ids)
    want = {int(x): k for k, x in enumerate(table.id_array())}
    assert rows.tolist() == [want.get(int(q), 0) for q in ids]
    parts = [table.rows_for(ids[a:a + 500]) for a in range(0, 5000, 500)]
    assert np.array_equal(rows, np.concatenate([p[0] for p in parts]))
    assert np.array_equal(found, np.concatenate([p[1] for p in parts]))
    assert 0 < found.sum() < 5000


def test_dense_ids_are_looked_up_by_address_and_sparse_ones_searched():
    rng = np.random.default_rng(6)
    dense = GrowableFactorTable(_init(), capacity=8)
    dense.ensure(rng.permutation(3000)[:2000])
    assert dense._direct_index() is not None
    sparse = GrowableFactorTable(_init(), capacity=8)
    sparse.ensure(np.array([5, 10**12, 77, 2**40]))
    assert sparse._direct_index() is None
    signed = GrowableFactorTable(_init(), capacity=8)
    signed.ensure(np.array([3, -1, 9]))
    assert signed._direct_index() is None
    queries = np.concatenate([rng.integers(-3, 4000, 3000), [10**12, -1]])
    for table in (dense, sparse, signed):
        ids = table.id_array()
        want = {int(x): r for r, x in enumerate(ids)}
        rows, found = table.rows_for(queries)
        assert rows.dtype == np.int64 and found.dtype == np.float32
        assert [int(r) for r in rows] == [want.get(int(q), 0)
                                          for q in queries]
        assert [bool(f) for f in found] == [int(q) in want for q in queries]
    # fresh ids keep the address table current while they fit, and a
    # stream that turns sparse falls back to the search
    table_before = dense._direct[0]
    rows = dense.ensure(np.array([3500, 3501, 7]))
    assert dense._direct[0] is table_before and dense._direct[1] == (
        dense.num_rows)
    assert np.array_equal(dense.rows_for(np.array([3500, 3501]))[0],
                          rows[:2])
    dense.ensure(np.array([10**9]))
    assert dense._direct_index() is None
    assert dense.rows_for(np.array([10**9, 3500]))[1].tolist() == [1.0, 1.0]


def test_load_rows_in_steps_equals_one_scatter(monkeypatch):
    values = np.random.default_rng(1).normal(size=(700, 4)).astype(
        np.float32)
    rows = np.random.default_rng(2).permutation(1024)[:700]
    one = GrowableFactorTable(_init(), capacity=1024)
    one.load_rows(rows, values)
    monkeypatch.setattr(tables, "STEP_BYTES", 4096)
    cut = GrowableFactorTable(_init(), capacity=1024)
    cut.load_rows(rows, values)
    with one.borrowed() as a, cut.borrowed() as b:
        assert np.array_equal(np.asarray(a), np.asarray(b))
        assert np.array_equal(np.asarray(b)[rows], values)


def test_a_snapshot_is_a_copy_and_every_mutation_is_in_place():
    table = GrowableFactorTable(_init(), capacity=64)
    table.ensure(np.arange(10))
    held = table.array                     # a copy, the caller's to keep
    before = np.asarray(held).copy()
    with table.borrowed() as live:
        live_ref = live                    # kept past the block: must die
    table.ensure(np.arange(10, 40))        # an install: in place
    assert live_ref.is_deleted()
    table.load_rows(np.arange(3), np.ones((3, 4), np.float32))
    assert not held.is_deleted()
    assert np.array_equal(np.asarray(held), before)
    assert np.array_equal(table.lookup(np.arange(3)), np.ones((3, 4)))
    assert np.array_equal(np.asarray(table.snapshot_rows(64))[3:10],
                          before[3:10])
    # a whole-table capture is an array of its own too
    whole = table.snapshot_rows(64)
    table.load_rows(np.arange(2), np.zeros((2, 4), np.float32))
    assert not whole.is_deleted() and float(whole[0, 0]) == 1.0
    # an array assigned from outside is the table's from then on
    mine = jnp.zeros((64, 4), jnp.float32)
    table.array = mine
    table.load_rows(np.arange(2), np.ones((2, 4), np.float32))
    assert mine.is_deleted()
    assert np.array_equal(table.lookup(np.arange(2)), np.ones((2, 4)))


def test_growth_allocates_the_new_table_alone():
    table = GrowableFactorTable(_init(), capacity=8)
    table.ensure(np.arange(8))
    before = table.lookup(np.arange(8))
    held = table.array
    table.ensure(np.arange(8, 40))
    assert table.capacity == 64 and not held.is_deleted()
    assert np.array_equal(table.lookup(np.arange(8)), before)
    assert jax.block_until_ready(table.array).shape == (64, 4)
