"""Tiered factor store (``store/``, ISSUE 17): the host-RAM cold tier
behind a fixed-capacity device slot pool.

The pinned invariant everything here defends: tiered training and
serving are BIT-EXACT with the untiered baseline at ANY slot capacity
that fits the concurrently pinned working set — the tier moves bytes,
never values. Covered: bit-exactness at {∞, ~2×, ~1.1×} of the
per-batch working set (evictions active at the small capacities), the
async prefetcher racing the trainer, N=2 row-disjoint concurrent
applies with eviction write-back under both threads, kill/restart with
a dirty slot pool, the mmap-backed cold tier, read-only serving
gathers, the overcommit guard (with no leaked pins), and the STORE obs
surface (/storez, bundle freeze, MonotonicGrowthCheck wiring).
"""

import json
import os
import threading

import numpy as np
import pytest

from large_scale_recommendation_tpu import obs
from large_scale_recommendation_tpu.core.initializers import (
    PseudoRandomFactorInitializer,
)
from large_scale_recommendation_tpu.core.types import Ratings
from large_scale_recommendation_tpu.models.online import (
    OnlineMF,
    OnlineMFConfig,
)
from large_scale_recommendation_tpu.store import (
    StorePrefetcher,
    TieredFactorStore,
)
from large_scale_recommendation_tpu.utils.checkpoint import (
    CheckpointManager,
    restore_online_state,
    save_online_state,
)

RANK = 4


@pytest.fixture(autouse=True)
def _reset_store_plane():
    """Construction installs the store as the process STORE plane —
    never leak a test's store into the next test."""
    from large_scale_recommendation_tpu.obs.store import (
        get_store,
        set_store,
    )

    prev = get_store()
    yield
    set_store(prev)


def _tiered_users(cfg, slots, capacity=64, mmap_dir=None):
    # the EXACT initializer OnlineMF builds — same per-id pseudo-random
    # rows, so tiered-vs-plain diffs can only come from the tier itself
    return TieredFactorStore(
        PseudoRandomFactorInitializer(cfg.num_factors,
                                      scale=cfg.init_scale),
        capacity=capacity, slot_capacity=slots, mmap_dir=mmap_dir)


def _model(slots=None, mmap_dir=None, minibatch=32):
    cfg = OnlineMFConfig(num_factors=RANK, minibatch_size=minibatch)
    m = OnlineMF(cfg)
    if slots is not None:
        m.users = _tiered_users(m.config, slots, mmap_dir=mmap_dir)
    return m


def _batches(n_batches=8, users=100, per_batch_users=30, items=24,
             seed=0):
    """Each batch touches EXACTLY ``per_batch_users`` distinct users
    (2 ratings each) out of a universe ``slot_capacity`` can't hold —
    small pools must evict between batches yet stay exact."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_batches):
        uu = rng.permutation(users)[:per_batch_users]
        u = np.repeat(uu, 2).astype(np.int64)
        i = rng.integers(0, items, u.size).astype(np.int64)
        out.append(Ratings.from_arrays(
            u, i, rng.random(u.size).astype(np.float32)))
    return out


def _train(m, batches, **kw):
    for b in batches:
        m.partial_fit(b, emit_updates=False, **kw)
    return m


def _table(m):
    """Registered user rows only — a plain table's ``full_table`` is
    its whole (pow2-capacity) array, a tiered store's is its own
    capacity; the comparable region is the first ``num_rows``."""
    return np.asarray(m.users.full_table())[: m.users.num_rows]


# --------------------------------------------------------------------------
# Bit-exactness across capacities
# --------------------------------------------------------------------------


class TestBitExactness:
    def test_tiered_matches_untiered_at_every_capacity(self):
        """∞ (pool ≥ whole table), ~2× and ~1.1× the 30-row per-batch
        working set. The small pools evict and write back constantly;
        the final tables, predictions and RMSE must still be
        byte-identical to the plain GrowableFactorTable run."""
        batches = _batches()
        probe_u, probe_i = [3, 50, 97], [1, 11, 23]
        base = _train(_model(), batches)
        U0 = _table(base)
        p0 = np.asarray(base.predict(probe_u, probe_i))
        r0 = base.rmse(batches[0])

        for slots in (128, 64, 32):
            m = _train(_model(slots=slots), batches)
            st = m.users
            assert isinstance(st, TieredFactorStore)
            assert st.num_rows == base.users.num_rows
            np.testing.assert_array_equal(_table(m), U0)
            np.testing.assert_array_equal(
                np.asarray(m.predict(probe_u, probe_i)), p0)
            assert m.rmse(batches[0]) == r0
            # pins all returned, accounting consistent
            snap = st.snapshot()
            assert snap["hot"]["pinned"] == 0
            assert st.stats.hits + st.stats.misses > 0
            if slots < 100:  # universe is 100 rows: eviction forced
                assert st.stats.evictions > 0
                assert st.stats.writebacks > 0

    def test_prefetcher_racing_trainer_stays_bit_exact(self):
        """The async worker stages each NEXT batch's ids while the
        trainer runs the current one — lookahead changes hit rate,
        never values."""
        batches = _batches()
        base = _train(_model(), batches)
        U0 = _table(base)

        m = _model(slots=32)
        pf = StorePrefetcher(m.users).start()
        try:
            for k, b in enumerate(batches):
                if k + 1 < len(batches):
                    pf.submit(np.unique(b.users))  # announce lookahead
                m.partial_fit(b, emit_updates=False)
            pf.drain()
        finally:
            pf.stop()
        np.testing.assert_array_equal(_table(m), U0)
        assert pf.submitted > 0
        assert m.users.stats.prefetched >= 0  # best-effort plane

    def test_prefetch_hits_cut_demand_misses(self):
        """Sequential control: announce a KNOWN batch, drain, THEN
        acquire — every acquire is a hit and the demand path faults
        nothing."""
        cfg = OnlineMFConfig(num_factors=RANK)
        st = _tiered_users(cfg, slots=32)
        ids = np.arange(20)
        st.ensure(ids)  # register: rows land cold, not resident
        st.prefetch(ids)
        assert st.stats.prefetched == 20
        assert st.stats.misses == 0  # prefetch is not demand traffic
        rows = st.acquire_rows(ids)
        st.release_rows(rows)
        assert st.stats.hits == 20
        assert st.stats.misses == 0
        assert st.stats.hit_rate == 1.0

    def test_prefetch_never_registers_ids(self):
        """id→row assignment is FIRST-SEEN order and belongs to the
        training path alone: a prefetcher announcing unregistered ids
        (it sees batch N+1 while batch N trains, in np.unique-sorted
        order) must drop them, or a tiered run's vocabulary would be
        a permutation of the untiered run's — per-id values equal,
        row-for-row tables NOT (the exact failure the WAL-driven
        bench first exposed)."""
        cfg = OnlineMFConfig(num_factors=RANK)
        st = _tiered_users(cfg, slots=32)
        assert st.prefetch(np.arange(50, 70)) == 0  # all unknown: no-op
        assert st.num_rows == 0
        assert st.stats.prefetched == 0
        # training then assigns rows in ITS order, unperturbed
        rows = st.acquire_rows(np.asarray([60, 55, 50]))
        st.release_rows(rows)
        r, found = st.rows_for(np.asarray([60, 55, 50]))
        assert (found > 0).all()
        np.testing.assert_array_equal(r, [0, 1, 2])
        # fresh first-seen registrations are installs, not tier misses
        assert st.stats.installs == 3
        assert st.stats.misses == 0
        assert st.stats.hit_rate == 1.0


# --------------------------------------------------------------------------
# Concurrent applies with eviction write-back
# --------------------------------------------------------------------------


class TestConcurrentEviction:
    def _streams(self, n_parts=2, n_batches=6, seed=0):
        """Row-disjoint streams: thread p's users ≡ p (mod 2), items in
        block p. 16 distinct users per batch per thread — both pinned
        sets fit a 32-slot pool together, while the 100-user universe
        forces evictions."""
        rng = np.random.default_rng(seed)
        streams = []
        for p in range(n_parts):
            bs = []
            for _ in range(n_batches):
                uu = rng.choice(50, 16, replace=False) * n_parts + p
                u = np.repeat(uu, 4).astype(np.int64)
                i = (rng.integers(0, 12, u.size) + p * 12).astype(
                    np.int64)
                bs.append(Ratings.from_arrays(
                    u, i, rng.random(u.size).astype(np.float32)))
            streams.append(bs)
        return streams

    def test_n2_disjoint_threads_match_serial_bitexact(self):
        """The Gemulla pin composed with the tier: row-disjoint applies
        commute AND the slot pool under both threads evicts/writes back
        without tearing either stratum."""
        from large_scale_recommendation_tpu.streams.parallel import (
            RowConflictGate,
        )

        streams = self._streams()
        serial = _model(slots=32)
        for bs in streams:
            for b in bs:
                serial.partial_fit(b, emit_updates=False)

        conc = _model(slots=32)
        conc.enable_concurrent_applies()
        conc.apply_gate = RowConflictGate()
        errs = []

        def consume(bs):
            try:
                for b in bs:
                    conc.partial_fit(b, emit_updates=False)
            except BaseException as e:  # surfaced below
                errs.append(e)

        threads = [threading.Thread(target=consume, args=(bs,))
                   for bs in streams]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs, errs
        assert conc.step == serial.step
        assert conc.users.stats.evictions > 0  # the race we're pinning
        # align by id: registration order differs across interleavings
        for side in ("users", "items"):
            st, ct = getattr(serial, side), getattr(conc, side)
            ids = np.sort(st.id_array())
            np.testing.assert_array_equal(ids, np.sort(ct.id_array()))
            np.testing.assert_array_equal(st.lookup(ids),
                                          ct.lookup(ids))
        assert conc.users.snapshot()["hot"]["pinned"] == 0


# --------------------------------------------------------------------------
# Kill/restart with a dirty slot pool
# --------------------------------------------------------------------------


class TestKillRestart:
    def test_restart_with_dirty_pool_resumes_bit_exact(self, tmp_path):
        """Checkpoint mid-stream while the pool holds dirty slots, then
        'crash': a fresh process restores, re-warms the snapshot's hot
        set, and finishing the stream lands byte-identical to the
        uninterrupted run."""
        batches = _batches()
        full = _train(_model(slots=32), batches)
        U_full = _table(full)

        m = _train(_model(slots=32), batches[:5],
                   offset=(0, 5))
        assert m.users.dirty_rows().size > 0  # pool dirty at capture
        mgr = CheckpointManager(str(tmp_path))
        save_online_state(mgr, m, step=5)

        fresh = _model(slots=32)
        ck = restore_online_state(mgr, fresh)
        assert fresh.consumed_offsets == {0: 5}
        np.testing.assert_array_equal(_table(fresh), _table(m))
        # the snapshot's resident set came back hot
        assert set(fresh.users.resident_rows()) == \
            set(m.users.resident_rows())
        assert ck.meta["step"] == 5

        _train(fresh, batches[5:])
        np.testing.assert_array_equal(_table(fresh), U_full)

    def test_tiered_checkpoint_restores_into_plain_model(self, tmp_path):
        """Cross-compat both ways: the tier is a storage detail, not a
        format — a tiered snapshot restores into an untiered model (and
        the tables agree) because rows are the same first-seen order."""
        m = _train(_model(slots=32), _batches(n_batches=4))
        mgr = CheckpointManager(str(tmp_path))
        save_online_state(mgr, m, step=4)

        plain = _model()
        restore_online_state(mgr, plain)
        np.testing.assert_array_equal(_table(plain), _table(m))


# --------------------------------------------------------------------------
# Cold-tier backing, serving, guards
# --------------------------------------------------------------------------


class TestColdTierAndServing:
    def test_mmap_backed_cold_tier_is_bit_exact(self, tmp_path):
        batches = _batches(n_batches=5)
        base = _train(_model(), batches)
        m = _train(_model(slots=32, mmap_dir=str(tmp_path)), batches)
        np.testing.assert_array_equal(_table(m), _table(base))
        assert any(f.startswith("cold_") for f in os.listdir(tmp_path))
        assert m.users.snapshot()["cold"]["mmap"] is True

    def test_serve_rows_merges_hot_and_cold_readonly(self):
        """Serving gathers hot rows from the pool and cold rows from
        the host tier WITHOUT admitting them — the resident set (and
        training's working set) is untouched by a serve scan."""
        m = _train(_model(slots=32), _batches(n_batches=5))
        st = m.users
        resident_before = set(st.resident_rows())
        n = st.num_rows
        rows = np.arange(n)
        got = np.asarray(st.serve_rows(rows))
        np.testing.assert_array_equal(got,
                                      np.asarray(st.full_table())[:n])
        assert set(st.resident_rows()) == resident_before
        assert st.stats.serve_hits + st.stats.serve_misses == n
        assert st.stats.serve_misses > 0  # 100-row scan over 32 slots

    def test_overcommit_raises_with_accounting_and_no_leaked_pins(self):
        cfg = OnlineMFConfig(num_factors=RANK)
        st = _tiered_users(cfg, slots=8)
        with pytest.raises(RuntimeError, match="overcommitted"):
            st.acquire_rows(np.arange(20))
        # a raising acquire must leak no refcounts: everything it
        # pinned on the way in is unpinned on the way out
        assert st.snapshot()["hot"]["pinned"] == 0
        rows = st.acquire_rows(np.arange(8))  # pool-sized batch: fine
        st.release_rows(rows)
        assert st.snapshot()["hot"]["pinned"] == 0


# --------------------------------------------------------------------------
# STORE obs surface
# --------------------------------------------------------------------------


class TestStoreObs:
    def test_storez_route_and_index(self, null_obs):
        from large_scale_recommendation_tpu.obs.server import (
            ObsServer,
            http_get,
        )

        obs.enable()
        m = _train(_model(slots=32), _batches(n_batches=3))
        with ObsServer() as server:
            code, body = http_get(server.url + "/storez")
            icode, ibody = http_get(server.url + "/")
        assert code == 200
        doc = json.loads(body)
        assert doc["hot"]["slot_capacity"] == 32
        assert doc["cold"]["rows"] == m.users.num_rows
        assert doc["stats"]["hits"] + doc["stats"]["misses"] > 0
        assert "/storez" in json.loads(ibody)["routes"]

    def test_storez_without_store_is_a_note(self, null_obs):
        from large_scale_recommendation_tpu.obs.server import (
            ObsServer,
            http_get,
        )

        obs.enable()
        with ObsServer() as server:
            code, body = http_get(server.url + "/storez")
        assert code == 200
        assert "no tiered store" in json.loads(body)["note"]

    def test_bundle_freezes_store_and_monitor_watches_host_bytes(
            self, null_obs, tmp_path):
        """One v5 bundle carries store.json; the registry gauges the
        store publishes auto-sample into the recorder, and
        watch_store_memory gates tier_host_bytes growth on them."""
        from large_scale_recommendation_tpu.obs.health import (
            HealthMonitor,
        )
        from large_scale_recommendation_tpu.obs.recorder import (
            get_recorder,
            load_bundle,
            write_bundle,
        )

        obs.enable()
        obs.enable_flight_recorder(interval_s=0.05)
        rec = get_recorder()
        try:
            m = _train(_model(slots=32), _batches(n_batches=3))
            rec.sample()
            assert any(s.startswith("tier_host_bytes")
                       for s in rec.series_names())
            mon = HealthMonitor()
            mon.watch_store_memory(rec)
            report = mon.run()
            assert report["checks"]["store_memory"]["status"] == "ok"

            out = write_bundle(str(tmp_path), trigger="test")
            doc = load_bundle(out)
            # the store plane arrived at bundle v7; later planes bump it
            assert doc["manifest"]["bundle_version"] >= 7
            assert doc["store"]["hot"]["slot_capacity"] == 32
            assert doc["store"]["cold"]["rows"] == m.users.num_rows
        finally:
            obs.disable()

    def test_disable_resets_store_plane(self, null_obs):
        from large_scale_recommendation_tpu.obs.store import get_store

        obs.enable()
        _model(slots=32)
        assert get_store() is not None
        obs.disable()
        assert get_store() is None
