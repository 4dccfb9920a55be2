"""Durable-ingest micro-bench: ratings/s through log→queue→online_train.

The streaming acceptance number for the ingest runtime (``streams/``):
the SAME micro-batch stream driven two ways —

- **bare**: ``OnlineMF.partial_fit`` straight off in-memory batches —
  the demo loop the repo had before the durable tier existed. Fast, and
  a crash loses everything since the last factor snapshot.
- **durable**: the full ``StreamingDriver`` path — fsync-less event-log
  appends (fsync is a knob; CI machines' fsync latency would measure
  the disk, not the runtime), ``LogTailSource`` offset-stamped reads
  through the bounded backpressure queue, per-batch (U, V, offset)
  checkpoints, crash-recoverable by contract.

``value`` is the durable path's ratings/s; ``vs_baseline`` is
durable/bare — the *throughput retention* of durability (1.0 = free;
~1.0 measured on CPU at default sizes, where the queue overlaps host
batch prep with device compute). tests/test_bench_contract.py pins the
JSON contract structurally; the retention number itself is bench-round
evidence (``streams_ingest_vs_bare``), not a CI gate. The log-append
leg is also timed alone (``log_append_ratings_per_s``).

**N_CONSUMERS mode** (``STREAMS_CONSUMERS=1,2,4,8``): the parallel
ingest round (``INGEST_r*.json``, ISSUE 13) — STRONG scaling: for each
N on the curve, the SAME fixed-universe workload (``STREAMS_USERS`` ×
``STREAMS_ITEMS``, ``STREAMS_BATCHES`` total micro-batches) is
stratum-routed across an N-partition WAL (partition p's users ≡ p mod
N, its items in block p — the Gemulla row-disjointness the concurrent
applies exploit; the model geometry is IDENTICAL at every N, so the
curve measures parallelism, not table growth) and drained by a
``ParallelIngestRunner`` with N consumers; the headline is sustained
aggregate ratings/s at the largest N, ``vs_baseline`` the speedup over
N=1, and ``scaling_eff_n<K>`` = rate_K / (K · rate_1) the scaling
efficiency the ``--family ingest`` gate watches. The round also measures
recovery-after-kill at the largest N (one consumer crashes mid-stream
with partitions at different offsets; a fresh runner resumes from the
cross-partition barrier snapshot and re-drains — ``recovery_s``, with
the per-partition duplicate window in batches) and a sustained
follow-mode pass with lineage + critical-path armed
(``freshness_slo_held``: the ingest→serve ``FreshnessCheck`` stayed
green under continuous N-consumer write load;
``critical_path_partitions``: ``/criticalpathz`` samples resolved for
every partition). Machines with fewer cores than N cap thread scaling
at ~min(N, cores); the result carries an explicit ``error`` caveat
when that happens so cross-machine gating reads it.

Contract: the LAST stdout line is one JSON object
``{"metric", "value", "unit", "vs_baseline", "extra"}``, emitted after
a stderr flush (the bench.py/serving_bench hardening, so 2>&1-merged
wrappers always parse the last line).

Every result header stamps ``cpu_count`` and ``jax_platforms`` (the
round's machine identity — cross-machine gating must read them), the
1-core ``error`` caveat auto-emits whenever ``cores < max(N_CONSUMERS)``,
and each scaling rung runs with the contention plane armed
(``obs.enable_contention``): ``serial_fraction_n<K>`` (the Karp–Flatt
Amdahl estimate over the rung's window, N>1 rungs) and
``lock_wait_s_total_n<K>`` extras say WHERE a flat curve's headroom
went (ISSUE 14 — the ``--family ingest`` gate watches them as
lower-is-better via direction rules). The sustained pass serves
``/contentionz`` over a real socket and dumps the body to
``STREAMS_CONTENTION_OUT`` (the CI smoke's structural-assert artifact).

**TIERED mode** (``STREAMS_TIER_SLOTS=8192``): the tiered-factor-store
round (``TIERED_r*.json``, ISSUE 17) — the SAME bounded-Zipf WAL
stream (rank-weighted ``r^-s`` ids over a 1M universe) driven all-HBM
and through a ``TieredFactorStore`` whose device slot pool holds a
fraction of the user table (default geometry: ~36k realized rows over
8k slots, a ≥4× simulated device budget), with the driver's feeder
queue announcing batches to the async prefetcher two ahead (short
lead measured best: staged rows survive to their acquire and
not-yet-registered ids are exactly the ones LRU still holds).
``value`` is the tiered path's ratings/s, ``vs_baseline`` the
throughput retention vs all-HBM, and the round hard-checks the pinned
invariant end-to-end: final user tables AND both engines' served top-K
(the tiered engine gather-on-miss through ``user_store``) must be
bit-identical. Extras carry the tier's report card
(``tier_hit_rate``, ``tier_prefetch_wait_s``, ``tier_evictions``,
``tier_host_bytes``, serve hit/miss split) — the ``--family tier``
gate's keys. The simulated-budget caveat is ALWAYS stamped in
``error``: the slot pool caps rows on a CPU host, so the overhead is
real but HBM pressure is not.

Every mode stamps ``retrace_total`` / ``implicit_transfers_total``
from the transfer plane (``obs.transfers``, ISSUE 18) into the result
header, measured over the round's post-warmup streamed phase (the
ledger resets at each warm/stream boundary — steady state should be
ZERO on both). TIERED mode additionally stamps measured per-site
transfer GB/s for both legs (h2d stage-in sites like
``transfer_store_prefetch_gbs``, the d2h
``transfer_store_writeback_gbs`` leg) plus the h2d/d2h byte totals —
honest on CPU: the rates price the host↔"device" copy machinery on
this backend, not a real PCIe/ICI link (the simulated-budget caveat
above still rides ``error``).

Env knobs: STREAMS_USERS, STREAMS_ITEMS, STREAMS_RANK, STREAMS_BATCHES,
STREAMS_BATCH (records per micro-batch), STREAMS_CHECKPOINT_EVERY,
STREAMS_FSYNC (=1 to fsync appends), STREAMS_FORCE_CPU (=1 pins the CPU;
the default is jax's default backend, and the result names the platform
it ran on). Parallel mode adds: STREAMS_CONSUMERS (the N
curve; presence selects the mode), STREAMS_FRESHNESS_S (sustained-pass
duration, 0 skips), STREAMS_RECOVERY (=0 skips the kill/restart pass),
STREAMS_CONTENTION_OUT (path for the sustained pass's /contentionz
dump), STREAMS_TRANSFERS_OUT (path for its /transferz dump — fetched
over the same real socket). Tiered mode is selected by
STREAMS_TIER_SLOTS (the device slot pool size; takes precedence over
STREAMS_CONSUMERS) and adds STREAMS_TIER_ZIPF_S (the Zipf exponent,
default 1.25). STREAMS_TRANSFER_GUARD (off|log|disallow, default off)
arms the implicit-transfer guard around the hot paths in every mode —
CI runs the ingest smoke with ``disallow`` so any unplanned host
round-trip aborts the round instead of hiding in the wall time.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _emit_final(result: dict) -> None:
    """Flush stderr BEFORE printing the final JSON line so a
    2>&1-merged capture always parses the last line (the same
    hardening bench.py / serving_bench / pallas_probe / pod_dryrun
    carry)."""
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def run(num_users=20_000, num_items=5_000, rank=32, n_batches=10,
        batch_records=50_000, checkpoint_every=1, fsync=False,
        seed=0) -> dict:
    import jax

    from large_scale_recommendation_tpu import obs
    from large_scale_recommendation_tpu.core.generators import (
        SyntheticMFGenerator,
    )
    from large_scale_recommendation_tpu.models.online import (
        OnlineMF,
        OnlineMFConfig,
    )
    from large_scale_recommendation_tpu.streams import (
        EventLog,
        StreamingDriver,
        StreamingDriverConfig,
    )

    gen = SyntheticMFGenerator(num_users=num_users, num_items=num_items,
                               rank=16, noise=0.1, seed=seed, skew_lam=2.0)
    batches = [gen.generate(batch_records) for _ in range(n_batches)]
    warm = gen.generate(batch_records)
    total = n_batches * batch_records

    def make_model():
        return OnlineMF(OnlineMFConfig(
            num_factors=rank, learning_rate=0.05,
            minibatch_size=min(16384, batch_records),
            init_capacity=1 << 15))

    extra = {
        "device": str(jax.devices()[0]), "cpu_count": os.cpu_count() or 1,
        "jax_platforms": os.environ.get("JAX_PLATFORMS",
                                        jax.default_backend()),
        "num_users": num_users,
        "num_items": num_items, "rank": rank, "n_batches": n_batches,
        "batch_records": batch_records,
        "checkpoint_every": checkpoint_every, "fsync": fsync,
    }

    # the transfer plane rides the round (ISSUE 18): registry stays
    # NULL (the ledger keeps its own totals), the reset at the durable
    # warm/stream boundary makes the stamped retrace count a
    # steady-state number
    ledger = obs.enable_transfers(
        guard=os.environ.get("STREAMS_TRANSFER_GUARD", "off"))

    with tempfile.TemporaryDirectory() as tmp:
        # ---- log append leg (host-only) -------------------------------
        log = EventLog(os.path.join(tmp, "log"), fsync=fsync)
        # file creation / first-segment cost; the acked end offset (not
        # batch_records — append drops weight-0 padding) is where the
        # timed stream starts
        _, warm_end = log.append(0, warm)
        t0 = time.perf_counter()
        for b in batches:
            log.append(0, b)
        append_wall = time.perf_counter() - t0
        extra["log_append_ratings_per_s"] = round(total / append_wall, 1)

        # ---- bare baseline: partial_fit off in-memory batches ---------
        bare = make_model()
        bare.partial_fit(warm, emit_updates=False)  # compile+grow warm-up
        t0 = time.perf_counter()
        for b in batches:
            bare.partial_fit(b, emit_updates=False)
        jax.block_until_ready(bare.users.array)
        bare_wall = time.perf_counter() - t0
        extra["bare_ratings_per_s"] = round(total / bare_wall, 1)

        # ---- durable path: log → queue → online_train -----------------
        model = make_model()
        model.partial_fit(warm, emit_updates=False)  # same warm-up
        drv = StreamingDriver(
            model, log, os.path.join(tmp, "ckpt"),
            config=StreamingDriverConfig(
                batch_records=batch_records,
                checkpoint_every=checkpoint_every))
        # the warm batch occupies [0, warm_end) of the log; skip it so
        # both timed paths train the identical stream
        model.consumed_offsets[0] = warm_end
        ledger.reset()  # warm/stream boundary: stamps cover the
        # durable leg only (the headline)
        t0 = time.perf_counter()
        applied = drv.run()
        jax.block_until_ready(model.users.array)
        durable_wall = time.perf_counter() - t0
        tele = drv.telemetry()
        extra["ingest_ratings_per_s"] = round(total / durable_wall, 1)
        extra["ingest_wall_s"] = round(durable_wall, 3)
        extra["ingest_batches"] = applied
        extra["ingest_lag_records"] = tele["lag_records"]
        extra["checkpoints_written"] = tele["checkpoints_written"]
        extra["queue_depth_high_water"] = (
            tele["queue"].get("depth_high_water", 0))
        ledger.poll_retraces()
        extra["retrace_total"] = int(ledger.retrace_total)
        extra["implicit_transfers_total"] = int(ledger.implicit_total)
        log.close()

    obs.disable()
    retention = (total / durable_wall) / (total / bare_wall)
    return {
        "metric": (f"durable ingest ratings/s (log→queue→online_train, "
                   f"{num_users}x{num_items} rank={rank}, "
                   f"{n_batches}x{batch_records} micro-batches, "
                   f"ckpt every {checkpoint_every})"),
        "value": extra["ingest_ratings_per_s"],
        "unit": "ratings/s",
        "vs_baseline": round(retention, 3),
        "extra": extra,
    }


# --------------------------------------------------------------------------
# TIERED mode: the tiered-factor-store round (TIERED_r*.json)
# --------------------------------------------------------------------------


def _zipf_batches(num_users, num_items, n_batches, batch_records,
                  seed, zipf_s):
    """Bounded-Zipf rating stream: user ids rank-weighted ``r^-s``
    over the full universe. The generator's truncated-exponential
    skew can't express a tiered workload — its tail is so thin that
    realized rows ≈ 3N/λ while 90% hot-mass needs slots ≥ 2.3N/λ,
    capping the honest overcommit near 1.3×. A Zipf tail keeps
    registering fresh rows for the WHOLE stream (the table outgrows
    the pool) while revisit mass stays concentrated (the pool can
    still serve it) — the actual access pattern tiering exists for."""
    from large_scale_recommendation_tpu.core.types import Ratings

    rng = np.random.default_rng(seed)
    ranks = np.arange(1, num_users + 1, dtype=np.float64)
    p = ranks ** -zipf_s
    p /= p.sum()

    def draw():
        return Ratings.from_arrays(
            rng.choice(num_users, size=batch_records, p=p),
            rng.integers(0, num_items, batch_records),
            rng.uniform(1.0, 5.0, batch_records).astype(np.float32))

    return [draw() for _ in range(n_batches)], draw()


def run_tiered(num_users=1_000_000, num_items=4_000, rank=32,
               n_batches=24, batch_records=20_000, slot_capacity=8_192,
               zipf_s=1.25, checkpoint_every=8, fsync=False, seed=0,
               serve_requests=16) -> dict:
    """Tiered-store round: the SAME Zipfian WAL stream driven twice —
    all-HBM (plain ``GrowableFactorTable``) and tiered (a
    ``TieredFactorStore`` whose device slot pool is a fraction of the
    user table, async-prefetched from the WAL lookahead the driver's
    feeder queue announces). The headline is the tiered ingest rate;
    ``vs_baseline`` is tiered/all-HBM (the throughput retention of the
    tier); the round also proves the pinned invariant on the real
    pipeline: the two final user tables and the two engines' top-K
    answers must be BIT-IDENTICAL (``bit_exact`` / ``serve_bit_exact``
    are hard evidence, not vibes). Default geometry: a 1M-id Zipf(1.25)
    universe realizing ~36k user rows over an 8k-slot pool (≥4× device
    budget), per-batch working set ~3.3k rows — the pinned batch plus
    the announced lookahead fit the pool, so the steady-state hit rate
    is LRU residency plus the prefetcher's report card. The
    simulated-budget caveat is stamped in ``error``."""
    import jax

    from large_scale_recommendation_tpu import obs
    from large_scale_recommendation_tpu.core.initializers import (
        PseudoRandomFactorInitializer,
    )
    from large_scale_recommendation_tpu.models.online import (
        OnlineMF,
        OnlineMFConfig,
    )
    from large_scale_recommendation_tpu.serving.engine import ServingEngine
    from large_scale_recommendation_tpu.store import TieredFactorStore
    from large_scale_recommendation_tpu.streams import (
        EventLog,
        StreamingDriver,
        StreamingDriverConfig,
    )

    batches, warm = _zipf_batches(num_users, num_items, n_batches,
                                  batch_records, seed, zipf_s)
    total = n_batches * batch_records

    cfg = OnlineMFConfig(num_factors=rank, learning_rate=0.05,
                         minibatch_size=min(16384, batch_records),
                         init_capacity=1 << 15)

    def make_model(tiered: bool) -> OnlineMF:
        m = OnlineMF(cfg)
        if tiered:
            # the EXACT initializer OnlineMF builds, so any divergence
            # can only come from the tier itself
            m.users = TieredFactorStore(
                PseudoRandomFactorInitializer(cfg.num_factors,
                                              scale=cfg.init_scale),
                capacity=cfg.init_capacity,
                slot_capacity=slot_capacity)
        return m

    # the transfer plane rides the round (ISSUE 18): registry stays
    # NULL (the ledger keeps its own totals); each leg's drive resets
    # the ledger at its warm/stream boundary, so the per-site GB/s
    # stamps below cover exactly the tiered streamed phase
    ledger = obs.enable_transfers(
        guard=os.environ.get("STREAMS_TRANSFER_GUARD", "off"))

    def drive(model, log, tmp, name, warm_end) -> float:
        model.partial_fit(warm, emit_updates=False)  # compile warm-up
        drv = StreamingDriver(
            model, log, os.path.join(tmp, name),
            config=StreamingDriverConfig(
                batch_records=batch_records,
                checkpoint_every=checkpoint_every,
                # bounded lookahead: the feeder announces at most 2
                # batches ahead. Short lead wins twice: an announced id
                # whose rows were staged is acquired before eviction
                # pressure ages it out, and ids unseen at announce time
                # (dropped — prefetch never registers vocabulary) are
                # exactly the recently-first-seen rows LRU still holds.
                # Measured: lead 2 ≈ 0.91 hit, lead 8 ≈ 0.79, lead 16
                # (the default) ≈ 0.77 on the default geometry
                queue_capacity=2))
        model.consumed_offsets[0] = warm_end  # both paths skip warm
        ledger.reset()  # warm/stream boundary (ISSUE 18): cold-start
        # faults and compile traces are warm-up, not steady state
        t0 = time.perf_counter()
        drv.run()
        jax.block_until_ready(model.users.array)
        return time.perf_counter() - t0

    extra = {
        "device": str(jax.devices()[0]), "cpu_count": os.cpu_count() or 1,
        "jax_platforms": os.environ.get("JAX_PLATFORMS",
                                        jax.default_backend()),
        "num_users": num_users, "num_items": num_items, "rank": rank,
        "n_batches": n_batches, "batch_records": batch_records,
        "slot_capacity": slot_capacity,
    }

    with tempfile.TemporaryDirectory() as tmp:
        log = EventLog(os.path.join(tmp, "log"), fsync=fsync)
        _, warm_end = log.append(0, warm)
        for b in batches:
            log.append(0, b)

        hbm = make_model(tiered=False)
        hbm_wall = drive(hbm, log, tmp, "ckpt_hbm", warm_end)

        tiered = make_model(tiered=True)
        st = tiered.users
        # isolate the streamed phase: the warm-up batch's cold-start
        # demand faults are compile-time noise, not steady state
        st.stats.hits = st.stats.misses = 0
        st.stats.demand_fault_s = 0.0
        tier_wall = drive(tiered, log, tmp, "ckpt_tier", warm_end)
        log.close()

        rows = int(st.num_rows)
        assert rows == int(hbm.users.num_rows)
        U_h = np.asarray(hbm.users.full_table())[:rows]
        U_t = np.asarray(st.full_table())[:rows]
        bit_exact = bool(np.array_equal(U_t, U_h))

        extra["hbm_ratings_per_s"] = round(total / hbm_wall, 1)
        extra["tiered_ratings_per_s"] = round(total / tier_wall, 1)
        extra["tiered_vs_hbm_frac"] = round(hbm_wall / tier_wall, 3)
        extra["user_rows"] = rows
        extra["device_budget_x"] = round(rows / slot_capacity, 2)
        extra["tier_hit_rate"] = round(st.stats.hit_rate, 4)
        extra["tier_prefetch_wait_s"] = round(st.stats.demand_fault_s, 4)
        extra["tier_evictions"] = int(st.stats.evictions)
        extra["tier_writebacks"] = int(st.stats.writebacks)
        extra["tier_host_bytes"] = int(st.stats.host_bytes)
        extra["tier_prefetched_rows"] = int(st.stats.prefetched)
        extra["bit_exact"] = bit_exact

        # measured per-site transfer GB/s for both legs (h2d stage-in
        # sites, the d2h write-back site) over the tiered streamed
        # phase, plus the steady-state retrace/guard stamps. CPU
        # caveat unchanged: the rates price the host<->"device"
        # copy machinery on this backend, not a real PCIe/ICI link.
        snap = ledger.snapshot()
        for site, s in snap["sites"].items():
            if s["effective_gbs"] is not None:
                extra["transfer_" + site.replace(".", "_") + "_gbs"] = (
                    round(s["effective_gbs"], 3))
        extra["transfer_h2d_bytes"] = sum(
            s["h2d_bytes"] for s in snap["sites"].values())
        extra["transfer_d2h_bytes"] = sum(
            s["d2h_bytes"] for s in snap["sites"].values())
        extra["retrace_total"] = int(snap["retraces"]["total"])
        extra["implicit_transfers_total"] = int(
            snap["implicit_transfers_total"])

        # ---- serve both sides over identical requests ----------------
        rng = np.random.default_rng(seed + 1)
        requests = [rng.integers(0, rows, 64).astype(np.int64)
                    for _ in range(serve_requests)]
        eng_h = ServingEngine(hbm.to_model(), k=10)
        t0 = time.perf_counter()
        res_h = eng_h.serve(requests)
        extra["serve_hbm_wall_s"] = round(time.perf_counter() - t0, 4)
        eng_t = ServingEngine(tiered.to_model(), k=10, user_store=st)
        t0 = time.perf_counter()
        res_t = eng_t.serve(requests)
        extra["serve_tiered_wall_s"] = round(time.perf_counter() - t0, 4)
        serve_exact = all(
            np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
            and np.array_equal(np.asarray(a[1]), np.asarray(b[1]))
            for a, b in zip(res_h, res_t))
        extra["serve_bit_exact"] = bool(serve_exact)
        extra["tier_serve_hits"] = int(st.stats.serve_hits)
        extra["tier_serve_misses"] = int(st.stats.serve_misses)

    obs.disable()
    return {
        "metric": (f"tiered ingest ratings/s (user table {rows} rows "
                   f"over {slot_capacity} device slots, "
                   f"{extra['device_budget_x']}x device budget, "
                   f"rank={rank})"),
        "value": extra["tiered_ratings_per_s"],
        "unit": "ratings/s",
        "vs_baseline": extra["tiered_vs_hbm_frac"],
        # honest caveat, the INGEST_r01 precedent: stamped on EVERY
        # tiered round, because the budget is simulated by capping the
        # slot pool on a CPU host — it prices the tier's bookkeeping,
        # transfers and prefetch machinery, not real HBM pressure
        "error": ("simulated device budget: the slot pool caps rows on "
                  "a CPU host; bookkeeping+transfer overhead is real, "
                  "HBM pressure is not"),
        "extra": extra,
    }


# --------------------------------------------------------------------------
# N_CONSUMERS mode: the parallel-ingest round (INGEST_r*.json)
# --------------------------------------------------------------------------


def _stratum_batch(rng, p: int, n_consumers: int, total_users: int,
                   total_items: int, count: int):
    """ONE stratum-routed batch for partition ``p`` over the FIXED
    shared universe: users ≡ p (mod N), items in block p of the same
    ``total_items`` catalog — two partitions' batches never share a
    user OR item row (the Gemulla disjointness that lets the N applies
    commute), and the model trained at N=8 has the same table geometry
    as at N=1, so the curve measures PARALLELISM, not table growth
    (full-table scatter cost scales with table size — a per-partition
    universe would confound the two). The ONE copy of the routing rule
    all three passes share."""
    u_blk = max(1, total_users // n_consumers)
    i_blk = max(1, total_items // n_consumers)
    u = rng.integers(0, u_blk, count) * n_consumers + p
    i = rng.integers(0, i_blk, count) + p * i_blk
    return u, i, rng.random(count).astype(np.float32)


def _fill_strata(log, n_consumers: int, total_users: int,
                 total_items: int, batches_per_part: int,
                 batch_records: int, seed: int = 0) -> None:
    """Fill each partition with ``batches_per_part`` stratum-routed
    batches (``_stratum_batch``)."""
    rng = np.random.default_rng(seed)
    for p in range(n_consumers):
        for _ in range(batches_per_part):
            u, i, r = _stratum_batch(rng, p, n_consumers, total_users,
                                     total_items, batch_records)
            log.append_arrays(p, u, i, r)


def _make_parallel(tmp, name, n_consumers, rank, batch_records,
                   checkpoint_every, fsync, minibatch):
    from large_scale_recommendation_tpu.models.online import (
        OnlineMF,
        OnlineMFConfig,
    )
    from large_scale_recommendation_tpu.streams import (
        EventLog,
        ParallelIngestRunner,
        StreamingDriverConfig,
    )

    log = EventLog(os.path.join(tmp, name), num_partitions=n_consumers,
                   fsync=fsync)
    model = OnlineMF(OnlineMFConfig(
        num_factors=rank, learning_rate=0.05,
        minibatch_size=minibatch, init_capacity=1 << 15))
    runner = ParallelIngestRunner(
        model, log, os.path.join(tmp, name + "_ckpt"),
        config=StreamingDriverConfig(batch_records=batch_records,
                                     checkpoint_every=checkpoint_every))
    return log, model, runner


def run_parallel(curve=(1, 2, 4, 8), total_users=32_000,
                 total_items=8_000, rank=32, n_batches=16,
                 batch_records=20_000, checkpoint_every=4, fsync=False,
                 freshness_s=2.0, recovery=True, seed=0) -> dict:
    import jax

    from large_scale_recommendation_tpu import obs

    minibatch = min(8192, batch_records)
    curve = sorted(set(int(n) for n in curve))
    cores = os.cpu_count() or 1
    extra = {
        "device": str(jax.devices()[0]), "cpu_count": cores,
        "jax_platforms": os.environ.get("JAX_PLATFORMS",
                                        jax.default_backend()),
        "curve": list(curve), "total_users": total_users,
        "total_items": total_items, "rank": rank,
        "n_batches_total": n_batches,
        "batch_records": batch_records,
        "checkpoint_every": checkpoint_every, "fsync": fsync,
    }

    # the contention plane rides every rung (ISSUE 14): the locks bind
    # at model/runner construction, the window resets per rung, and
    # serial_fraction_n<K>/lock_wait_s_total_n<K> say where a flat
    # curve's headroom went. Registry stays NULL here — the tracker
    # keeps its own stats, so the rungs pay only the (µs-scale)
    # wrapped-lock accounting, not the full obs stack.
    tracker = obs.enable_contention(interval_s=0.2)
    # the transfer plane rides the rungs the same way (ISSUE 18): null
    # registry, own totals; reset alongside each rung's window so the
    # round-header stamps cover the largest-N rung's timed drain
    ledger = obs.enable_transfers(
        guard=os.environ.get("STREAMS_TRANSFER_GUARD", "off"))

    rates: dict[int, float] = {}
    with tempfile.TemporaryDirectory() as tmp:
        # ---- scaling curve (STRONG scaling): the same fixed-universe
        # workload split over N stratum-routed partitions ---------------
        for n in curve:
            bpp = max(1, n_batches // n)  # batches per partition
            log, model, runner = _make_parallel(
                tmp, f"log_n{n}", n, rank, batch_records,
                checkpoint_every, fsync, minibatch)
            # warm: one batch per partition through the FULL path
            # (compiles the concurrent-apply kernels + grows tables)
            _fill_strata(log, n, total_users, total_items,
                         1 + bpp, batch_records, seed=seed)
            runner.run(max_batches=1)
            total = n * bpp * batch_records
            tracker.reset_window()
            ledger.reset()  # warm/stream boundary per rung
            t0 = time.perf_counter()
            applied = runner.run()
            jax.block_until_ready(model.users.array)
            wall = time.perf_counter() - t0
            sat = obs.SaturationAnalyzer(tracker).snapshot()
            tele = runner.telemetry()
            assert applied == n * bpp, (applied, n, bpp)
            assert all(v == 0 for v in tele["lag_records"].values())
            rates[n] = total / wall
            extra[f"ingest_n{n}_ratings_per_s"] = round(rates[n], 1)
            extra[f"lock_wait_s_total_n{n}"] = round(
                sat["lock_wait_s_total"], 4)
            if n > 1:
                if sat["serial_fraction"] is not None:
                    extra[f"serial_fraction_n{n}"] = round(
                        sat["serial_fraction"], 4)
                if 1 in rates:
                    # efficiency is DEFINED against the true N=1 rate;
                    # a curve without N=1 has no honest baseline —
                    # rate_K/(K·rate_minN) would halve the number and
                    # still gate under the same key
                    extra[f"scaling_eff_n{n}"] = round(
                        rates[n] / (n * rates[1]), 4)
                if tele.get("gate"):
                    extra[f"gate_waits_n{n}"] = tele["gate"]["waits"]
            extra[f"checkpoints_n{n}"] = tele["checkpoints_written"]
            log.close()
            top = (sat["top_contended"][0] if sat["top_contended"]
                   else None)
            print(f"[parallel] N={n}: {rates[n]:,.0f} ratings/s "
                  f"({applied} batches; lock wait "
                  f"{sat['lock_wait_s_total']:.3f}s"
                  + (f", top {top['lock']}" if top else "") + ")",
                  file=sys.stderr)

        n_max = max(curve)

        # round-header stamps (ISSUE 18): the largest-N rung's timed
        # drain, captured BEFORE the recovery/sustained passes (the
        # sustained pass tears the whole obs stack down in its finally)
        ledger.poll_retraces()
        extra["retrace_total"] = int(ledger.retrace_total)
        extra["implicit_transfers_total"] = int(ledger.implicit_total)

        # ---- recovery after a mid-stream kill at N=max --------------
        if recovery:
            extra.update(_recovery_pass(
                tmp, n_max, total_users, total_items, rank,
                max(4, n_batches // n_max), batch_records,
                checkpoint_every, fsync, minibatch, seed))

        # ---- sustained follow-mode pass: freshness SLO + critical
        # path per partition -------------------------------------------
        if freshness_s > 0:
            extra.update(_sustained_pass(
                tmp, n_max, total_users, total_items, rank,
                batch_records, checkpoint_every, fsync, minibatch,
                freshness_s, seed))

    obs.disable()  # the rungs' tracker (the sustained pass tears its
    # own stack down; with freshness_s=0 this is what stops the
    # contention sampler)
    speedup = rates[n_max] / rates[min(curve)]
    result = {
        "metric": (f"parallel ingest ratings/s (N={n_max} per-partition "
                   f"consumers, stratum-routed strong scaling, "
                   f"rank={rank}, {n_batches} total x {batch_records}, "
                   f"barrier every {checkpoint_every})"),
        "value": round(rates[n_max], 1),
        "unit": "ratings/s",
        "vs_baseline": round(speedup, 3),
        "extra": extra,
    }
    if cores < n_max:
        result["error"] = (
            f"only {cores} CPU core(s) for N={n_max} consumers: speedup "
            f"beyond ~min(N, cores)x is physically unreachable here — "
            f"the measured curve is host/device pipeline overlap plus "
            f"contention on {cores} core(s), not N-core parallel "
            f"capacity; re-run on a machine with >= {n_max} cores to "
            f"price the scaling target")
    return result


def _recovery_pass(tmp, n, total_users, total_items, rank,
                   batches_per_part, batch_records, checkpoint_every,
                   fsync, minibatch, seed) -> dict:
    """Kill one consumer mid-stream (partitions at different offsets),
    resume a fresh runner from the barrier snapshot, re-drain. Returns
    recovery_s + the per-partition duplicate window in batches."""
    import jax

    class _Kill(RuntimeError):
        pass

    # the kill must land AFTER at least one barrier (else there is
    # nothing to resume from — a different scenario than the one this
    # pass prices): clamp the cadence to the stream length and kill on
    # partition 0's OWN (ck+1)-th batch — p0 crossing ck guarantees a
    # barrier fired, and counting p0's batches (not a global counter)
    # makes the kill deterministic under any thread schedule (a global
    # threshold could let p0 drain before its siblings ever counted)
    ck = min(checkpoint_every, max(1, batches_per_part // 2))
    log, model, runner = _make_parallel(
        tmp, "log_recov", n, rank, batch_records, ck, fsync, minibatch)
    # uneven partitions: p gets batches_per_part + p extra batches, so
    # the kill leaves every partition at a DIFFERENT offset
    rng = np.random.default_rng(seed + 1)
    for p in range(n):
        for _ in range(batches_per_part + p):
            u, i, r = _stratum_batch(rng, p, n, total_users,
                                     total_items, batch_records)
            log.append_arrays(p, u, i, r)
    p0_seen = [0]

    def kill_late(batch):
        if batch.partition == 0:
            p0_seen[0] += 1
            if p0_seen[0] > ck:
                raise _Kill("mid-stream kill")

    runner.on_batch = kill_late
    t_kill = None
    try:
        runner.run()
    except _Kill:
        t_kill = time.perf_counter()
    assert t_kill is not None, "kill never fired"
    frontier_at_kill = runner.applied_frontier()

    m2_log, m2, r2 = _make_parallel(
        tmp, "log_recov", n, rank, batch_records, ck, fsync, minibatch)
    t0 = time.perf_counter()
    assert r2.resume(), "no barrier snapshot to resume from"
    restored = dict(m2.consumed_offsets)
    r2.run()
    jax.block_until_ready(m2.users.array)
    recovery_s = time.perf_counter() - t0
    tele = r2.telemetry()
    assert all(v == 0 for v in tele["lag_records"].values()), \
        "records lost after resume"
    # duplicate window: batches applied past the restored offset at the
    # kill instant — the replay each partition pays, bounded by the
    # barrier cadence
    dup = {p: max(0, -(-(frontier_at_kill.get(p, 0)
                         - restored.get(p, 0)) // batch_records))
           for p in range(n)}
    m2_log.close()
    return {
        "recovery_s": round(recovery_s, 3),
        "recovery_replayed_records": int(sum(
            max(0, frontier_at_kill.get(p, 0) - restored.get(p, 0))
            for p in range(n))),
        "duplicate_window_batches_max": int(max(dup.values())),
        "duplicate_window_bound": int(ck),
    }


def _sustained_pass(tmp, n, total_users, total_items, rank,
                    batch_records, checkpoint_every, fsync, minibatch,
                    duration_s, seed) -> dict:
    """Follow-mode N-consumer run under continuous producer load with
    lineage + critical path armed: periodic coalesced delta refreshes
    must keep the ingest→serve ``FreshnessCheck`` green, and
    ``/criticalpathz`` samples must resolve for every partition."""
    import json as _json

    from large_scale_recommendation_tpu import obs
    from large_scale_recommendation_tpu.obs.health import OK
    from large_scale_recommendation_tpu.obs.lineage import FreshnessCheck
    from large_scale_recommendation_tpu.obs.server import (
        ObsServer,
        http_get,
    )

    per = max(1024, batch_records // 8)  # smaller sustained batches
    try:
        obs.enable()
        obs.enable_lineage()
        analyzer = obs.enable_disttrace()
        # the contention plane re-arms ON TOP of the live registry (the
        # rungs ran it against the null one) so /contentionz joins the
        # per-partition streams_* gauges — locks bind at the runner
        # construction below
        tracker = obs.enable_contention(interval_s=0.2)
        log, model, runner = _make_parallel(
            tmp, "log_sustained", n, rank, per, checkpoint_every,
            fsync, minibatch)
        engine = runner.serving_engine(k=10, max_batch=256)
        server = ObsServer().start()
        tracker.reset_window()
        check = FreshnessCheck(obs.get_lineage(),
                               degraded_after_s=max(2.0, duration_s),
                               critical_after_s=4 * max(2.0, duration_s))
        rng = np.random.default_rng(seed + 2)
        stop = threading.Event()

        def produce():
            while not stop.is_set():
                for p in range(n):
                    u, i, r = _stratum_batch(rng, p, n, total_users,
                                             total_items, per)
                    log.append_arrays(p, u, i, r)
                time.sleep(0.01)

        producer = threading.Thread(target=produce, daemon=True)
        producer.start()
        runner.start(follow=True)
        t_end = time.perf_counter() + duration_s
        verdicts = []
        while time.perf_counter() < t_end:
            time.sleep(0.1)
            runner.refresh_serving()
            verdicts.append(check().status)
        # /contentionz over the REAL socket while the N consumers are
        # still following (live threads, live lock traffic) — the body
        # the CI smoke structurally asserts on and the --contention
        # renderer's artifact
        code, body = http_get(server.url + "/contentionz")
        contention_doc = _json.loads(body) if code == 200 else {
            "note": f"fetch failed: {code}", "locks": [],
            "partitions": {}}
        out_path = os.environ.get("STREAMS_CONTENTION_OUT")
        if out_path:
            with open(out_path, "w") as f:
                _json.dump(contention_doc, f, indent=2)
        # /transferz over the SAME real socket (ISSUE 18): the round's
        # ledger survives the obs.enable() above (only disable() clears
        # it), so the served body carries the sustained pass's live
        # site totals + the retrace ring — the CI smoke's
        # transferz_ci.json artifact
        tout = os.environ.get("STREAMS_TRANSFERS_OUT")
        if tout:
            code, tbody = http_get(server.url + "/transferz")
            with open(tout, "w") as f:
                f.write(tbody if code == 200
                        else _json.dumps({"note": f"fetch failed: {code}",
                                          "sites": {}}))
        stop.set()
        producer.join()
        runner.stop()
        runner.join()
        runner.refresh_serving()  # final covering swap
        verdicts.append(check().status)
        parts = {s["partition"] for s in analyzer.samples()}
        tele = runner.telemetry()
        server.stop()
        log.close()
        return {
            "freshness_slo_held": int(all(v == OK for v in verdicts)),
            "freshness_checks": len(verdicts),
            "critical_path_partitions": len(parts),
            "critical_path_samples": analyzer.samples_total,
            "contention_partitions": len(contention_doc.get(
                "partitions", {})),
            "contention_locks": len(contention_doc.get("locks", [])),
            "sustained_records": tele["records_processed"],
            "sustained_refreshes_coalesced": tele["refreshes_coalesced"],
            "sustained_catalog_swaps": len(tele["catalog_versions"]),
        }
    finally:
        obs.disable()  # back to the zero-cost null layer for any
        # passes that follow — the bench owns the whole process


def main() -> None:
    from large_scale_recommendation_tpu.utils.platform import (
        enable_compilation_cache,
        force_cpu,
        stamp_device,
    )

    if os.environ.get("STREAMS_FORCE_CPU") == "1":
        force_cpu()
    enable_compilation_cache()
    consumers = os.environ.get("STREAMS_CONSUMERS")
    tier_slots = os.environ.get("STREAMS_TIER_SLOTS")
    if tier_slots:
        result = run_tiered(
            num_users=int(os.environ.get("STREAMS_USERS", 1_000_000)),
            num_items=int(os.environ.get("STREAMS_ITEMS", 4_000)),
            rank=int(os.environ.get("STREAMS_RANK", 32)),
            n_batches=int(os.environ.get("STREAMS_BATCHES", 24)),
            batch_records=int(os.environ.get("STREAMS_BATCH", 20_000)),
            slot_capacity=int(tier_slots),
            zipf_s=float(os.environ.get("STREAMS_TIER_ZIPF_S", 1.25)),
            checkpoint_every=int(
                os.environ.get("STREAMS_CHECKPOINT_EVERY", 8)),
            fsync=os.environ.get("STREAMS_FSYNC") == "1",
        )
    elif consumers:
        result = run_parallel(
            curve=[int(x) for x in consumers.split(",")],
            total_users=int(os.environ.get("STREAMS_USERS", 32_000)),
            total_items=int(os.environ.get("STREAMS_ITEMS", 8_000)),
            rank=int(os.environ.get("STREAMS_RANK", 32)),
            n_batches=int(os.environ.get("STREAMS_BATCHES", 16)),
            batch_records=int(os.environ.get("STREAMS_BATCH", 20_000)),
            checkpoint_every=int(
                os.environ.get("STREAMS_CHECKPOINT_EVERY", 4)),
            fsync=os.environ.get("STREAMS_FSYNC") == "1",
            freshness_s=float(os.environ.get("STREAMS_FRESHNESS_S", 2.0)),
            recovery=os.environ.get("STREAMS_RECOVERY", "1") == "1",
        )
    else:
        result = run(
            num_users=int(os.environ.get("STREAMS_USERS", 20_000)),
            num_items=int(os.environ.get("STREAMS_ITEMS", 5_000)),
            rank=int(os.environ.get("STREAMS_RANK", 32)),
            n_batches=int(os.environ.get("STREAMS_BATCHES", 10)),
            batch_records=int(os.environ.get("STREAMS_BATCH", 50_000)),
            checkpoint_every=int(
                os.environ.get("STREAMS_CHECKPOINT_EVERY", 1)),
            fsync=os.environ.get("STREAMS_FSYNC") == "1",
        )
    # nothing here is a chip number unless this says ``tpu``
    print(f"# ran on {stamp_device(result['extra'])}", file=sys.stderr)
    _emit_final(result)


if __name__ == "__main__":
    sys.exit(main())
