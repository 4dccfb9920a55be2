"""Observability demo: train → serve → stream behind a LIVE health layer.

One run starts the endpoint server, drives every tier through it, then
deliberately poisons the stream to show the watchdog + ``/healthz``
doing their job:

1. ``obs.enable()`` + ``ObsServer`` — ``/metrics``, ``/healthz``,
   ``/varz``, ``/tracez`` served over a real socket (port printed).
2. DSGD training (2 segments: compile vs execute split in the trace).
3. ``ServingEngine`` with an ``SLOTracker`` — flush walls feed the
   attainment window; the serving health check reads its burn rate.
4. Durable streaming ingest with a ``TrainingWatchdog(policy=
   "rollback")``, a stream-lag check, a checkpoint-staleness check, and
   the timed telemetry export keeping the lag gauges fresh.
5. **The model plane (ISSUE 10)**: an ``OnlineEvaluator`` reservoir
   holdout (split out of every batch BEFORE ``partial_fit`` trains —
   the eval set is never trained on) shadow-scored into ``eval_*``
   gauges with threshold-free quality anomaly checks armed
   (``watch_quality``), a ``DataQualityInspector`` in front of
   training, and a ``LineageJournal`` stamping every catalog swap.
   **A staleness condition is injected** — ingest continues while
   swaps stop — and the freshness SLO check flips ``/healthz`` to 503;
   the ``/lineagez`` tail shows every served ``catalog_version``'s
   provenance (WAL watermark, train step, source); a re-swap recovers.
6. ``curl /healthz`` → 200, every check OK.
7. **A NaN micro-batch is injected**: the watchdog trips BEFORE the
   offset stamp, rolls the model back to the last durable checkpoint,
   and ``/healthz`` flips to 503 with the training check CRITICAL —
   the poisoned batch never reaches a checkpoint or a catalog swap.
   Because a flight recorder is running (step 1), the trip also
   FREEZES A POSTMORTEM BUNDLE — recent metric series, the structured
   event tail (catalog swaps, checkpoints, the trip itself), the span
   tail, and the health/registry snapshots — whose path is printed and
   which ``scripts/obs_report.py --bundle <dir>`` renders.

Artifacts under ``--out`` (default ``obs_out/``): ``metrics.prom``
(fetched from the live ``/metrics`` route), ``metrics.jsonl``,
``trace.json`` (Perfetto-loadable), ``healthz.json`` (the final
CRITICAL report), ``roofline.json`` (the per-kernel roofline table —
XLA cost analysis joined with measured walls, rendered inline and by
``scripts/obs_report.py --roofline``), and
``postmortem/bundle_watchdog_trip_*/`` (the validated incident bundle,
with a short ``profile/`` capture attached). ``scripts/obs_report.py <url>/varz
--watch 2`` tails the same server live; ``/seriesz`` and ``/eventz``
serve the recorder's history and the event ring.

Run: ``JAX_PLATFORMS=cpu python examples/obs_demo.py``
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from large_scale_recommendation_tpu.obs.server import http_get as _curl  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="obs_out", help="artifact directory")
    args = ap.parse_args(argv)

    from large_scale_recommendation_tpu import obs

    # enable FIRST: instruments bind at construction time — and the
    # flight recorder right after, so event hooks bind too and the
    # sampler is already recording the lead-up when the incident hits
    reg, tracer = obs.enable()
    recorder, journal = obs.enable_flight_recorder(
        interval_s=0.25, bundle_dir=os.path.join(args.out, "postmortem"),
        # watchdog-trip bundles get a short jax.profiler capture
        # attached (<bundle>/profile/)
        profile_on_trip_s=0.2)
    # XLA introspection: every compile below lands in the roofline
    # table (cost analysis joined with measured execute walls), the
    # device-memory sampler feeds the recorder, and /rooflinez serves it
    introspector = obs.enable_introspection(interval_s=0.25)
    # catalog lineage: every swap below stamps its provenance, every
    # flush joins the served version back — /lineagez serves the journal
    lineage = obs.enable_lineage()

    from large_scale_recommendation_tpu.core.generators import (
        SyntheticMFGenerator,
    )
    from large_scale_recommendation_tpu.core.types import Ratings
    from large_scale_recommendation_tpu.models.dsgd import DSGD, DSGDConfig
    from large_scale_recommendation_tpu.models.online import (
        OnlineMF,
        OnlineMFConfig,
    )
    from large_scale_recommendation_tpu.obs.health import (
        HealthMonitor,
        SLOTracker,
        TrainingDivergedError,
        TrainingWatchdog,
    )
    from large_scale_recommendation_tpu.obs.server import ObsServer
    from large_scale_recommendation_tpu.serving.engine import ServingEngine
    from large_scale_recommendation_tpu.streams.driver import (
        StreamingDriver,
        StreamingDriverConfig,
    )
    from large_scale_recommendation_tpu.streams.log import EventLog

    monitor = HealthMonitor()
    server = ObsServer(monitor=monitor).start()
    print(f"# endpoint server live at {server.url} "
          f"(/metrics /healthz /varz /tracez)")

    # ---- train: segmented so compile vs execute splits in the trace ----
    print("# train: DSGD, 2 segments (first carries the compile)")
    gen = SyntheticMFGenerator(num_users=500, num_items=200, rank=8,
                               noise=0.1, seed=0)
    ratings = gen.generate(20_000)
    solver = DSGD(DSGDConfig(num_factors=16, iterations=2, num_blocks=2,
                             minibatch_size=1024, learning_rate=0.05))
    model = solver.fit(ratings, checkpoint_every=1)

    # ---- serve: SLO-tracked mixed-size request stream ------------------
    # target is deliberately loose (10s): demo flushes carry XLA compiles
    # and run on arbitrary CI hosts — the point here is the wiring, not a
    # latency claim. A deployment would set its real target.
    print("# serve: 40 mixed-size requests, SLO 99% of flushes < 10s")
    slo = SLOTracker(target_s=10.0, objective=0.99, window=256)
    monitor.watch_slo(slo)
    engine = ServingEngine(model, k=10, max_batch=256, slo=slo)
    rng = np.random.default_rng(1)
    engine.serve([rng.integers(0, 500, int(sz)).astype(np.int64)
                  for sz in rng.integers(1, 48, 40)])
    print(f"#   slo: attainment={slo.attainment:.3f} "
          f"burn={slo.burn_rate:.2f} "
          f"budget_remaining={slo.error_budget_remaining:.2f}")

    # ---- stream: watchdog-guarded durable ingest -----------------------
    print("# stream: 3 micro-batches through the durable ingest driver, "
          "watchdog armed (policy=rollback)")
    with tempfile.TemporaryDirectory() as tmp:
        log = EventLog(os.path.join(tmp, "log"))
        for _ in range(3):
            ru, ri, rv, _ = gen.generate(2_000).to_numpy()
            log.append_arrays(0, ru, ri, rv)
        online = OnlineMF(OnlineMFConfig(num_factors=8,
                                         minibatch_size=512))
        # the model plane (ISSUE 10): a reservoir holdout the model
        # NEVER trains on (split before partial_fit sees each batch)
        # and a per-batch data-quality inspector in front of training
        from large_scale_recommendation_tpu.obs.dataquality import (
            DataQualityInspector,
        )
        from large_scale_recommendation_tpu.obs.quality import (
            OnlineEvaluator,
        )

        evaluator = OnlineEvaluator(online, holdout_fraction=0.15,
                                    reservoir_size=2048,
                                    min_eval_rows=64)
        # duplicate policy priced at THIS workload's baseline (the
        # synthetic stream has ~1% natural birthday collisions in
        # 2K-pair batches over a 100K-pair space); the corruption
        # classes keep the tight defaults
        inspector = DataQualityInspector(
            rating_range=(-50.0, 50.0),
            class_policy={"duplicate_key": (0.05, 0.5)})
        driver = StreamingDriver(
            online, log, os.path.join(tmp, "ckpt"),
            config=StreamingDriverConfig(batch_records=2_000),
            inspector=inspector, evaluator=evaluator)
        watchdog = TrainingWatchdog(policy="rollback",
                                    manager=driver.manager)
        online.watchdog = watchdog
        monitor.watch_watchdog(watchdog)
        monitor.watch_driver(driver, degraded_lag=50_000)
        monitor.watch_checkpoints(driver.manager, degraded_after_s=300)
        monitor.watch_data_quality(inspector)
        # quality anomaly checks: eval_rmse spikes / eval_ndcg drops
        # flip /healthz with zero static per-model thresholds — they
        # learn the series' normal from the flight recorder
        monitor.watch_quality(recorder)
        driver.start_telemetry_export(interval_s=1.0)  # fresh lag gauges
        driver.run()

        # ---- quality: shadow-score the never-trained-on holdout --------
        qm = evaluator.evaluate()
        print(f"# quality: holdout={evaluator.holdout_rows} rows "
              f"(never trained on), eval_rmse={qm['rmse']:.3f} "
              f"ndcg@10={qm.get('ndcg', float('nan')):.3f} "
              f"hr@10={qm.get('hr', float('nan')):.3f} "
              f"coverage={qm.get('coverage', float('nan')):.3f}")
        print(f"# data quality: {inspector.batches} batches inspected, "
              f"status={inspector.status()[0]!r}")

        # ---- lineage + staleness: ingest continues, swaps stop ---------
        sengine = driver.serving_engine(k=5, max_batch=64)
        driver.refresh_serving()  # swap: provenance gains the watermark
        r0 = sengine.recommend(np.arange(16, dtype=np.int64))
        rec0 = lineage.resolve(r0.catalog_version)
        print(f"# lineage: served catalog_version={r0.catalog_version} "
              f"→ watermark={rec0['wal_offset_watermark']} "
              f"step={rec0['train_step']} source={rec0['source']!r}")
        monitor.watch_freshness(lineage, degraded_after_s=0.05,
                                critical_after_s=0.2)
        print("# inject: ingest continues while catalog swaps STOP")
        ru, ri, rv, _ = gen.generate(2_000).to_numpy()
        log.append_arrays(0, ru, ri, rv)
        driver.run()  # applies the new records — but nobody refreshes
        import time as _time

        _time.sleep(0.3)  # the unservable records age past the SLO
        # absorb the ok→CRITICAL transition in-process first: the
        # transition freezes a postmortem bundle (+ profiler capture),
        # and that work belongs here, not inside the HTTP request the
        # assertion below times
        monitor.run()
        code, body = _curl(server.url + "/healthz")
        report = json.loads(body)
        print(f"# healthz (stale): HTTP {code}, "
              f"freshness={report['checks']['freshness']['status']!r} "
              f"(unservable_age_s="
              f"{report['checks']['freshness']['detail'].get('unservable_age_s')})")
        assert code == 503, body
        _, lineagez = _curl(server.url + "/lineagez")
        ltail = json.loads(lineagez)
        print(f"# lineagez: {ltail['swaps']} swaps, tail:")
        for r in ltail["records"][-3:]:
            print(f"#   version={r['catalog_version']} "
                  f"watermark={r['wal_offset_watermark']} "
                  f"source={r['source']!r}")
        driver.refresh_serving()  # the fix: swap → freshness recovers
        code, _ = _curl(server.url + "/healthz")
        print(f"# healthz (re-swapped): HTTP {code} — freshness OK again")
        assert code == 200

        # ---- healthy: /healthz is 200 with every check OK --------------
        code, body = _curl(server.url + "/healthz")
        report = json.loads(body)
        checks = {k: v["status"] for k, v in report["checks"].items()}
        print(f"# healthz (healthy): HTTP {code}, status="
              f"{report['status']!r}, checks={checks}")
        assert code == 200, body

        # ---- poison: a NaN batch trips the watchdog --------------------
        print("# inject: one NaN micro-batch")
        bad = Ratings.from_arrays(
            np.arange(16, dtype=np.int64) % 500,
            np.arange(16, dtype=np.int64) % 200,
            np.full(16, np.nan, np.float32))
        try:
            online.partial_fit(bad, offset=(0, driver.consumed_offset + 16))
            print("#   ERROR: watchdog did not trip")
            return 1
        except TrainingDivergedError as e:
            print(f"#   tripped: reason={e.reason!r} "
                  f"rolled_back={e.rolled_back} — the poisoned offset was "
                  "never stamped, no checkpoint/catalog swap saw NaNs")

        # ---- the trip froze a postmortem bundle ------------------------
        from large_scale_recommendation_tpu.obs.recorder import (
            validate_bundle,
        )

        bundle = watchdog.last_bundle
        assert bundle is not None, "watchdog trip wrote no bundle"
        manifest = validate_bundle(bundle)  # the schema contract holds
        print(f"# postmortem bundle: {bundle}")
        print(f"#   trigger={manifest['trigger']!r} "
              f"series={manifest['counts']['series']} "
              f"events={manifest['counts']['events']} "
              f"spans={manifest['counts']['spans']} — render it with "
              f"scripts/obs_report.py --bundle {bundle}")
        _, eventz = _curl(server.url + "/eventz")
        kinds = sorted({e["kind"]
                        for e in json.loads(eventz)["recent"]})
        print(f"# eventz: {len(journal)} journaled, kinds={kinds}")

        code, body = _curl(server.url + "/healthz")
        report = json.loads(body)
        print(f"# healthz (tripped): HTTP {code}, "
              f"training={report['checks']['training']['status']!r}")
        assert code == 503, body
        driver.stop_telemetry_export()
        recorder.stop()

        # ---- dump the artifacts ----------------------------------------
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, "healthz.json"), "w") as f:
            json.dump(report, f, indent=2)
        _, prom = _curl(server.url + "/metrics")  # the SERVED text
        prom_path = os.path.join(args.out, "metrics.prom")
        with open(prom_path, "w") as f:
            f.write(prom)
        # the model plane's artifacts (the CI quality smoke parses
        # both): the SERVED /lineagez body and the recorder's series
        # snapshot — eval_*/dataq_* series must be present in it
        _, lineagez_body = _curl(server.url + "/lineagez")
        with open(os.path.join(args.out, "lineagez.json"), "w") as f:
            f.write(lineagez_body)
        recorder.sample()  # one last point: eval_*/dataq_* are current
        with open(os.path.join(args.out, "seriesz.json"), "w") as f:
            json.dump(recorder.snapshot(), f, indent=2)
    jsonl_path = os.path.join(args.out, "metrics.jsonl")
    reg.append_jsonl(jsonl_path)
    trace_path = os.path.join(args.out, "trace.json")
    doc = tracer.to_chrome_trace(trace_path)
    server.stop()

    from large_scale_recommendation_tpu.obs.trace import (
        validate_chrome_trace,
    )

    events = validate_chrome_trace(doc)
    cats = sorted({e["cat"] for e in events})
    print(f"# wrote {prom_path}, {jsonl_path}, {trace_path}, "
          f"{os.path.join(args.out, 'healthz.json')}")
    print(f"# trace: {len(events)} spans, categories {cats} "
          f"— open trace.json in https://ui.perfetto.dev")

    from scripts.obs_report import (
        render_lineage,
        render_quality,
        render_roofline,
        render_snapshot,
    )

    # ---- the model-quality & lineage tables (ISSUE 10) -----------------
    print()
    print(render_lineage(lineage.snapshot()))
    print()
    print(render_quality(recorder.snapshot()))

    # ---- the per-kernel roofline table (ISSUE 9) -----------------------
    # every compile above was captured at the funnel: XLA's own
    # flops/bytes-accessed per compile key, joined with the measured
    # execute walls — rendered here and dumped for
    # `scripts/obs_report.py --roofline`
    roofline = introspector.roofline()
    roofline_path = os.path.join(args.out, "roofline.json")
    with open(roofline_path, "w") as f:
        json.dump(roofline, f, indent=2)
    print(f"# wrote {roofline_path} "
          f"({len(roofline['rows'])} compile keys, "
          f"{roofline['compile_count']} compiles)")
    print()
    print(render_roofline(roofline))
    print()
    print(render_snapshot(reg.snapshot()))
    obs.disable()
    return 0


if __name__ == "__main__":
    sys.exit(main())
