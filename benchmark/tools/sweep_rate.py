"""Find the offered rate of an open-loop serving cell, once, on the chip.

    python3 benchmark/tools/sweep_rate.py --workload <cell> --knee 3000,4000,5000,6000,7000 --knee-seconds 20
    python3 benchmark/tools/sweep_rate.py --workload <cell> --rates 2000,2500,3000,3500 --seeds 1,2,3 --seconds 48

One process builds the engine once (set-up is most of a run) and drives one
open-loop window per (rate, seed) through the same ``run_open_loop`` the
cell uses. ``--knee`` reports for each rate whether the backlog grew (the
median latency of the window's last fifth against its first fifth) so the
knee, the highest rate without a growing backlog, can be read off;
``--rates`` reports the p95 of each seed and the spread of the p95s (the
distance between their quartiles, or max − min for fewer than four, as a
share of their median). The result is one number written by hand into the
traffic file: the benchmark itself never searches for a rate.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402


def one_window(engine, cfg, traffic, rate, seed, seconds):
    from benchmark import loadgen
    from benchmark.spans import Spans

    t = dict(traffic, offered_users_per_s=float(rate))
    arrivals, requests = loadgen.open_loop_schedule(
        t, seconds, seed, cfg["num_users"])
    gc.collect()
    gc.freeze()  # as harness.Window does for the cell's own window
    try:
        out = loadgen.run_open_loop(engine, requests, arrivals,
                                    int(t["flush_rows"]),
                                    float(t["deadline_ms"]) / 1e3, Spans())
    finally:
        gc.unfreeze()
    lat = np.where(np.isnan(out["latency"]), np.inf, out["latency"]) * 1e3
    users = sum(len(r) for r, res in zip(requests, out["results"])
                if loadgen.answered(res))
    fifth = max(len(lat) // 5, 1)
    return {"rate": rate, "seed": seed, "users_per_s": users / out["wall"],
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "p99_ms": float(np.percentile(lat, 99)),
            "first_fifth_p50_ms": float(np.median(lat[:fifth])),
            "last_fifth_p50_ms": float(np.median(lat[-fifth:])),
            "flushes": len(out["flushes"]),
            "rows_p50": float(np.median([f[0] for f in out["flushes"]])),
            "late_p99_ms": float(np.percentile(out["late"], 99)) * 1e3}


def spread(values) -> float:
    if len(values) >= 4:
        q = statistics.quantiles(values, n=4)
        width = q[2] - q[0]
    else:
        width = max(values) - min(values)
    return width / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--knee", default="")
    ap.add_argument("--knee-seconds", type=float, default=20.0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--seconds", type=float, default=48.0)
    args = ap.parse_args()

    from benchmark import datagen, harness
    from benchmark.runners import serve

    cell = harness.resolve_cell(args.workload)
    harness.start_on_chip(cell.chips)
    cfg, traffic = cell.config, cell.traffic
    U, V = datagen.serving_factors(0, num_users=cfg["num_users"],
                                   num_items=cfg["num_items"],
                                   rank=cfg["num_factors"])
    engine = serve.build_engine(cfg, U, V)
    rng = np.random.default_rng(0)
    for rows in serve.buckets_reached(cfg, traffic):
        engine.submit(rng.integers(0, cfg["num_users"], rows))
        engine.flush()
    for rate in [float(x) for x in args.knee.split(",") if x]:
        r = one_window(engine, cfg, traffic, rate, 1, args.knee_seconds)
        r["backlog_grew"] = (r["last_fifth_p50_ms"]
                             > 1.5 * r["first_fifth_p50_ms"] + 10.0)
        print("knee", json.dumps(r), flush=True)
    for rate in [float(x) for x in args.rates.split(",") if x]:
        rows = [one_window(engine, cfg, traffic, rate, int(s), args.seconds)
                for s in args.seeds.split(",")]
        for r in rows:
            print("rate", json.dumps(r), flush=True)
        p95 = [r["p95_ms"] for r in rows]
        print("spread", json.dumps({
            "rate": rate, "p95_ms": p95, "median": statistics.median(p95),
            "spread_share": spread(p95)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
