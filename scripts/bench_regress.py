"""Bench regression gate: diff the newest bench round against a baseline.

The bench rounds (``BENCH_r*.json``) are the repo's perf evidence, but
nothing *reads* them across rounds — a 30% serving regression would ship
silently as long as tier-1 stays green. This gate closes that gap::

    python scripts/bench_regress.py                   # newest vs previous
    python scripts/bench_regress.py --baseline BENCH_r03.json
    python scripts/bench_regress.py --key serving_users_per_s=10
    python scripts/bench_regress.py --report out.txt  # also write the table
    python scripts/bench_regress.py --family multichip  # pod_dryrun rounds
                                      # (MULTICHIP_r*.json: pad ratio and
                                      # layout lower-is-better, sharded
                                      # train/ALS throughput higher)
    python scripts/bench_regress.py --family serving  # traffic-sim rounds
                                      # (SERVING_r*.json: p99 latencies
                                      # lower-is-better; fast/exact
                                      # throughput, QPS-at-SLO and
                                      # recall@10 higher)
    python scripts/bench_regress.py --family quality  # model-quality keys
                                      # inside the BENCH rounds: implicit
                                      # ndcg/hr10/coverage + the eval_*
                                      # family higher-is-better,
                                      # eval_rmse lower (ISSUE 10)
    python scripts/bench_regress.py --family ingest   # parallel-ingest
                                      # rounds (INGEST_r*.json: rates and
                                      # scaling efficiency higher-is-
                                      # better; recovery wall + duplicate
                                      # window lower, ISSUE 13)

It loads both rounds, compares the watched keys (higher-is-better rates
by default; ``--lower`` flags wall-clock-style keys), prints a table,
and exits non-zero iff any watched key regressed past its percentage
threshold. Keys missing on either side are reported but only fail under
``--strict`` (machine/config drift between rounds routinely drops
extras). Rounds flagged as CPU-fallback runs (an ``error`` field in the
result) are compared anyway but the caveat is printed — cross-backend
comparisons are noise, and CI runs this step non-blocking for exactly
that reason.

File formats accepted, per side:

- a driver wrapper ``{"n", "cmd", "rc", "tail", "parsed"}`` — ``parsed``
  is used when present; otherwise numeric ``"key": value`` pairs are
  regex-salvaged from the (possibly front-truncated) ``tail``;
- a raw bench JSON line (``{"metric", "value", "unit", "extra": {...}}``);
- a flat ``{key: number}`` dict (hand-built baselines).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# watched keys → allowed regression (percent). Rates: higher is better.
# Thresholds are deliberately loose — rounds run on shared machines with
# real drift; the gate exists to catch step-function regressions, not
# 5% noise (tighten per-key via --key NAME=PCT).
DEFAULT_KEYS: dict[str, float] = {
    "value": 30.0,  # the headline metric line
    "e2e_ratings_per_s_incl_setup": 30.0,
    "serving_users_per_s": 30.0,
    "online_ratings_per_s": 30.0,
    "online_ratings_per_s_steady": 30.0,
    "ps_ratings_per_s": 30.0,
    "als_rank32_rows_per_s": 30.0,
    # achieved-bandwidth gate (ISSUE 6): the DSGD hot loop's whole perf
    # story is effective HBM throughput — a regression here is a kernel
    # regression even when ratings/s noise hides it
    "effective_hbm_gbs": 30.0,
    "pct_of_hbm_peak": 30.0,
    # compile-time gate (ISSUE 9): compile_wall_s is the headline
    # kernel's hand-bracketed warm-up, compile_count /
    # xla_compile_wall_s the introspection hook's whole-run totals —
    # LOWER is better (a bucket-family explosion or a cache miss shows
    # up here long before throughput noise admits it). compile_count is
    # near-deterministic for the same code path, so its threshold is
    # tight; walls ride shared machines, so loose.
    "compile_wall_s": 50.0,
    "xla_compile_wall_s": 50.0,
    "compile_count": 10.0,
}

# watched keys for the MULTICHIP_r*.json trajectory (the pod_dryrun
# acceptance harness, ISSUE 7): sharded-training throughput is
# higher-is-better like every rate; pad ratio and layout bytes are
# LOWER-is-better — a growing pad ratio is a blocking-layout regression
# even when throughput noise hides it. Thresholds are tight for the
# deterministic geometry keys (same code + seed ⇒ same layout) and
# loose for walls-derived rates (shared machines).
MULTICHIP_KEYS: dict[str, float] = {
    "train_ratings_per_s": 30.0,
    "als_rows_per_s": 30.0,
    "max_pad_ratio": 10.0,
    "layout_mb": 10.0,
}

# watched keys for the SERVING_r*.json trajectory (the
# traffic-simulator rounds, ISSUE 8): fast-path/exact throughput, the
# fast-vs-exact ratio, QPS-at-SLO and recall are higher-is-better;
# p99 latencies are LOWER-is-better — a p99 blowup under the overload
# pass is an admission-control regression even when throughput noise
# hides it. Latency thresholds are loose (shared machines double tail
# latencies routinely); recall is tight (same code + seed ⇒ same
# index ⇒ same recall, drift means the retrieval math changed).
SERVING_KEYS: dict[str, float] = {
    "value": 30.0,  # fast-path users/s headline
    "fast_users_per_s": 30.0,
    "exact_users_per_s": 30.0,
    "fast_vs_exact": 30.0,
    "qps_at_slo": 30.0,
    "recall_at_10": 5.0,
    "p99_ms": 50.0,
    "overload_fast_p99_ms": 50.0,
}

# watched keys for the MODEL-QUALITY trajectory (ISSUE 10): the keys
# the BENCH rounds ACTUALLY carry — the implicit-ranking metrics
# (sampled-negative protocol, obs.quality.sampled_ranking_metrics —
# planted-structure-pinned) and the headline run's holdout rmse_final.
# Ranking metrics and coverage are higher-is-better; rmse is
# LOWER-is-better. The online evaluator's eval_* family is covered by
# the DIRECTION rules below (watch via --key when a quality-bearing
# round carries them), not listed here: a default watch key no round
# can contain is permanent "missing" noise and an unconditional
# --strict failure. Thresholds loose: ranking metrics on synthetic
# workloads carry sampling noise, and the gate exists to catch the
# ndcg-0.003-class collapse, not 5% drift.
QUALITY_KEYS: dict[str, float] = {
    "als_implicit_ndcg": 30.0,
    "als_implicit_hr10": 30.0,
    "als_implicit_coverage": 30.0,
    "rmse_final": 30.0,
}

# watched keys for the INGEST_r*.json trajectory (the
# N_CONSUMERS rounds, ISSUE 13): aggregate/per-N ingest rates and the
# scaling efficiency (rate_N / (N·rate_1)) are higher-is-better;
# recovery-after-kill wall and the per-partition duplicate window are
# LOWER-is-better — a growing replay window is a barrier-cadence
# regression even when throughput noise hides it. Rates loose (shared
# machines, and the curve is thread-scheduling sensitive); the
# duplicate window is near-deterministic (the barrier cadence bounds
# it), so tight.
INGEST_KEYS: dict[str, float] = {
    "value": 30.0,  # max-N aggregate ratings/s headline
    "ingest_n1_ratings_per_s": 30.0,
    "ingest_n4_ratings_per_s": 30.0,
    "scaling_eff_n4": 30.0,
    "recovery_s": 50.0,
    "duplicate_window_batches_max": 10.0,
}

# per-family round-file prefix + default watch set. The quality family
# reads the BENCH rounds — quality keys ride inside the bench extras,
# they just gate under their own watch set (and direction rules).
# watched keys for the TIERED_r*.json trajectory (the
# tiered-store rounds, ISSUE 17): the tiered ingest rate and its
# fraction of the all-HBM baseline regress when they DROP; the Zipfian
# hit rate is near-deterministic (same trace, same slot budget), so
# tight; prefetch stall time and eviction count regress UP — a rising
# eviction count at fixed capacity means the prefetcher stopped
# keeping the working set resident.
TIER_KEYS: dict[str, float] = {
    "value": 30.0,  # tiered ratings/s headline
    "tier_hit_rate": 10.0,
    "tiered_vs_hbm_frac": 30.0,
    "tier_prefetch_wait_s": 50.0,
    "tier_evictions": 30.0,
}

FAMILIES = {
    "bench": ("BENCH", DEFAULT_KEYS),
    "multichip": ("MULTICHIP", MULTICHIP_KEYS),
    "serving": ("SERVING", SERVING_KEYS),
    "quality": ("BENCH", QUALITY_KEYS),
    "ingest": ("INGEST", INGEST_KEYS),
    "tier": ("TIERED", TIER_KEYS),
}

# keys where HIGHER is explicitly better (throughputs, achieved
# bandwidth). These win over any accidental DEFAULT_LOWER substring
# match — a throughput key must NEVER be gated as lower-is-better, and
# before this list only ``*_wall_s``-style keys had an explicit rule
# while every rate relied on the absence of a pattern collision.
DEFAULT_HIGHER = ("_ratings_per_s", "_rows_per_s", "_users_per_s",
                  "_per_s", "effective_hbm_gbs", "pct_of_hbm_peak",
                  "_hbm_gbs", "_tflops", "_mbps", "qps_at_slo",
                  "recall_at", "_vs_exact",
                  # quality family (ISSUE 10): ranking metrics and
                  # catalog coverage regress when they DROP
                  "_ndcg", "_hr10", "_hr_at", "ndcg_at", "coverage",
                  # ingest family (ISSUE 13): the N-consumer scaling
                  # efficiency regresses when it drops
                  "scaling_eff",
                  # rank-sharded 2-D mesh pass (ISSUE 16): the 'model'-
                  # axis training throughput regresses when it drops
                  # (already covered by _ratings_per_s — listed so the
                  # direction is pinned even if the key is renamed
                  # without the suffix)
                  "rank_sharded",
                  # tiered store (ISSUE 17): the hot-set hit rate
                  # regresses when it drops. No suffix rule covers it —
                  # "_hit_rate" shares no pattern with _hr10/_hr_at —
                  # so the direction is pinned explicitly.
                  "tier_hit_rate",
                  # rollout budget plane (ISSUE 19): the remaining
                  # error budget regresses when it DROPS (burn eats
                  # it). No LOWER pattern matches the key — "_rmse"
                  # does not occur in "error_budget_remaining" — and
                  # the HIGHER rule wins precedence regardless.
                  "error_budget_remaining")

# keys where LOWER is better (walls, latencies, pad/layout overheads,
# compile counts, eval error, ingest→servable critical-path walls)
# when watched explicitly. ``critical_path`` covers
# critical_path_total_s and the per-stage critical_path_s keys
# (ISSUE 12): a growing ingest→servable wall is a freshness regression
# even when throughput noise hides it.
DEFAULT_LOWER = ("_wall_s", "_ms_", "time_to_", "_s_p", "_pad_ratio",
                 "layout_mb", "layout_bytes", "p99_ms", "p50_ms",
                 "shed_frac", "compile_count", "_rmse", "eval_rmse",
                 "rmse_final", "staleness_s", "critical_path",
                 # ingest family (ISSUE 13): recovery-after-kill wall
                 # and the per-partition replay window regress UP
                 "recovery_s", "duplicate_window",
                 # contention plane (ISSUE 14): a rising Amdahl serial
                 # fraction or per-rung lock-wait total is a
                 # serialization regression even when throughput noise
                 # hides it (covers serial_fraction_n<K> and
                 # lock_wait_s_total_n<K>). Watched via --key on rounds
                 # that carry them — not in the family default set: the
                 # pre-ISSUE-14 committed round lacks the keys, and a
                 # default watch key the baseline can't contain is
                 # permanent "missing" noise (the PR 10/13 lesson).
                 "serial_fraction", "lock_wait",
                 # rank-sharded footprint (ISSUE 16): growing per-device
                 # factor+catalog bytes (or the ratio vs model=1) is a
                 # sharding regression — the whole point of the 'model'
                 # axis is dividing them. Covers rank_shard_bytes_per_
                 # device[_m1] and rank_shard_bytes_ratio_vs_m1. Watched
                 # via --key, NOT in MULTICHIP_KEYS: rounds before r07
                 # lack the keys (the PR 10/13 lesson again).
                 "rank_shard_bytes",
                 # tiered store (ISSUE 17): time the trainer spends
                 # stalled on demand faults, and the eviction count at
                 # fixed slot capacity, both regress UP. Note
                 # tier_prefetch_wait_s does NOT collide with the
                 # _per_s HIGHER pattern ("_pre" != "_per") — pinned by
                 # the direction tests.
                 "prefetch_wait", "tier_evictions",
                 # transfer plane (ISSUE 18): steady-state retraces,
                 # implicit hot-path transfers, and blocked device↔host
                 # wait all regress UP — any of them growing means the
                 # pow2-padding/compile-cache or explicit-staging
                 # contract broke. Watched via --key on rounds that
                 # carry them, NOT in any family default set: committed
                 # rounds predating ISSUE 18 lack the keys (the
                 # PR 10/13 lesson). "transfer_wait" shares no pattern
                 # with the _per_s HIGHER rule; "retrace" and
                 # "implicit_transfers" collide with nothing — pinned
                 # by the direction tests.
                 "retrace", "implicit_transfers", "transfer_wait",
                 # rollout budget plane (ISSUE 19): the multi-window
                 # SLO burn pair (slo_burn_rate_fast/_slow) and the
                 # canary verdict latency (batches-to-ROLLBACK on a
                 # poisoned leg) both regress UP. Watched via --key on
                 # rounds that carry them, NOT in SERVING_KEYS:
                 # SERVING_r01 predates the plane (the PR 10/13
                 # lesson). "burn_rate" and "verdict_latency" collide
                 # with no HIGHER pattern — error_budget_remaining
                 # (higher-better) contains neither — pinned by the
                 # direction tests.
                 "burn_rate", "verdict_latency",
                 # request plane (ISSUE 20): per-stage serving walls
                 # (request_stage_*_s_p99 and friends) and per-request
                 # queue wait both regress UP — a stage's p99 growing
                 # means a serving seam got slower, queue_wait growing
                 # means admission/batching backpressure. Watched via
                 # --key on rounds that carry them, NOT in
                 # SERVING_KEYS: committed rounds predating ISSUE 20
                 # lack the keys (the PR 10/13 lesson). Neither
                 # "request_stage" nor "queue_wait" is a substring of
                 # any HIGHER pattern — pinned by the direction tests.
                 "request_stage", "queue_wait")

_NUM_PAIR = re.compile(
    r'"([A-Za-z_][A-Za-z0-9_]*)":\s*(-?\d+(?:\.\d+)?(?:[eE][+-]?\d+)?)')


def _salvage_numeric_pairs(text: str) -> dict[str, float]:
    """Numeric ``"key": value`` pairs from a (possibly front-truncated)
    stdout tail — array elements don't match (no preceding key), so
    ``rmse_curve`` entries and friends are skipped."""
    return {k: float(v) for k, v in _NUM_PAIR.findall(text)}


def flatten_result(doc: dict) -> dict[str, float]:
    """One flat {key: number} view of any accepted format. The headline
    ``value`` keeps its name; ``extra.*`` keys are lifted to top level
    (they don't collide — bench extras never use 'value')."""
    if "tail" in doc or "parsed" in doc:  # driver wrapper
        parsed = doc.get("parsed")
        if isinstance(parsed, dict):
            doc = parsed
        else:
            return _salvage_numeric_pairs(doc.get("tail") or "")
    out: dict[str, float] = {}
    if isinstance(doc.get("value"), (int, float)):
        out["value"] = float(doc["value"])
    extra = doc.get("extra")
    if isinstance(extra, dict):
        for k, v in extra.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out[k] = float(v)
    if not out:  # flat {key: number} baseline
        out = {k: float(v) for k, v in doc.items()
               if isinstance(v, (int, float)) and not isinstance(v, bool)}
    return out


_ERR_PAIR = re.compile(r'"error":\s*"((?:[^"\\]|\\.)*)"')


def load_result(path: str) -> tuple[dict[str, float], str | None]:
    """(flat metrics, caveat-or-None) for one bench file."""
    with open(path) as f:
        doc = json.load(f)
    inner = doc.get("parsed") if isinstance(doc.get("parsed"), dict) else doc
    err = inner.get("error") or doc.get("error")
    if not err and isinstance(doc.get("tail"), str):
        # tail-salvaged rounds (parsed=null) carry the CPU-fallback
        # caveat inside the tail text — a cross-backend comparison must
        # not print caveat-free
        m = _ERR_PAIR.search(doc["tail"])
        if m:
            err = m.group(1)
    return flatten_result(doc), (str(err) if err else None)


def find_rounds(directory: str = REPO, prefix: str = "BENCH") -> list[str]:
    """``<prefix>_r*.json`` sorted by round number, oldest first
    (``BENCH`` bench rounds, ``MULTICHIP`` pod_dryrun rounds)."""
    paths = glob.glob(os.path.join(directory, f"{prefix}_r*.json"))

    def round_no(p: str) -> int:
        m = re.search(rf"{prefix}_r(\d+)\.json$", p)
        return int(m.group(1)) if m else -1

    return sorted((p for p in paths if round_no(p) >= 0), key=round_no)


def is_lower_better(key: str, lower_flags: set[str]) -> bool:
    if key in lower_flags:
        return True  # an explicit --lower flag always wins
    if any(pat in key for pat in DEFAULT_HIGHER):
        return False  # rates/bandwidths are higher-is-better, full stop
    return any(pat in key for pat in DEFAULT_LOWER)


def compare(baseline: dict[str, float], current: dict[str, float],
            keys: dict[str, float],
            lower_flags: set[str] | None = None) -> list[dict]:
    """One row per watched key: baseline, current, delta %, verdict.
    Verdicts: ``ok`` / ``REGRESSION`` / ``missing`` (either side)."""
    lower_flags = lower_flags or set()
    rows = []
    for key, pct in keys.items():
        b, c = baseline.get(key), current.get(key)
        row = {"key": key, "baseline": b, "current": c,
               "threshold_pct": pct, "delta_pct": None, "verdict": "missing"}
        if b is not None and c is not None:
            lower = is_lower_better(key, lower_flags)
            delta = ((c - b) / abs(b) * 100.0) if b else 0.0
            row["delta_pct"] = delta
            worse = -delta if not lower else delta
            row["verdict"] = "REGRESSION" if worse > pct else "ok"
        rows.append(row)
    return rows


def render_table(rows: list[dict], baseline_path: str,
                 current_path: str) -> str:
    sys.path.insert(0, REPO)  # absolute, so the script works from any cwd
    from scripts.obs_report import format_table

    def fmt(v):
        if v is None:
            return "-"
        if isinstance(v, float):
            return f"{v:,.1f}" if abs(v) >= 100 else f"{v:.4g}"
        return str(v)

    header = ("key", "baseline", "current", "delta%", "allowed%", "verdict")
    body = [(r["key"], fmt(r["baseline"]), fmt(r["current"]),
             fmt(r["delta_pct"]), fmt(r["threshold_pct"]), r["verdict"])
            for r in rows]
    lines = [f"baseline: {baseline_path}", f"current:  {current_path}", ""]
    lines.extend(format_table(header, body))
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--family", choices=sorted(FAMILIES), default="bench",
                    help="round family to gate: 'bench' (BENCH_r*.json, "
                         "default), 'multichip' (MULTICHIP_r*.json "
                         "pod_dryrun rounds — pad ratio lower-is-better, "
                         "sharded throughput higher-is-better) or "
                         "'serving' (SERVING_r*.json traffic-sim rounds "
                         "— p99 lower-is-better, throughput/QPS-at-SLO/"
                         "recall higher-is-better) or 'quality' (the "
                         "model-quality keys inside the BENCH rounds — "
                         "ranking/coverage higher-is-better, eval_rmse "
                         "lower) or 'ingest' (INGEST_r*.json parallel-"
                         "ingest rounds — rates/scaling-efficiency "
                         "higher-is-better, recovery wall and duplicate "
                         "window lower-is-better)")
    ap.add_argument("--current", default=None,
                    help="current round file (default: newest round of "
                         "the family)")
    ap.add_argument("--baseline", default=None,
                    help="baseline file (default: previous round of the "
                         "family)")
    ap.add_argument("--key", action="append", default=[],
                    metavar="NAME[=PCT]",
                    help="watch NAME at PCT%% (repeatable; replaces the "
                         "default key set when given)")
    ap.add_argument("--threshold", type=float, default=None,
                    help="override every watched key's threshold %%")
    ap.add_argument("--lower", action="append", default=[], metavar="NAME",
                    help="NAME is lower-is-better (walls/latency)")
    ap.add_argument("--report", default=None,
                    help="also write the table to this path")
    ap.add_argument("--strict", action="store_true",
                    help="missing watched keys fail too")
    args = ap.parse_args(argv)

    prefix, family_keys = FAMILIES[args.family]
    current, baseline = args.current, args.baseline
    if current is None or baseline is None:
        rounds = find_rounds(prefix=prefix)
        if current is None:
            if not rounds:
                print(f"no {prefix}_r*.json rounds found — nothing to gate")
                return 2 if args.strict else 0
            current = rounds[-1]
        if baseline is None:
            prior = [p for p in rounds if os.path.abspath(p)
                     != os.path.abspath(current)]
            if not prior:
                print(f"only one round ({current}) — no baseline to "
                      "diff against")
                return 2 if args.strict else 0
            baseline = prior[-1]

    if args.key:
        keys = {}
        for spec in args.key:
            name, _, pct = spec.partition("=")
            keys[name] = float(pct) if pct else 30.0
    else:
        keys = dict(family_keys)
    if args.threshold is not None:
        keys = {k: args.threshold for k in keys}

    base_flat, base_caveat = load_result(baseline)
    cur_flat, cur_caveat = load_result(current)
    rows = compare(base_flat, cur_flat, keys, set(args.lower))
    table = render_table(rows, baseline, current)
    caveats = []
    if base_caveat:
        caveats.append(f"baseline caveat: {base_caveat}")
    if cur_caveat:
        caveats.append(f"current caveat:  {cur_caveat}")
    out = table + ("\n\n" + "\n".join(caveats) if caveats else "")
    print(out)
    if args.report:
        with open(args.report, "w") as f:
            f.write(out + "\n")

    regressed = [r["key"] for r in rows if r["verdict"] == "REGRESSION"]
    missing = [r["key"] for r in rows if r["verdict"] == "missing"]
    if regressed:
        print(f"\nREGRESSION in: {', '.join(regressed)}")
        return 1
    if missing and args.strict:
        print(f"\nmissing watched keys (strict): {', '.join(missing)}")
        return 1
    print("\nno regressions in watched keys")
    return 0


if __name__ == "__main__":
    sys.exit(main())
