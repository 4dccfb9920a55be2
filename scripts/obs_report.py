"""Render a metrics snapshot as a human-readable table.

One command to see serving p99, ingest lag, and train step time side by
side::

    python scripts/obs_report.py metrics.jsonl       # last snapshot line
    python scripts/obs_report.py snapshot.json       # single snapshot
    python scripts/obs_report.py metrics.jsonl --name serving_flush_s
    python scripts/obs_report.py http://127.0.0.1:8080/varz --watch 2
    python scripts/obs_report.py --bundle postmortem/bundle_watchdog_trip_000
    python scripts/obs_report.py --roofline http://127.0.0.1:8080/rooflinez
    python scripts/obs_report.py --roofline roofline.json
    python scripts/obs_report.py --lineage http://127.0.0.1:8080/lineagez
    python scripts/obs_report.py --quality http://127.0.0.1:8080/seriesz
    python scripts/obs_report.py --critical-path \
        http://127.0.0.1:8080/criticalpathz

``--bundle <dir>`` renders a postmortem bundle (``obs.recorder``):
validates it first (``validate_bundle`` — a torn bundle is an error,
not a pretty table), then prints the trigger/detail, the health report,
the event tail, and a per-series summary of the recorded lead-up.

``--roofline <src>`` renders the live per-kernel roofline table
(``obs.introspect``): one row per compile key with XLA's cost-analysis
FLOPs/bytes-accessed, the measured execute wall, achieved GB/s and
TFLOP/s, pct-of-HBM/FP32-peak, and the XLA-vs-hand-model bytes
cross-check. ``src`` is a ``/rooflinez`` URL on a live server or a
dumped roofline JSON file (``examples/obs_demo.py`` writes one).

``--lineage <src>`` renders catalog lineage (``obs.lineage``): the
freshness summary (servable watermark vs latest ingest — the staleness
SLO's inputs) and one row per swap's provenance record. ``src`` is a
``/lineagez`` URL, a dumped lineage JSON, or a bundle ``lineage.json``.

``--quality <src>`` renders the model-quality plane: the lead-up of
every ``eval_*`` / ``dataq_*`` / ``lineage_*`` flight-recorder series
from a ``/seriesz`` URL or dumped series JSON (``examples/obs_demo.py``
writes one), or the frozen instrument values from a bundle
``lineage.json``.

``--critical-path <src>`` renders the ingest→servable critical path
(``obs.disttrace.CriticalPathAnalyzer``): the per-stage attribution
summary (queue wait / train apply / swap lag / flush wait) and the
newest completed samples. ``src`` is a ``/criticalpathz`` URL or a
dumped snapshot JSON.

``--transfers <src>`` renders the device↔host transfer plane
(``obs.transfers.TransferLedger``): the per-site ledger (bytes and
counts per direction, blocked wait, derived effective GB/s), the
implicit-transfer attribution, and the retrace ring with its
human-readable signature diffs. ``src`` is a ``/transferz`` URL, a
dumped snapshot JSON (the CI steady-state gate writes one), a bundle
``transfers.json``, or a fleet ``/transferz`` pod aggregate.

``--budget <src>`` renders the rollout plane
(``obs.budget.RolloutBudget``): service-level multi-window burn
rates, the per-catalog-version cohort attribution table (served /
shed / attainment / fast burn / remaining budget per version), and
the canary verdict tail with any un-acted-on ROLLBACKs. ``src`` is a
``/budgetz`` URL, a dumped snapshot JSON, a bundle ``budget.json``,
or a fleet ``/budgetz`` pod aggregate.

``--contention <src>`` renders the concurrency & saturation plane
(``obs.contention.SaturationAnalyzer``): the Amdahl window summary
(consumers, efficiency, Karp–Flatt serial fraction, projected speedup
at 2N), the contended-lock table, and per-partition busy/blocked
shares joined with their ``streams_*`` gauges. ``src`` is a
``/contentionz`` URL, a dumped snapshot JSON, a bundle
``contention.json``, or a fleet ``/contentionz`` pod aggregate.

Input is a single-snapshot JSON file, a JSONL metrics log
(``MetricsRegistry.append_jsonl``), or — live mode — an HTTP URL to a
running ``obs.server.ObsServer``'s ``/varz`` route. For JSONL the LAST
line is rendered (``--line N`` picks another, 0-based). ``--name
SUBSTR`` filters rows.

``--watch N`` polls the source every N seconds and renders *deltas and
rates* between consecutive snapshots — counters show Δ and Δ/s,
histograms show new observations per second next to their current
p50/p99 — so the live endpoint is usable from a terminal without a
Prometheus stack. ``--count M`` bounds the number of polls (default:
until interrupted).

The renderers are importable (``render_snapshot``, ``render_deltas``,
``fetch_snapshot``) — the demo and tests drive them in-process.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def load_snapshot(path: str, line: int | None = None) -> dict:
    """Load a snapshot from a JSON file or a JSONL log (last line, or
    ``line`` 0-based)."""
    with open(path) as f:
        text = f.read()
    if line is None:
        # whole-file parse first: a single snapshot may be
        # pretty-printed (multi-line), which is NOT line-per-record JSONL
        try:
            doc = json.loads(text)
            if isinstance(doc, dict):
                return doc
        except json.JSONDecodeError:
            pass
    lines = [ln for ln in text.splitlines() if ln.strip()]
    if not lines:
        raise ValueError(f"{path} is empty")
    return json.loads(lines[-1 if line is None else line])


def _fmt(v) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        if v == 0:
            return "0"
        if abs(v) >= 1000:
            return f"{v:,.0f}"
        if abs(v) >= 1:
            return f"{v:.3g}"
        return f"{v:.3g}"
    return str(v)


def _label_str(labels: dict) -> str:
    return ",".join(f"{k}={v}" for k, v in sorted(labels.items()))


def render_snapshot(snap: dict, name_filter: str | None = None) -> str:
    """The table: counters/gauges first (name, labels, value), then
    histograms (count, mean, p50/p90/p99, max)."""
    metrics = snap.get("metrics", [])
    if name_filter:
        metrics = [m for m in metrics if name_filter in m["name"]]
    scalars = [m for m in metrics if m["type"] in ("counter", "gauge")]
    hists = [m for m in metrics if m["type"] == "histogram"]
    out: list[str] = []

    if scalars:
        rows = [(m["name"], _label_str(m["labels"]), _fmt(m["value"]),
                 m["type"]) for m in scalars]
        w0 = max(len("metric"), *(len(r[0]) for r in rows))
        w1 = max(len("labels"), *(len(r[1]) for r in rows))
        w2 = max(len("value"), *(len(r[2]) for r in rows))
        out.append(f"{'metric':<{w0}}  {'labels':<{w1}}  "
                   f"{'value':>{w2}}  type")
        out.append("-" * (w0 + w1 + w2 + 12))
        for r in rows:
            out.append(f"{r[0]:<{w0}}  {r[1]:<{w1}}  {r[2]:>{w2}}  {r[3]}")
        out.append("")

    if hists:
        cols = ("count", "mean", "p50", "p90", "p99", "max")
        rows = [(m["name"], _label_str(m["labels"]),
                 *(_fmt(m.get(c)) for c in cols)) for m in hists]
        w0 = max(len("histogram"), *(len(r[0]) for r in rows))
        w1 = max(len("labels"), *(len(r[1]) for r in rows))
        ws = [max(len(c), *(len(r[2 + j]) for r in rows))
              for j, c in enumerate(cols)]
        head = f"{'histogram':<{w0}}  {'labels':<{w1}}"
        for j, c in enumerate(cols):
            head += f"  {c:>{ws[j]}}"
        out.append(head)
        out.append("-" * len(head))
        for r in rows:
            line = f"{r[0]:<{w0}}  {r[1]:<{w1}}"
            for j in range(len(cols)):
                line += f"  {r[2 + j]:>{ws[j]}}"
            out.append(line)
        out.append("")

    if not out:
        return "(no metrics)"
    return "\n".join(out)


def fetch_snapshot(src: str, line: int | None = None,
                   timeout: float = 5.0) -> dict:
    """One snapshot from a file path or a live ``/varz`` URL."""
    if src.startswith(("http://", "https://")):
        import urllib.request

        with urllib.request.urlopen(src, timeout=timeout) as resp:
            return json.loads(resp.read().decode("utf-8"))
    return load_snapshot(src, line)


def _index(snap: dict) -> dict:
    return {(m["name"], tuple(sorted(m["labels"].items()))): m
            for m in snap.get("metrics", [])}


def snapshot_deltas(prev: dict, cur: dict, dt: float) -> list[dict]:
    """Per-instrument deltas between two snapshots: counters get
    ``delta``/``rate`` (per second), histograms get observation-count
    deltas alongside their current quantiles, gauges get their current
    value plus the change since the last snapshot (``delta``, no rate —
    a gauge delta is rarely a rate, but it decides whether the row is
    "active" in watch mode: a moving lag gauge must show up). New
    instruments count from zero. ``dt`` ≤ 0 suppresses rates."""
    before = _index(prev)
    rows = []
    for key, m in _index(cur).items():
        p = before.get(key)
        row = {"name": m["name"], "labels": m["labels"], "type": m["type"]}
        if m["type"] in ("counter", "gauge"):
            row["value"] = m["value"]
            delta = m["value"] - (p["value"] if p else 0.0)
            row["delta"] = delta
            if m["type"] == "counter":
                row["rate"] = delta / dt if dt > 0 else None
        else:  # histogram
            delta = m["count"] - (p["count"] if p else 0)
            row["value"] = m["count"]
            row["delta"] = delta
            row["rate"] = delta / dt if dt > 0 else None
            row["p50"] = m.get("p50")
            row["p99"] = m.get("p99")
        rows.append(row)
    rows.sort(key=lambda r: (r["name"], sorted(r["labels"].items())))
    return rows


def format_table(header: tuple, rows: list) -> list[str]:
    """Fixed-width left-aligned table lines (header, dashed rule, one
    line per row of pre-formatted strings) — ONE copy of the layout
    logic, shared with ``scripts/bench_regress.py``'s report table."""
    widths = [max(len(header[i]), *(len(r[i]) for r in rows)) if rows
              else len(header[i]) for i in range(len(header))]
    lines = ["  ".join(h.ljust(widths[i]) for i, h in enumerate(header))]
    lines.append("-" * (sum(widths) + 2 * (len(widths) - 1)))
    for r in rows:
        lines.append("  ".join(r[i].ljust(widths[i])
                               for i in range(len(header))))
    return lines


def render_deltas(prev: dict, cur: dict, dt: float,
                  name_filter: str | None = None,
                  active_only: bool = False) -> str:
    """Delta/rate table between two snapshots. ``active_only`` drops
    rows whose counters/gauges/histograms saw nothing this interval."""
    rows = snapshot_deltas(prev, cur, dt)
    if name_filter:
        rows = [r for r in rows if name_filter in r["name"]]
    if active_only:
        rows = [r for r in rows if r.get("delta")]
    if not rows:
        return "(no activity)" if active_only else "(no metrics)"
    cells = [(r["name"], _label_str(r["labels"]), r["type"],
              _fmt(r["value"]), _fmt(r.get("delta")),
              _fmt(r.get("rate")), _fmt(r.get("p50")), _fmt(r.get("p99")))
             for r in rows]
    header = ("metric", "labels", "type", "value", "Δ", "Δ/s", "p50", "p99")
    return "\n".join(format_table(header, cells))


def watch(src: str, interval_s: float, count: int | None = None,
          name_filter: str | None = None, out=sys.stdout) -> int:
    """Poll ``src`` every ``interval_s`` and render deltas/rates. The
    first poll prints the full snapshot (nothing to diff yet)."""
    prev = fetch_snapshot(src)
    print(f"# {src} — snapshot at {time.strftime('%H:%M:%S')}", file=out)
    print(render_snapshot(prev, name_filter), file=out)
    polls = 0
    while count is None or polls < count:
        time.sleep(interval_s)
        cur = fetch_snapshot(src)
        dt = cur.get("time", 0.0) - prev.get("time", 0.0)
        if dt <= 0:
            dt = interval_s
        print(f"\n# Δ over {dt:.1f}s at {time.strftime('%H:%M:%S')}",
              file=out)
        print(render_deltas(prev, cur, dt, name_filter, active_only=True),
              file=out)
        prev = cur
        polls += 1
    return 0


def render_bundle(directory: str, name_filter: str | None = None,
                  event_tail: int = 20) -> str:
    """Validate + render one postmortem bundle directory."""
    import os

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from large_scale_recommendation_tpu.obs.recorder import load_bundle

    docs = load_bundle(directory)  # validates; raises on a torn bundle
    manifest = docs["manifest"]

    out = [f"# postmortem bundle {directory}",
           f"trigger   : {manifest['trigger']}",
           f"created   : "
           f"{time.strftime('%Y-%m-%d %H:%M:%S', time.localtime(manifest['created']))}",
           f"detail    : {json.dumps(manifest['detail'])}",
           f"contents  : {manifest['counts']['series']} series, "
           f"{manifest['counts']['events']} events, "
           f"{manifest['counts']['spans']} spans", ""]

    health = docs["health"]
    out.append(f"health    : {health.get('status', 'unknown')}")
    for name, res in sorted(health.get("checks", {}).items()):
        if res.get("status") != "ok":
            out.append(f"  {name}: {res['status']} "
                       f"{json.dumps(res.get('detail', {}))[:120]}")
    out.append("")

    events = docs["events"]
    if events:
        out.append(f"event tail (last {min(event_tail, len(events))} "
                   f"of {len(events)}):")
        rows = [(time.strftime("%H:%M:%S", time.localtime(e["time"])),
                 e["severity"], e["kind"],
                 "-" if e.get("span_id") is None else str(e["span_id"]),
                 json.dumps(e.get("detail", {}))[:60])
                for e in events[-event_tail:]]
        out.extend(format_table(("time", "sev", "kind", "span", "detail"),
                                rows))
        out.append("")

    series = docs["series"].get("series", {})
    keys = sorted(k for k in series
                  if name_filter is None or name_filter in k)
    if keys:
        out.append(f"series lead-up ({len(keys)} of {len(series)}):")
        rows = []
        for key in keys:
            vals = [v for _, v in series[key]["points"]] or [None]
            numeric = [v for v in vals if isinstance(v, (int, float))]
            rows.append((key, str(len(series[key]["points"])),
                         _fmt(vals[0]),
                         _fmt(min(numeric) if numeric else None),
                         _fmt(max(numeric) if numeric else None),
                         _fmt(vals[-1])))
        out.extend(format_table(
            ("series", "n", "first", "min", "max", "last"), rows))
        out.append("")
    out.append("(full registry snapshot: metrics.json; span tail: "
               "trace.json — Perfetto-loadable)")
    return "\n".join(out)


def render_roofline(doc: dict, name_filter: str | None = None) -> str:
    """Render one roofline document (``/rooflinez`` body or
    ``Introspector.roofline()``): header with compile totals + chip
    peaks, then one row per compile key. Wall-less rows (key compiled
    but never executed a steady-state span) render with ``-`` in the
    measured columns rather than being dropped — a compiled-but-unused
    kernel is information."""
    rows = doc.get("rows", [])
    if name_filter:
        rows = [r for r in rows if name_filter in r["key"]]
    out = [
        "# per-kernel roofline "
        f"(HBM peak {_fmt(doc.get('hbm_peak_gbs'))} GB/s, "
        f"fp32 peak {_fmt(doc.get('fp32_peak_tflops'))} TFLOP/s)",
        f"compiles: {doc.get('compile_count', '-')} totalling "
        f"{_fmt(doc.get('compile_wall_s'))}s"
        + (f"; note: {doc['note']}" if doc.get("note") else ""),
        "",
    ]
    if not rows:
        out.append("(no compile records)")
        return "\n".join(out)

    def num(v, scale=1.0):
        return "-" if v is None else _fmt(v * scale)

    cells = [(r["key"][:64], str(r["compiles"]),
              num(r.get("compile_wall_s")),
              num(r.get("xla_flops"), 1e-9),
              num(r.get("xla_bytes_accessed"), 1e-6),
              str(r.get("execute_count", 0)),
              num(r.get("wall_per_exec_s"), 1e3),
              num(r.get("achieved_gbs")),
              num(r.get("pct_of_hbm_peak")),
              num(r.get("pct_of_fp32_peak")),
              num(r.get("xla_vs_model_bytes")))
             for r in sorted(rows,
                             key=lambda r: -(r.get("xla_bytes_accessed")
                                             or 0))]
    header = ("compile key", "comp", "comp_s", "GFLOP", "MB_acc", "execs",
              "ms/exec", "GB/s", "%HBM", "%FP32", "xla/model")
    out.extend(format_table(header, cells))
    return "\n".join(out)


def render_lineage(doc: dict, tail: int = 30) -> str:
    """Render catalog lineage (``/lineagez`` body, a dumped lineage
    JSON, or a bundle's ``lineage.json``): the freshness summary the
    staleness SLO verdicts on, then one row per provenance record —
    version, source, WAL watermark, train step, retrain id, age."""
    if "lineage" in doc and isinstance(doc["lineage"], dict):
        doc = doc["lineage"]  # a bundle lineage.json wraps the snapshot
    records = doc.get("records", [])
    fresh = doc.get("freshness", {}) or {}
    now = doc.get("time", time.time())
    out = [
        "# catalog lineage "
        f"({doc.get('swaps', '-')} swaps, {len(records)} records"
        + (f", {doc['evicted']} evicted" if doc.get("evicted") else "")
        + ")"
        + (f"; note: {doc['note']}" if doc.get("note") else ""),
        f"servable watermark: {_fmt(fresh.get('servable_watermark'))} "
        f"(swap age {_fmt(fresh.get('servable_swap_age_s'))}s); "
        f"latest ingest offset: "
        f"{_fmt(fresh.get('latest_ingest_offset'))}; "
        + ("INGEST AHEAD — oldest unservable record waited "
           f"{_fmt(fresh.get('unservable_age_s'))}s"
           if fresh.get("ingest_ahead") else "servable covers ingest"),
        "",
    ]
    if not records:
        out.append("(no provenance records)")
        return "\n".join(out)
    rows = [(str(r.get("catalog_version")), str(r.get("source") or "-"),
             _fmt(r.get("wal_offset_watermark")),
             _fmt(r.get("train_step")), _fmt(r.get("retrain_id")),
             _fmt(round(now - r["wall_time"], 1))
             if r.get("wall_time") else "-")
            for r in records[-tail:]]
    out.extend(format_table(("version", "source", "wal_watermark",
                             "step", "retrain", "age_s"), rows))
    return "\n".join(out)


def render_critical_path(doc: dict, tail: int = 20) -> str:
    """Render the ingest→servable critical path (a ``/criticalpathz``
    body or dumped analyzer snapshot): the per-stage attribution
    summary, then the newest completed samples — one row per sampled
    record with its stage decomposition and total."""
    stages = doc.get("stages", {})
    samples = doc.get("samples", [])
    out = [
        "# ingest→servable critical path "
        f"({doc.get('samples_total', '-')} samples)"
        + (f"; note: {doc['note']}" if doc.get("note") else ""),
        "",
    ]
    stage_rows = [(name, str(st.get("count", 0)), _fmt(st.get("mean_s")),
                   _fmt(st.get("last_s")), _fmt(st.get("max_s")))
                  for name, st in stages.items()]
    if stage_rows:
        out.extend(format_table(("stage", "n", "mean_s", "last_s",
                                 "max_s"), stage_rows))
        out.append("")
    if not samples:
        out.append("(no completed samples — arm obs.enable_disttrace() "
                   "before building the log/driver/engine)")
        return "\n".join(out)
    rows = [(str(s.get("catalog_version")), str(s.get("partition")),
             str(s.get("offset")), _fmt(s.get("queue_wait_s")),
             _fmt(s.get("train_apply_s")), _fmt(s.get("swap_lag_s")),
             _fmt(s.get("flush_wait_s")), _fmt(s.get("total_s")))
            for s in samples[-tail:]]
    out.extend(format_table(("version", "part", "offset", "queue_s",
                             "train_s", "swap_s", "flush_s", "total_s"),
                            rows))
    return "\n".join(out)


def render_contention(doc: dict, tail: int = 20) -> str:
    """Render a ``/contentionz`` body (or dumped snapshot / bundle
    ``contention.json`` / fleet pod aggregate): the Amdahl window
    summary, the contended-lock table (wait/hold/acquisition columns),
    and — per-process docs — one row per consumer partition with its
    busy/blocked split and ``streams_*`` joins."""
    window = doc.get("window") or {}
    head = ["# concurrency & saturation"]
    if doc.get("note"):
        head[0] += f" — note: {doc['note']}"
    summary = (f"consumers: {_fmt(doc.get('consumers'))}; "
               f"window: {_fmt(window.get('wall_s'))}s wall, "
               f"{_fmt(doc.get('capacity_s'))}s capacity; "
               f"busy {_fmt(doc.get('busy_s'))}s / blocked "
               f"{_fmt(doc.get('blocked_s'))}s")
    head.append(summary)
    head.append(
        f"efficiency: {_fmt(doc.get('efficiency'))}; serial fraction "
        f"(Karp–Flatt): {_fmt(doc.get('serial_fraction'))}; projected "
        f"speedup at 2N: {_fmt(doc.get('projected_speedup_at_2n'))}; "
        f"Amdahl limit: {_fmt(doc.get('amdahl_limit'))}"
        + (f" (cpu: {doc['cpu_source']})" if doc.get("cpu_source")
           else ""))
    head.append(f"lock wait total: "
                f"{_fmt(doc.get('lock_wait_s_total'))}s")
    out = head + [""]
    locks = doc.get("locks", [])
    if locks:
        rows = [(r["lock"], str(r.get("kind") or "-"),
                 _fmt(r.get("acquisitions")), _fmt(r.get("contended")),
                 _fmt(r.get("cv_waits")), _fmt(r.get("wait_s")),
                 _fmt(r.get("hold_s")),
                 _fmt(r.get("wait_frac_of_capacity")))
                for r in locks[:tail]]
        out.extend(format_table(("lock", "kind", "acq", "contended",
                                 "cv_waits", "wait_s", "hold_s",
                                 "wait/cap"), rows))
        out.append("")
    else:
        out.append("(no lock activity in window — arm "
                   "obs.enable_contention() before building the "
                   "models/drivers/engines)")
    partitions = doc.get("partitions") or {}
    if partitions:
        rows = [(p, str(row.get("thread") or "-"),
                 _fmt(row.get("busy_s")), _fmt(row.get("blocked_s")),
                 _fmt(row.get("blocked_frac")),
                 _fmt(row.get("records_total")),
                 _fmt(row.get("lag_records")),
                 _fmt(row.get("queue_depth")))
                for p, row in sorted(partitions.items())]
        out.extend(format_table(("part", "thread", "busy_s",
                                 "blocked_s", "blocked%", "records",
                                 "lag", "queue"), rows))
        out.append("")
    targets = doc.get("targets")
    if targets:  # a fleet pod aggregate: per-host summaries ride along
        rows = [(str(t.get("host")), _fmt(t.get("consumers")),
                 _fmt(t.get("wall_s")), _fmt(t.get("efficiency")),
                 _fmt(t.get("serial_fraction")),
                 _fmt(t.get("lock_wait_s_total")),
                 str(t.get("note") or "-"))
                for t in targets]
        out.extend(format_table(("host", "consumers", "wall_s", "eff",
                                 "serial", "lock_wait_s", "note"), rows))
        out.append("")
    return "\n".join(out).rstrip()


def render_transfers(doc: dict, tail: int = 12) -> str:
    """Render a ``/transferz`` body (or dumped snapshot / bundle
    ``transfers.json`` / fleet pod aggregate): the per-site transfer
    ledger (bytes/counts/wait per direction + derived effective GB/s),
    the implicit-transfer attribution, and the retrace ring with its
    signature diffs."""
    head = ["# device↔host transfers & retraces"]
    if doc.get("note"):
        head[0] += f" — note: {doc['note']}"
    if doc.get("guard_mode"):
        head.append(f"guard mode: {doc['guard_mode']}")
    steady = doc.get("steady") or {}
    if steady:
        head.append(
            f"steady state: "
            f"{'marked' if steady.get('marked') else 'warmup (unmarked)'}"
            f"; retraces {_fmt(steady.get('retraces'))}, implicit "
            f"transfers {_fmt(steady.get('implicit_transfers'))}")
    out = head + [""]
    sites = doc.get("sites") or {}
    if sites:
        rows = [(name,
                 _fmt(s.get("h2d_bytes")), _fmt(s.get("h2d_count")),
                 _fmt(s.get("d2h_bytes")), _fmt(s.get("d2h_count")),
                 _fmt(s.get("wait_s")), _fmt(s.get("effective_gbs")),
                 _fmt(s.get("hosts")) if "hosts" in s else "-")
                for name, s in sorted(
                    sites.items(),
                    key=lambda kv: -((kv[1].get("h2d_bytes") or 0)
                                     + (kv[1].get("d2h_bytes") or 0)))]
        out.extend(format_table(("site", "h2d_B", "h2d_n", "d2h_B",
                                 "d2h_n", "wait_s", "GB/s", "hosts"),
                                rows))
        out.append("")
    else:
        out.append("(no transfers recorded — arm "
                   "obs.enable_transfers() before building the "
                   "stores/drivers/engines)")
        out.append("")
    imp = doc.get("implicit_by_site") or {}
    out.append(f"implicit transfers: "
               f"{_fmt(doc.get('implicit_transfers_total'))}"
               + (" — " + ", ".join(f"{k}={v}"
                                    for k, v in sorted(imp.items()))
                  if imp else ""))
    retr = doc.get("retraces") or {}
    by_fn = retr.get("by_fn") or {}
    out.append(f"retraces: {_fmt(retr.get('total', doc.get('retrace_total')))}"
               + (" — " + ", ".join(f"{k}={v}"
                                    for k, v in sorted(by_fn.items()))
                  if by_fn else ""))
    ring = retr.get("ring") or []
    if ring:
        out.append("")
        rows = [(time.strftime("%H:%M:%S", time.localtime(r["time"])),
                 r["fn"], str(r["traces"]), str(r["new"]),
                 "; ".join(r.get("diff", []))[:80])
                for r in ring[-tail:]]
        out.extend(format_table(("time", "fn", "traces", "new",
                                 "signature diff"), rows))
    targets = doc.get("targets")
    if targets:  # a fleet pod aggregate: per-host summaries ride along
        out.append("")
        rows = [(str(t.get("host")), str(t.get("guard_mode") or "-"),
                 _fmt(t.get("implicit_transfers_total")),
                 _fmt(t.get("retrace_total")),
                 str(t.get("note") or "-"))
                for t in targets]
        out.extend(format_table(("host", "guard", "implicit", "retraces",
                                 "note"), rows))
    return "\n".join(out).rstrip()


def render_budget(doc: dict, tail: int = 12) -> str:
    """Render a ``/budgetz`` body (or dumped snapshot / bundle
    ``budget.json`` / fleet pod aggregate): service-level multi-window
    burn rates, the per-catalog-version cohort attribution table, and
    the canary verdict tail with any un-acted-on ROLLBACKs."""
    head = ["# rollout error budget & canary verdicts"]
    if doc.get("note"):
        head[0] += f" — note: {doc['note']}"
    if doc.get("objective") is not None:
        slo_bits = [f"objective {_fmt(doc['objective'])}"]
        if doc.get("target_s") is not None:
            slo_bits.insert(0, f"target {_fmt(doc['target_s'] * 1e3)} ms")
        head.append("slo: " + ", ".join(slo_bits))
    burns = doc.get("burn_rates") or {}
    if burns:
        head.append("burn rates: " + ", ".join(
            f"{w}={_fmt(b)}" for w, b in sorted(burns.items())))
    out = head + [""]

    cohorts = doc.get("cohorts")
    # A local snapshot keys cohorts by version string; a fleet pod
    # aggregate ships a pre-merged, version-sorted row list.
    if isinstance(cohorts, dict):
        rows_in = [dict(row, version=v) for v, row in sorted(
            cohorts.items(), key=lambda kv: int(kv[0]))]
    else:
        rows_in = list(cohorts or [])
    if rows_in:
        rows = [(str(r.get("version")), _fmt(r.get("served")),
                 _fmt(r.get("shed")), _fmt(r.get("shed_frac")),
                 _fmt(r.get("attainment")),
                 _fmt(r.get("burn_rate_fast",
                            r.get("burn_rate_fast_max"))),
                 _fmt(r.get("p99_ms", r.get("p99_ms_max"))),
                 _fmt(r.get("error_budget_remaining",
                            r.get("error_budget_remaining_min"))),
                 _fmt(r.get("hosts")) if "hosts" in r else "-")
                for r in rows_in]
        out.extend(format_table(("version", "served", "shed", "shed%",
                                 "attain", "burn_fast", "p99_ms",
                                 "budget", "hosts"), rows))
        out.append("")
    else:
        out.append("(no cohorts recorded — arm obs.enable_budget() "
                   "before constructing the serving engines)")
        out.append("")

    verdicts = doc.get("verdicts") or {}
    pending = (verdicts.get("pending_rollbacks")
               or doc.get("pending_rollbacks") or {})
    if pending:
        for version, rec in sorted(pending.items()):
            if isinstance(rec, list):  # fleet form: one entry per host
                for entry in rec:
                    out.append(f"PENDING ROLLBACK v{version} "
                               f"[{entry.get('host')}]: "
                               f"{entry.get('reason')}")
            else:
                out.append(f"PENDING ROLLBACK v{version}: "
                           f"{rec.get('reason')}")
        out.append("")
    history = verdicts.get("history") or []
    if history:
        rows = [(time.strftime("%H:%M:%S", time.localtime(h["time"])),
                 str(h.get("canary_version")),
                 str(h.get("incumbent_version")),
                 str(h.get("verdict")), str(h.get("reason"))[:70])
                for h in history[-tail:]]
        out.extend(format_table(("time", "canary", "incumbent",
                                 "verdict", "reason"), rows))
    targets = doc.get("targets")
    if targets:  # a fleet pod aggregate: per-host summaries ride along
        out.append("")
        rows = [(str(t.get("host")), _fmt(t.get("evaluations")),
                 ",".join(t.get("pending_rollbacks") or []) or "-",
                 str(t.get("note") or "-"))
                for t in targets]
        out.extend(format_table(("host", "evals", "pending", "note"),
                                rows))
    return "\n".join(out).rstrip()


def render_requests(doc: dict, tail: int = 12) -> str:
    """Render a ``/slowz`` body (or dumped snapshot / bundle
    ``requests.json`` / fleet pod aggregate): window stage
    decomposition with the dominant stage, then the exemplar table
    worst-first — each row naming where that request's time went."""
    head = ["# per-request stage decomposition & tail exemplars"]
    if doc.get("note"):
        head[0] += f" — note: {doc['note']}"
    if doc.get("target_s") is not None:
        head.append(f"slo: target {_fmt(doc['target_s'] * 1e3)} ms, "
                    f"objective {_fmt(doc.get('objective'))}")
    bits = []
    for key in ("count", "violations", "shed", "window_fill"):
        if doc.get(key) is not None:
            bits.append(f"{key}={_fmt(doc[key])}")
    if doc.get("burn_rate") is not None:
        bits.append(f"burn_rate={_fmt(doc['burn_rate'])}")
    if doc.get("p99_ms") is not None:
        bits.append(f"p99={_fmt(doc['p99_ms'])} ms")
    if bits:
        head.append(", ".join(bits))
    out = head + [""]

    frac = doc.get("stage_frac") or {}
    totals = doc.get("stage_totals_s") or {}
    if frac:
        dominant = doc.get("dominant_stage")
        rows = [(s + (" *" if s == dominant else ""),
                 _fmt(totals.get(s)), _fmt(f))
                for s, f in sorted(frac.items(),
                                   key=lambda kv: -kv[1])]
        out.extend(format_table(("stage", "total_s", "frac"), rows))
        out.append("")

    exemplars = doc.get("exemplars") or []
    if exemplars:
        out.append(f"exemplars worst-first (showing "
                   f"{min(tail, len(exemplars))} of {len(exemplars)}):")
        rows = [(str(e.get("host", "-")) if "host" in e else
                 str(e.get("seq", "-")),
                 str(e.get("kind")), _fmt((e.get("wall_s") or 0.0) * 1e3),
                 str(e.get("dominant_stage") or "-"),
                 str(e.get("catalog_version")),
                 str(e.get("queue_depth") if e.get("queue_depth")
                     is not None else "-"),
                 str(e.get("bucket") or "-"),
                 str(e.get("admission_level") or "-"))
                for e in exemplars[:tail]]
        out.extend(format_table(
            ("id", "kind", "wall_ms", "dominant", "ver", "qdepth",
             "bucket", "admission"), rows))
    elif not doc.get("note"):
        out.append("(no exemplars kept — no traffic noted yet)")
    targets = doc.get("targets")
    if targets:  # a fleet pod aggregate: per-host summaries ride along
        out.append("")
        rows = [(str(t.get("host")), _fmt(t.get("count")),
                 _fmt(t.get("violations")), _fmt(t.get("shed")),
                 _fmt(t.get("p99_ms")),
                 str(t.get("dominant_stage") or "-"),
                 str(t.get("note") or "-"))
                for t in targets]
        out.extend(format_table(("host", "count", "viol", "shed",
                                 "p99_ms", "dominant", "note"), rows))
    return "\n".join(out).rstrip()


QUALITY_PREFIXES = ("eval_", "dataq_", "lineage_")


def render_quality(doc: dict, name_filter: str | None = None) -> str:
    """Render the model-quality plane from a ``/seriesz`` body (or a
    dumped recorder snapshot / bundle ``series.json``): the lead-up of
    every ``eval_*`` / ``dataq_*`` / ``lineage_*`` series — or, given a
    bundle ``lineage.json`` (``quality``/``data_quality`` metric
    lists), the latest frozen instrument values."""
    if "quality" in doc and "lineage" in doc:  # a bundle lineage.json
        rows = []
        for m in doc.get("quality", []) + doc.get("data_quality", []):
            val = m.get("value", m.get("count"))
            rows.append((m["name"], _label_str(m.get("labels", {})),
                         _fmt(val), m.get("type", "-")))
        if not rows:
            return "(no quality/data-quality instruments frozen)"
        return "\n".join(["# model-quality snapshot (bundle)", ""]
                         + format_table(("metric", "labels", "value",
                                         "type"), rows))
    series = doc.get("series", {})
    keys = sorted(k for k in series
                  if k.startswith(QUALITY_PREFIXES)
                  and (name_filter is None or name_filter in k))
    out = [f"# model-quality series ({len(keys)} of {len(series)})", ""]
    if not keys:
        out.append("(no eval_/dataq_/lineage_ series recorded — attach "
                   "an OnlineEvaluator/DataQualityInspector and a "
                   "flight recorder)")
        return "\n".join(out)
    rows = []
    for key in keys:
        vals = [v for _, v in series[key]["points"]] or [None]
        numeric = [v for v in vals if isinstance(v, (int, float))]
        rows.append((key, str(len(series[key]["points"])),
                     _fmt(vals[0]),
                     _fmt(min(numeric) if numeric else None),
                     _fmt(max(numeric) if numeric else None),
                     _fmt(vals[-1])))
    out.extend(format_table(("series", "n", "first", "min", "max",
                             "last"), rows))
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", nargs="?", default=None,
                    help="snapshot JSON / metrics JSONL file, or "
                         "a live /varz URL")
    # (--bundle/--roofline/--lineage/--quality below are the artifact
    # renderers; path is only required for the snapshot/watch modes)
    ap.add_argument("--line", type=int, default=None,
                    help="0-based JSONL line (default: last)")
    ap.add_argument("--name", default=None,
                    help="only metrics whose name contains this")
    ap.add_argument("--watch", type=float, default=None, metavar="N",
                    help="poll every N seconds and render deltas/rates")
    ap.add_argument("--count", type=int, default=None,
                    help="number of --watch polls (default: forever)")
    ap.add_argument("--bundle", default=None, metavar="DIR",
                    help="validate + render a postmortem bundle directory")
    ap.add_argument("--roofline", default=None, metavar="SRC",
                    help="render a per-kernel roofline table from a "
                         "/rooflinez URL or a dumped roofline JSON file")
    ap.add_argument("--lineage", default=None, metavar="SRC",
                    help="render catalog lineage from a /lineagez URL, "
                         "a dumped lineage JSON, or a bundle's "
                         "lineage.json")
    ap.add_argument("--quality", default=None, metavar="SRC",
                    help="render the eval_*/dataq_*/lineage_* series "
                         "from a /seriesz URL or dumped series JSON "
                         "(or a bundle lineage.json's frozen snapshot)")
    ap.add_argument("--critical-path", default=None, metavar="SRC",
                    dest="critical_path",
                    help="render the ingest→servable critical-path "
                         "stage table from a /criticalpathz URL or a "
                         "dumped analyzer snapshot JSON")
    ap.add_argument("--contention", default=None, metavar="SRC",
                    help="render the concurrency/saturation table "
                         "(Amdahl summary + contended locks + "
                         "per-partition blocked shares) from a "
                         "/contentionz URL, a dumped snapshot JSON, a "
                         "bundle contention.json, or a fleet pod "
                         "aggregate")
    ap.add_argument("--transfers", default=None, metavar="SRC",
                    help="render the device↔host transfer ledger "
                         "(per-site bytes/wait/GB/s + implicit-transfer "
                         "attribution + retrace ring) from a /transferz "
                         "URL, a dumped snapshot JSON, a bundle "
                         "transfers.json, or a fleet pod aggregate")
    ap.add_argument("--budget", default=None, metavar="SRC",
                    help="render the rollout error-budget plane "
                         "(multi-window burn rates + per-catalog-version "
                         "cohort attribution + canary verdict tail) from "
                         "a /budgetz URL, a dumped snapshot JSON, a "
                         "bundle budget.json, or a fleet pod aggregate")
    ap.add_argument("--requests", default=None, metavar="SRC",
                    help="render the per-request plane (window stage "
                         "decomposition + dominant stage + tail "
                         "exemplars worst-first) from a /slowz URL, a "
                         "dumped snapshot JSON, a bundle requests.json, "
                         "or a fleet pod aggregate")
    args = ap.parse_args(argv)
    if args.bundle is not None:
        print(render_bundle(args.bundle, args.name))
        return 0
    if args.roofline is not None:
        print(render_roofline(fetch_snapshot(args.roofline), args.name))
        return 0
    if args.lineage is not None:
        print(render_lineage(fetch_snapshot(args.lineage)))
        return 0
    if args.quality is not None:
        print(render_quality(fetch_snapshot(args.quality), args.name))
        return 0
    if args.critical_path is not None:
        print(render_critical_path(fetch_snapshot(args.critical_path)))
        return 0
    if args.contention is not None:
        print(render_contention(fetch_snapshot(args.contention)))
        return 0
    if args.transfers is not None:
        print(render_transfers(fetch_snapshot(args.transfers)))
        return 0
    if args.budget is not None:
        print(render_budget(fetch_snapshot(args.budget)))
        return 0
    if args.requests is not None:
        print(render_requests(fetch_snapshot(args.requests)))
        return 0
    if args.path is None:
        ap.error("path is required unless --bundle is given")
    if args.watch is not None:
        try:
            return watch(args.path, args.watch, args.count, args.name)
        except KeyboardInterrupt:
            return 0
    snap = fetch_snapshot(args.path, args.line)
    print(render_snapshot(snap, args.name))
    return 0


if __name__ == "__main__":
    sys.exit(main())
