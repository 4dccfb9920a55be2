"""Test environment: force an 8-device virtual CPU mesh.

Multi-chip TPU hardware is not available in CI; every sharding/collective
code path is exercised on XLA's host-platform virtual devices instead
(SURVEY §4: multi-device tests via xla_force_host_platform_device_count).
``force_cpu`` must run before anything initializes a jax backend: it sets
the platform and the virtual-device flag (see utils/platform.py).
"""

import os
import sys

os.environ.setdefault("JAX_ENABLE_X64", "0")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from large_scale_recommendation_tpu.utils.platform import force_cpu  # noqa: E402

force_cpu(n_devices=8)

# OBS_OUT=<dir>: run the whole suite with the observability layer live
# and dump the session's metrics JSONL + Prometheus snapshot + Chrome
# trace there at exit — the artifact the CI workflow uploads for every
# tier-1 run. The endpoint server also runs for the whole session, and
# sessionfinish fetches /healthz + /metrics over the REAL socket (the
# .prom artifact is the served body, proving the scrape surface end to
# end); the /healthz report lands in tier1_healthz.json, which the CI
# workflow gates on (job fails if status == "critical"). The FLIGHT
# RECORDER also runs for the whole session (1 s cadence, bounded
# memory), so sessionfinish can freeze a full postmortem bundle
# (tier1_bundle/) — on a health-gate failure, the uploaded artifact
# carries the lead-up series/events/spans, not just the final verdict.
# Unset (the default, local runs): the null layer stays installed and
# instrumentation costs nothing.
_OBS_OUT = os.environ.get("OBS_OUT")
_OBS_REG = _OBS_TRACER = _OBS_SERVER = _OBS_RECORDER = None
if _OBS_OUT:
    from large_scale_recommendation_tpu import obs as _obs  # noqa: E402
    from large_scale_recommendation_tpu.obs import health as _health  # noqa: E402
    from large_scale_recommendation_tpu.obs.server import ObsServer  # noqa: E402

    _OBS_REG, _OBS_TRACER = _obs.enable()
    _OBS_RECORDER, _OBS_JOURNAL = _obs.enable_flight_recorder(
        interval_s=1.0, bundle_dir=os.path.join(_OBS_OUT, "postmortem"))
    # XLA introspection for the whole session: every compile the suite
    # pays is captured at the funnel (cost analysis + wall, attributed
    # to the enclosing compile key), the device-memory sampler feeds
    # the recorder, and sessionfinish renders the joined roofline as a
    # tier-1 artifact (tier1_roofline.json/.txt)
    _OBS_INTROSPECTOR = _obs.enable_introspection(interval_s=1.0)
    # catalog lineage for the whole session: every engine the suite
    # builds stamps its swaps, and sessionfinish freezes the journal +
    # the quality-plane series into tier1_quality.json
    _OBS_LINEAGE = _obs.enable_lineage()
    # critical-path attribution for the whole session: drivers/engines
    # the suite builds stamp their ingest→servable stages
    # (critical_path_s{stage} gauges ride the same recorder)
    _OBS_DISTTRACE = _obs.enable_disttrace()
    # concurrency plane for the whole session: every model/engine/
    # driver lock the suite constructs binds its instrumented form,
    # the thread sampler feeds contention_* gauges into the recorder,
    # and sessionfinish freezes tier1_contention.json
    # (max_threads raised: a whole tier-1 session churns through many
    # short-lived driver/server threads; the table is still bounded)
    _OBS_CONTENTION = _obs.enable_contention(interval_s=1.0,
                                             max_threads=512)
    # transfer plane for the whole session: every deliberate
    # device<->host crossing the suite drives lands in the per-site
    # ledger, and the hot jitted fns are watched for retraces. Guard
    # stays OFF: a tier-1 session legitimately runs eager paths the
    # hot-loop disallow contract does not cover
    _OBS_TRANSFERS = _obs.enable_transfers(guard="off")
    _OBS_MONITOR = _health.HealthMonitor()

    def _session_check():
        # the layer itself is the subject: a live registry and a trace
        # buffer that isn't silently dropping spans
        if not _OBS_REG.enabled:
            return _health.critical(note="registry not live")
        if _OBS_TRACER.dropped:
            return _health.degraded(dropped_spans=_OBS_TRACER.dropped)
        return _health.ok(metric_names=len(_OBS_REG.names()))

    _OBS_MONITOR.register("obs_session", _session_check)
    _OBS_SERVER = ObsServer(registry=_OBS_REG, tracer=_OBS_TRACER,
                            monitor=_OBS_MONITOR).start()


import pytest  # noqa: E402


@pytest.fixture
def null_obs():
    """The fully-disabled obs layer installed for one test, with the
    ENTIRE previous layer restored after — registry, tracer, event
    journal, AND flight recorder (an OBS_OUT session runs one
    suite-wide; its sampler is restarted if it was live). ONE copy,
    shared by every obs test file: the restore invariant is non-trivial
    and must not drift between copies."""
    from large_scale_recommendation_tpu import obs
    from large_scale_recommendation_tpu.obs.contention import (
        get_contention,
        set_contention,
    )
    from large_scale_recommendation_tpu.obs.disttrace import (
        get_disttrace,
        set_disttrace,
    )
    from large_scale_recommendation_tpu.obs.events import (
        get_events,
        set_events,
    )
    from large_scale_recommendation_tpu.obs.introspect import (
        get_introspector,
        set_introspector,
    )
    from large_scale_recommendation_tpu.obs.lineage import (
        get_lineage,
        set_lineage,
    )
    from large_scale_recommendation_tpu.obs.recorder import (
        get_recorder,
        set_recorder,
    )
    from large_scale_recommendation_tpu.obs.registry import (
        get_registry,
        set_registry,
    )
    from large_scale_recommendation_tpu.obs.store import (
        get_store,
        set_store,
    )
    from large_scale_recommendation_tpu.obs.trace import (
        get_tracer,
        set_tracer,
    )
    from large_scale_recommendation_tpu.obs.budget import (
        get_budget,
        set_budget,
    )
    from large_scale_recommendation_tpu.obs.requests import (
        get_requests,
        set_requests,
    )
    from large_scale_recommendation_tpu.obs.transfers import (
        get_transfers,
        set_transfers,
    )

    prev_r, prev_t = get_registry(), get_tracer()
    prev_j, prev_rec = get_events(), get_recorder()
    prev_ins, prev_lin = get_introspector(), get_lineage()
    prev_dt = get_disttrace()
    prev_ct = get_contention()
    prev_tf = get_transfers()
    prev_store = get_store()
    prev_budget = get_budget()
    prev_requests = get_requests()
    was_running = prev_rec is not None and prev_rec.running
    ins_was_running = prev_ins is not None and prev_ins.running
    ct_was_running = prev_ct is not None and prev_ct.running
    obs.disable()  # closes the introspector too: compile funnel unpatched
    yield get_registry()
    set_registry(prev_r)
    set_tracer(prev_t)
    set_events(prev_j)
    set_recorder(prev_rec)
    set_lineage(prev_lin)
    set_disttrace(prev_dt)
    set_contention(prev_ct)
    if ct_was_running:  # an OBS_OUT session runs one suite-wide
        prev_ct.start()
    set_introspector(prev_ins)
    if prev_ins is not None:  # an OBS_OUT session runs one suite-wide
        prev_ins.install()
        if ins_was_running:
            prev_ins.start()
    if was_running:
        prev_rec.start()
    set_transfers(prev_tf)
    set_store(prev_store)  # a test-built TieredFactorStore must not leak
    set_budget(prev_budget)
    set_requests(prev_requests)


def pytest_sessionfinish(session, exitstatus):
    if not _OBS_OUT:
        return
    import json

    from large_scale_recommendation_tpu.obs.server import http_get

    os.makedirs(_OBS_OUT, exist_ok=True)
    # graftlint finding counts stamped into the SAME registry the
    # metrics artifacts freeze below (ISSUE 15): the trajectory of
    # suppressed/baselined static-analysis debt ships with every tier-1
    # round — a rising lint_baselined_total is debt accruing even while
    # the --strict CI gate stays green
    try:
        from tools.graftlint import run_lint as _graftlint

        _lint = _graftlint()  # pure-AST, sub-second, no jax touched
        _OBS_REG.gauge("lint_findings_total").set(len(_lint.findings))
        for _rule, _n in _lint.per_rule().items():
            _OBS_REG.gauge("lint_findings", rule=_rule).set(_n)
        _OBS_REG.gauge("lint_baselined_total").set(len(_lint.baselined))
        _OBS_REG.gauge("lint_suppressed_total").set(len(_lint.suppressed))
        with open(os.path.join(_OBS_OUT, "tier1_lint.json"), "w") as f:
            json.dump(_lint.to_dict(), f, indent=2)
    except Exception as e:  # artifact-only: never fail the session
        with open(os.path.join(_OBS_OUT, "tier1_lint_error.txt"),
                  "w") as f:
            f.write(repr(e))
    _OBS_REG.append_jsonl(os.path.join(_OBS_OUT, "tier1_metrics.jsonl"))
    _OBS_TRACER.to_chrome_trace(os.path.join(_OBS_OUT, "tier1_trace.json"))
    # the session's per-kernel roofline: every compile key the suite
    # exercised, XLA cost analysis joined with measured execute walls
    try:
        from scripts.obs_report import render_roofline

        _roofline = _OBS_INTROSPECTOR.roofline()
        with open(os.path.join(_OBS_OUT, "tier1_roofline.json"), "w") as f:
            json.dump(_roofline, f, indent=2)
        with open(os.path.join(_OBS_OUT, "tier1_roofline.txt"), "w") as f:
            f.write(render_roofline(_roofline) + "\n")
    except Exception as e:  # artifact-only: never fail the session on it
        with open(os.path.join(_OBS_OUT, "tier1_roofline_error.txt"),
                  "w") as f:
            f.write(repr(e))
    # the model-quality plane's artifact (ISSUE 10): the session's
    # lineage journal + every eval_*/dataq_*/lineage_* series the
    # suite's flight recorder captured, next to the roofline/bundle
    try:
        from large_scale_recommendation_tpu.obs.lineage import get_lineage

        _lin = get_lineage()  # tests swap journals; freeze the current
        _series = _OBS_RECORDER.snapshot()
        _quality_doc = {
            "lineage": (_lin.snapshot() if _lin is not None
                        else {"note": "no lineage journal",
                              "records": []}),
            "series": {k: v for k, v in _series["series"].items()
                       if k.startswith(("eval_", "dataq_", "lineage_"))},
        }
        with open(os.path.join(_OBS_OUT, "tier1_quality.json"), "w") as f:
            json.dump(_quality_doc, f, indent=2)
    except Exception as e:
        with open(os.path.join(_OBS_OUT, "tier1_quality_error.txt"),
                  "w") as f:
            f.write(repr(e))
    # the concurrency plane's artifact (ISSUE 14): the suite-long
    # saturation window — lock table + thread utilization — next to
    # the roofline/quality artifacts
    try:
        from large_scale_recommendation_tpu.obs.contention import (
            SaturationAnalyzer,
        )

        with open(os.path.join(_OBS_OUT, "tier1_contention.json"),
                  "w") as f:
            json.dump(SaturationAnalyzer(_OBS_CONTENTION).snapshot(), f,
                      indent=2, default=repr)
    except Exception as e:
        with open(os.path.join(_OBS_OUT, "tier1_contention_error.txt"),
                  "w") as f:
            f.write(repr(e))
    # the transfer plane's artifact (ISSUE 18): the suite-long per-site
    # device<->host ledger plus retrace attribution — which sites moved
    # how many bytes at what effective rate across the whole tier-1 run
    try:
        from large_scale_recommendation_tpu.obs.transfers import (
            get_transfers as _get_tf,
        )

        _tf = _get_tf()  # tests swap ledgers; freeze the current one
        with open(os.path.join(_OBS_OUT, "tier1_transfers.json"),
                  "w") as f:
            json.dump(_tf.snapshot() if _tf is not None
                      else {"note": "no transfer ledger", "sites": {}},
                      f, indent=2)
    except Exception as e:
        with open(os.path.join(_OBS_OUT, "tier1_transfers_error.txt"),
                  "w") as f:
            f.write(repr(e))
    # scrape the session's endpoint server for real: the artifacts below
    # came over the socket, not from in-process calls (http_get turns a
    # dead-server connection failure into a synthetic 599, so both
    # artifacts always exist and the CI gate shows WHAT broke)
    code, prom = http_get(_OBS_SERVER.url + "/metrics")
    if code != 200:  # fall back so the artifact always exists
        prom = _OBS_REG.to_prometheus()
    with open(os.path.join(_OBS_OUT, "tier1_metrics.prom"), "w") as f:
        f.write(prom)
    code, body = http_get(_OBS_SERVER.url + "/healthz")
    try:
        report = json.loads(body)
    except ValueError:
        report = {"status": "critical",
                  "error": "unparseable /healthz body",
                  "body": body[:500]}
    report["http_status"] = code
    with open(os.path.join(_OBS_OUT, "tier1_healthz.json"), "w") as f:
        json.dump(report, f, indent=2)
    _OBS_SERVER.stop()
    # freeze the session's flight-recorder state as a bundle: on a
    # health-gate failure this is the postmortem CI ships — series
    # lead-up, event tail, span tail, final health/registry snapshots
    _OBS_RECORDER.stop()
    try:
        _OBS_RECORDER.sample()  # one last point so the bundle is current
        _OBS_RECORDER.dump(
            trigger="session_end", detail={"exitstatus": int(exitstatus),
                                           "healthz": report.get("status")},
            directory=os.path.join(_OBS_OUT, "tier1_bundle"),
            health_report=report)
    except Exception as e:  # the suite's verdict must not die on its
        with open(os.path.join(_OBS_OUT,  # own black box
                               "tier1_bundle_error.txt"), "w") as f:
            f.write(repr(e))
