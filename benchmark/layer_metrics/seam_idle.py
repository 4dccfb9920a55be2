"""What the six ``flush_*_idle_ms`` readers share. Each is the milliseconds
per flush in which chip 0 ran nothing while the host was inside one of the
program's seams (``obs.trace.SEAMS``, profiler annotations the program
opens itself): the sum of ``span_host_s[seam]`` (span wall − chip 0's busy
time inside it, ``trace_reduce.reduce_trace``) over the seam's spans, over
the number of ``serving/flush`` spans (the benchmark's own, around every
``engine.flush()`` / ``engine.serve()``), × 1000.

A reader returns None when there is no trace or a seam is not in it (a
program from before the seams): the metric is then left out of the line. A
measured 0 stays 0: these are times, not shares of a peak."""

FLUSH = "serving/flush"
REMAINDER = "flush_unspanned_idle_ms"
# metric -> the seams it adds up
SEAMS_OF = {
    "flush_form_idle_ms": ("serving/engine/form",),
    "flush_prepare_idle_ms": ("serving/engine/excl",
                              "serving/engine/gather"),
    "flush_dispatch_idle_ms": ("serving/retrieval/stage1",
                               "serving/retrieval/stage2"),
    "flush_drain_idle_ms": ("serving/pipeline/drain",),
    "flush_results_idle_ms": ("serving/engine/results",),
}


def _idle_ms_per_flush(trace, flushes, seams):
    if any(s not in trace["span_host_s"] for s in seams):
        return None
    return sum(sum(trace["span_host_s"][s]) for s in seams) / flushes * 1e3


def read(metric, ctx):
    """``metric``'s value for the run ``ctx`` collected: one of
    ``SEAMS_OF``, or ``REMAINDER`` — the mean host share of a flush (chip 0
    idle inside ``serving/flush``; its median is ``flush_host_ms_p50``)
    less the five others, what no seam covers."""
    trace = ctx.get("trace")
    flushes = trace["span_runs"].get(FLUSH, 0) if trace else 0
    if not flushes:
        return None
    if metric != REMAINDER:
        return _idle_ms_per_flush(trace, flushes, SEAMS_OF[metric])
    parts = [_idle_ms_per_flush(trace, flushes, seams)
             for seams in SEAMS_OF.values()]
    if any(p is None for p in parts):
        return None
    return sum(trace["span_host_s"][FLUSH]) / flushes * 1e3 - sum(parts)
