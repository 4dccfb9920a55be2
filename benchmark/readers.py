"""The generic readers behind the per-layer metrics. A metric is one file
``benchmark/layer_metrics/<name>.json`` whose ``reader`` names one of these
kinds and its parameters, so a metric that a generic reader can compute is
data alone; ``{"kind": "python", "file": "<name>.py"}`` names a reader of
its own beside the JSON file (``read(ctx) -> float | None``).

``ctx`` is what a run collected: ``trace`` (``trace_reduce.reduce_trace``'s
output, None without ``--trace 1``), ``series`` (named lists of numbers from
the benchmark's spans), ``counters``, ``sizes`` (the numbers the counts
need), ``peaks``, ``window_s`` (host wall of the window), ``chips`` and, from
the ``fit`` kind, ``sweep_flops`` (the FLOP one sweep of the cell's solver
needs, by the solver file's own count).

A reader that finds nothing to read returns None. It never returns 0 for a
share of a roofline or of a peak.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np

from benchmark import counts

HERE = os.path.dirname(os.path.abspath(__file__))


def _stat(values, stat: str):
    values = np.asarray(values, float)
    values = values[~np.isnan(values)]
    if values.size == 0:
        return None
    if stat == "sum":
        return float(values.sum())
    if stat == "mean":
        return float(values.mean())
    if stat.startswith("p"):
        return float(np.percentile(values, float(stat[1:])))
    raise ValueError(f"unknown statistic {stat!r}")


def _program_seconds(trace: dict, programs):
    table = trace["program_s"]
    found = [table[p] for p in programs if p in table]
    return sum(found) if found else None


def _program_runs(trace: dict, programs):
    return sum(trace["program_runs"].get(p, 0) for p in programs)


def counter(spec, ctx):
    """A count the benchmark made (``sweeps_to_target``)."""
    return ctx["counters"].get(spec["counter"])


def series_stat(spec, ctx):
    """A statistic of a named series of the benchmark's spans."""
    values = ctx["series"].get(spec["series"])
    if values is None or len(values) == 0:
        return None
    out = _stat(values, spec["stat"])
    return None if out is None else out * spec.get("scale", 1.0)


def program_time(spec, ctx):
    """Device seconds of the named programs (by jit name, from the device
    trace), per run of the first program that ran, per host span of a
    name, or in total."""
    trace = ctx.get("trace")
    if not trace:
        return None
    total = _program_seconds(trace, spec["programs"])
    if total is None:
        return None
    per = spec.get("per", "total")
    if per == "run":
        n = max(trace["program_runs"].get(p, 0) for p in spec["programs"])
    elif per == "span":
        n = trace["span_runs"].get(spec["span"], 0)
    else:
        n = 1
    if n == 0:
        return None
    return total / n * spec.get("scale", 1.0)


def program_start_after_span(spec, ctx):
    """Seconds from the start of a host span to the first start of a
    program on the device (``blocking_s``: the call of ``fit_device`` to
    the first sweep's start)."""
    trace = ctx.get("trace")
    if not trace:
        return None
    starts = [trace["program_first_start_s"][p] for p in spec["programs"]
              if p in trace["program_first_start_s"]]
    span = trace["span_first_start_s"].get(spec["span"])
    if not starts or span is None:
        return None
    return (min(starts) - span) * spec.get("scale", 1.0)


def span_minus_device(spec, ctx):
    """A statistic over the spans of a name of (span wall − device busy
    time inside it): the host's own share of a flush."""
    trace = ctx.get("trace")
    if not trace or spec["span"] not in trace["span_host_s"]:
        return None
    out = _stat(trace["span_host_s"][spec["span"]], spec["stat"])
    return None if out is None else out * spec.get("scale", 1.0)


def roofline(spec, ctx):
    """The least time the chip could take for the work the named programs
    did, over their device time, in percent. ``floor`` names how the least
    time is counted (``benchmark/counts.py``)."""
    trace = ctx.get("trace")
    if not trace or ctx["peaks"] is None:
        return None
    chips = ctx["chips"]
    device_s = _program_seconds(trace, spec["programs"])
    if not device_s:
        return None
    sizes, peaks = ctx["sizes"], ctx["peaks"]
    if spec["floor"] == "sweep_min_bytes":
        runs = _program_runs(trace, spec["programs"]) / chips
        sweeps = runs * spec.get("sweeps_per_run", 1)
        floor = sweeps * counts.sweep_min_bytes(
            sizes["nnz_train"], sizes["num_users"], sizes["num_items"],
            sizes["rank"], sizes["num_blocks"]) / peaks["hbm_bytes_per_s"]
        floor /= chips      # the bytes are spread over the chips
        device_s /= chips   # device time is summed over them
    elif spec["floor"] == "stage1_per_bucket":
        buckets = ctx["series"].get("bucket_rows")
        if not buckets:
            return None
        floor = sum(counts.stage1_floor_s(int(b), sizes["num_items"],
                                          sizes["rank"], peaks)
                    for b in buckets)
    else:
        raise ValueError(f"unknown floor {spec['floor']!r}")
    return 100.0 * floor / device_s


def window_share(spec, ctx):
    """Work done in the window over what the chips' peak would do in the
    window's wall, in percent (``*_mfu``)."""
    sizes, peaks = ctx["sizes"], ctx["peaks"]
    if peaks is None:  # a CPU rehearsal: never a share of a chip's peak
        return None
    if spec["count"] == "sweep_flops":
        done = ctx["counters"].get("sweeps_done")
        if not done:
            return None
        work = done * ctx["sweep_flops"]
    elif spec["count"] == "serve_ops":
        users = ctx["counters"].get("users_answered")
        if not users:
            return None
        work = counts.serve_ops(int(users), sizes["num_items"],
                                sizes["rank"])
    else:
        raise ValueError(f"unknown count {spec['count']!r}")
    return 100.0 * work / (ctx["window_s"] * ctx["chips"]
                           * peaks[spec["peak"]])


KINDS = {f.__name__: f for f in (
    counter, series_stat, program_time, program_start_after_span,
    span_minus_device, roofline, window_share)}


def read(metric: dict, ctx: dict,
         directory: str = os.path.join(HERE, "layer_metrics")):
    """``directory``: where the metric's file is, and so its own reader."""
    spec = metric["reader"]
    if spec["kind"] == "python":
        path = os.path.join(directory, spec["file"])
        module_spec = importlib.util.spec_from_file_location(
            "layer_metric_" + metric["name"].replace(".", "_"), path)
        module = importlib.util.module_from_spec(module_spec)
        module_spec.loader.exec_module(module)
        return module.read(ctx)
    return KINDS[spec["kind"]](spec, ctx)
