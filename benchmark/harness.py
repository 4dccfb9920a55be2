"""What every runner shares: resolving a cell to its files, the chip guard,
the measured window (compile count, profiler, peak memory), the per-layer
readers' context and the result line."""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time

from benchmark import readers, trace_reduce
from benchmark.peaks import load_peaks
from benchmark.spans import Spans

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T_IMPORT = time.perf_counter()


def seconds_since_process_start() -> float:
    """Process start to now, by the kernel's clock where it can be read."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - T_IMPORT


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: list      # manifest entries this cell reports
    per_layer: list
    root: str = ROOT      # the checkout whose files the lookups read


def resolve_cell(name: str, manifest: dict | None = None,
                 root: str = ROOT) -> Cell:
    """Cell name -> its configuration and traffic files, found by the names
    in ``BENCHMARK.json`` (or, for a cell not in the manifest yet, by
    ``<config>.<traffic>`` and the files of those names)."""
    manifest = manifest or load_manifest(root)
    entry = next((w for w in manifest["workloads"] if w["name"] == name),
                 None)
    siblings = set()
    if entry is None:
        # a cell whose files are in the tree but which is not in the
        # manifest yet: it reports what the cells of its traffic report
        config_name, _, traffic_name = name.rpartition(".")
        entry = {"name": name, "config": config_name,
                 "traffic": traffic_name, "chips": None}
        config_file = os.path.join("benchmark", "configs",
                                   config_name + ".json")
        siblings = {w["name"] for w in manifest["workloads"]
                    if w["traffic"] == traffic_name}
    else:
        config_file = next(c["file"] for c in manifest["configs"]
                           if c["name"] == entry["config"])
    config = load_json(os.path.join(root, config_file))
    traffic = load_json(os.path.join(
        root, "benchmark", "traffic", entry["traffic"] + ".json"))
    chips = entry["chips"] or int(config.get("chips", 1))

    def mine(metric):
        cells = metric.get("workloads")
        return cells is None or name in cells or bool(siblings & set(cells))

    return Cell(name=name, chips=chips, config_name=entry["config"],
                traffic_name=entry["traffic"], config=config,
                traffic=traffic,
                end_to_end=[m for m in manifest["end_to_end"] if mine(m)],
                per_layer=[m for m in manifest["per_layer"] if mine(m)],
                root=root)


def load_file(root: str, rel: str, what: str):
    """``<root>/benchmark/<rel>.py`` as a module: how a runner kind, a
    solver and a reference are found, by the name a data file gives. One
    that has no file ends the run, naming the file looked for. In this
    checkout the module is the package's own (one copy, whoever imports
    it); in another root it is loaded from the file."""
    path = os.path.join(root, "benchmark", *rel.split("/")) + ".py"
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: {what} has no file {path}")
    dotted = "benchmark." + rel.replace("/", ".")
    if os.path.realpath(root) == os.path.realpath(ROOT):
        return importlib.import_module(dotted)
    spec = importlib.util.spec_from_file_location(
        dotted.replace(".", "_") + "_of_another_root", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def runner_for(cell: Cell):
    """The cell's runner kind, ``runners/<kind>.py`` (its contract:
    ``benchmark/README.md``)."""
    kind = cell.traffic["runner"]
    runner = load_file(cell.root, "runners/" + kind, f"runner kind {kind!r}")
    if not callable(getattr(runner, "run", None)):
        raise SystemExit(f"benchmark: {runner.__file__} has no run()")
    return runner


def reference_for(cell: Cell, default: str):
    """The plain reference the configuration names, ``reference/<name>.py``;
    ``default`` (the runner kind's own) where it names none."""
    name = cell.config.get("reference", default)
    return load_file(cell.root, "reference/" + name, f"reference {name!r}")


def require_chips(chips: int) -> dict:
    """The device as JAX reports it; exits non-zero, printing no result,
    when there is no TPU or fewer chips than the cell asks for."""
    summary = device_summary()
    if summary["platform"] != "tpu" or summary["count"] < chips:
        print(f"benchmark: needs {chips} TPU chip(s), found "
              f"{summary['count']} x {summary['platform']} "
              f"({summary['kind']}): no timing is taken off the chip",
              file=sys.stderr)
        raise SystemExit(2)
    return summary


def start_on_chip(chips: int) -> dict:
    """The chip guard, then the persistent compile cache where the program
    keeps it: ``JAX_COMPILATION_CACHE_DIR`` when set, else
    ``<checkout>/.jax_cache``."""
    device = require_chips(chips)
    from large_scale_recommendation_tpu.utils.platform import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    return device


def device_summary() -> dict:
    """The device as JAX reports it."""
    import jax

    devices = jax.local_devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def per_device_peak_bytes() -> list:
    """Peak bytes in use per local device (None where the backend keeps no
    allocator statistics, as the CPU's)."""
    import jax

    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.local_devices()]


def memory_peak_bytes() -> int | None:
    """The peak on the fullest chip."""
    peaks = [p for p in per_device_peak_bytes() if p is not None]
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts programs lowered while it is armed. A lowering is what a
    compile starts with, persistent cache or not, so a warmed window counts
    0 and any new shape inside it counts."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    _installed = None

    def __init__(self):
        self.count = 0
        self.armed = False
        if CompileCounter._installed is None:
            import jax.monitoring

            CompileCounter._installed = []
            jax.monitoring.register_event_duration_secs_listener(
                CompileCounter._dispatch)
        CompileCounter._installed.append(self)

    @staticmethod
    def _dispatch(event, duration, **kw):
        if event == CompileCounter.EVENT:
            for c in CompileCounter._installed:
                if c.armed:
                    c.count += 1


class Window:
    """The measured window: arms the compile counter, runs the profiler
    when tracing, and names the window in the trace."""

    def __init__(self, trace: bool, trace_dir: str, strict: bool = True):
        self.trace = trace
        self.strict = strict  # False: a CPU rehearsal, no device plane
        self.trace_dir = trace_dir
        self.compiles = CompileCounter()
        self.spans = Spans(annotate=trace)
        self.t0 = self.t1 = self.setup_s = None
        self.reduced = None

    @contextlib.contextmanager
    def measure(self):
        import jax

        # Everything alive after warm-up is frozen out of the cyclic
        # collector for the length of the window: a full collection in a
        # JAX process walks ~1M long-lived objects and stops the one
        # thread that both offers the load and serves it for ~130 ms, and
        # the benchmark's own growing lists of results trigger several a
        # window (PERF.md, Findings, PR 24). Young objects are still
        # collected.
        gc.collect()
        gc.freeze()
        ann = None
        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=options)
            ann = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
            ann.__enter__()
        self.compiles.armed = True
        self.setup_s = seconds_since_process_start()
        self.t0 = time.perf_counter()
        try:
            yield self
        finally:
            self.t1 = time.perf_counter()
            gc.unfreeze()
            self.compiles.armed = False
            if ann is not None:
                ann.__exit__(None, None, None)
            if self.trace:
                jax.profiler.stop_trace()

    @property
    def wall(self) -> float:
        return self.t1 - self.t0

    def reduce(self) -> dict:
        """Read the trace once the window has closed; the files are deleted
        afterwards (a trace is tens of MB and nothing reads it again)."""
        if self.trace and self.reduced is None:
            events = trace_reduce.read_xplane(
                trace_reduce.find_xplane(self.trace_dir))
            if events["devices"] or self.strict:
                self.reduced = trace_reduce.reduce_trace(events)
            shutil.rmtree(self.trace_dir, ignore_errors=True)
        return self.reduced


def trace_dir_for(cell: str) -> str:
    """Inside the checkout, at a fixed path per cell."""
    return os.path.join(ROOT, ".bench_trace", cell)


def layer_metrics(cell: Cell, ctx: dict) -> dict:
    """Every per-layer metric of the cell whose reader finds something to
    read. A reader that finds nothing returns None and the metric is left
    out of the line."""
    out = {}
    directory = os.path.join(cell.root, "benchmark", "layer_metrics")
    for m in cell.per_layer:
        spec = load_json(os.path.join(directory, m["name"] + ".json"))
        value = readers.read(spec, ctx, directory)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(*, correct, attempted, failed, metrics, device, compared,
                breakdown=None, notes=None) -> str:
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    if notes:
        line["notes"] = notes
    line["compared"] = compared
    return json.dumps(line)


def peaks_for(device: dict) -> dict | None:
    """The chip's peaks; None off the chip (a CPU rehearsal never reports a
    share of a chip's peak)."""
    return load_peaks(device["kind"]) if device["platform"] == "tpu" else None
