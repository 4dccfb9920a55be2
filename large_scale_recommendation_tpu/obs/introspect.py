"""XLA introspection: per-kernel cost/memory capture at the compile
boundary, device-memory telemetry, and on-demand profiler capture.

The span layer (PR 3) measures WALLS and the bench layer prices a
hand-built traffic model (``ops.sgd.dsgd_bytes_per_sweep``) — but
nothing in the live system can say what the COMPILER thinks each
executable moves and computes, so the Open-item-2 roofline
(``pct_of_hbm_peak`` < 1%) rests on trust-me arithmetic. This module
closes that gap from below, the way CuMF_SGD reasons (measured memory
behavior per kernel) and the way ALX's pod recipe requires (per-host
HBM visibility):

- ``Introspector.install()`` hooks the ONE funnel every jit compile in
  the installed jax passes through
  (``jax._src.compiler.compile_or_get_cached``, called via module
  attribute from ``pxla``). Each captured executable records its
  ``cost_analysis()`` FLOPs / bytes-accessed, its
  ``get_compiled_memory_stats()``, and the measured compile wall — and
  is attributed to the *enclosing tracer compile key*
  (``Tracer.current_compile_key()``): the first call of a keyed span
  family is the one that pays the compile, so an executable built while
  that span is open IS that family's kernel. Compiles outside any keyed
  span fall back to the XLA module name (``jit_foo``). Published
  metrics: ``compile_count{key=}`` / ``compile_wall_s{key=}`` counters,
  ``xla_flops{key=}`` / ``xla_bytes_accessed{key=}`` gauges.
- ``roofline()`` joins those records with the tracer's measured
  execute-span walls (``Tracer.key_walls()``) into a live per-kernel
  roofline table — achieved GB/s and TFLOP/s per compile key,
  ``pct_of_hbm_peak`` / ``pct_of_fp32_peak`` against the chip peaks —
  served at ``/rooflinez`` (``obs.server``), rendered by
  ``scripts/obs_report.py --roofline``, and sampled into the flight
  recorder as ``xla_pct_of_hbm_peak{key=}`` gauges. Training loops
  additionally register the HAND model's bytes/flops per sweep
  (``TrainSegmentTimer.finish`` → ``register_model_cost``), so every
  roofline row carries ``xla_vs_model_bytes`` — the cross-check that
  turns the Open-item-2 arithmetic into measured agreement
  (docs/OBSERVABILITY.md documents the expected factor).
- ``sample_device_memory()`` samples ``device.memory_stats()``
  (bytes-in-use / peak / limit per local device — ``None`` on CPU, the
  graceful-absent path) plus a ``jax.live_arrays()`` dtype breakdown
  into registry gauges; the flight recorder turns those into series,
  ``obs.anomaly.MonotonicGrowthCheck`` watches them for leak-shaped
  monotonic growth, and ``obs.recorder.write_bundle`` freezes a fresh
  sample into every postmortem (``device_memory.json``).
- ``profile_trace(log_dir)`` / ``capture_profile(dir, seconds)`` — the
  ONE ``jax.profiler`` capture layer (process-singleton lock, capture
  accounting): ``/profilez`` records an N-second trace on demand,
  watchdog-trip postmortems attach a short capture
  (``FlightRecorder(profile_on_trip_s=...)``), and the legacy
  ``utils.metrics.profile`` shim routes here instead of calling
  ``jax.profiler.trace`` on its own.

Zero-cost when unused — the same discipline as the rest of ``obs``:
the module default is ``None`` (``get_introspector()``), the compile
funnel stays UNPATCHED until ``install()``, and every producer-side
hook is one ``is not None`` test. ``obs.enable_introspection()`` is
the one-call form; ``obs.disable()`` uninstalls.
"""

from __future__ import annotations

import contextlib
import math
import os
import threading
import time
from typing import Any

from large_scale_recommendation_tpu.obs.registry import get_registry
from large_scale_recommendation_tpu.obs.trace import get_tracer

# Chip peaks for the roofline denominators — one TPU v5e chip, which
# jax reports as device_kind "TPU v5 lite". They describe THAT device:
# on any other (a CPU run, another TPU generation) every share-of-peak
# field is None, never a share of a v5e's peak (``device_peaks``).
PEAKS_DEVICE_KIND = "TPU v5 lite"
HBM_PEAK_GBS = 819.0
BF16_PEAK_TFLOPS = 197.0
FP32_PEAK_TFLOPS = 49.0


def device_peaks() -> tuple[float | None, float | None]:
    """``(HBM GB/s, fp32 TFLOP/s)`` of the local device when it is the
    one the constants describe, else ``(None, None)``."""
    import jax

    if jax.devices()[0].device_kind == PEAKS_DEVICE_KIND:
        return HBM_PEAK_GBS, FP32_PEAK_TFLOPS
    return None, None

DEFAULT_MAX_RECORDS = 1024

# process-wide profiler serialization: jax.profiler is a singleton —
# a second start_trace while one runs raises deep inside tsl. ONE lock
# for every capture path (/profilez, watchdog auto-capture, the
# utils.metrics.profile shim), so concurrent triggers get a clean
# "capture in progress" instead of a profiler backtrace.
_PROFILE_LOCK = threading.Lock()
# captures completed through profile_trace since import — the
# registry-independent count tests pin the shim routing on
CAPTURE_COUNT = 0


def render_key(key: Any) -> str:
    """Canonical string form of a tracer compile key: top-level tuple
    parts joined by ``/``, strings kept verbatim, everything else
    ``repr``'d — stable across recompiles of the same geometry, so it
    can label metrics and join tables."""
    if isinstance(key, str):
        return key
    if isinstance(key, tuple):
        return "/".join(p if isinstance(p, str) else repr(p) for p in key)
    return repr(key)


def _module_name(computation: Any) -> str:
    """The MLIR module's sym_name (``jit_foo``), defensively — an
    attribute-layout change must degrade the label, not kill a
    compile."""
    try:
        attr = computation.operation.attributes["sym_name"]
        return str(getattr(attr, "value", attr)).strip('"')
    except Exception:
        return "<unknown>"


def _cost_entries(executable: Any) -> dict:
    """``{flops, bytes_accessed}`` from a LoadedExecutable's
    ``cost_analysis()`` (a list of one properties dict on this jaxlib;
    a bare dict on others). Missing analysis (some backends) → zeros."""
    try:
        ca = executable.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
    except Exception:
        ca = {}
    return {
        "flops": float(ca.get("flops", 0.0) or 0.0),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0) or 0.0),
    }


def _memory_stats(executable: Any) -> dict | None:
    """``get_compiled_memory_stats()`` as a plain dict, or None where
    the backend doesn't implement it."""
    try:
        ms = executable.get_compiled_memory_stats()
    except Exception:
        return None
    out = {}
    for field in ("generated_code_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "alias_size_in_bytes",
                  "temp_size_in_bytes"):
        v = getattr(ms, field, None)
        if v is not None:
            out[field] = int(v)
    return out or None


class Introspector:
    """Captures every XLA compile's cost/memory analysis, keyed by the
    enclosing tracer compile key.

    ``install()`` patches the jax compile funnel (idempotent;
    ``uninstall()`` restores it — only if the current funnel is still
    ours). Capture is defensive end to end: an introspection failure
    increments ``errors`` and the compile proceeds untouched.
    ``max_records`` caps the table (distinct (key, module) pairs past
    it are counted in ``dropped``, never grown — same bounded-memory
    discipline as the flight recorder's series table).
    """

    def __init__(self, registry=None, tracer=None,
                 max_records: int = DEFAULT_MAX_RECORDS):
        self._obs = registry if registry is not None else get_registry()
        self._tracer = tracer if tracer is not None else get_tracer()
        self.max_records = int(max_records)
        self.compile_count = 0
        self.compile_wall_s = 0.0
        self.errors = 0
        self.dropped = 0
        self._records: dict[tuple[str, str], dict] = {}
        self._model_costs: dict[str, dict] = {}
        self._lock = threading.Lock()
        self._orig = None
        self._patched_module = None
        self._task = None

    # -- compile hook --------------------------------------------------------

    @property
    def installed(self) -> bool:
        return self._orig is not None

    def install(self) -> bool:
        """Patch the compile funnel. Returns whether this introspector
        owns the hook (False when another one already does)."""
        if self._orig is not None:
            return True
        import jax._src.compiler as compiler

        target = compiler.compile_or_get_cached
        if hasattr(target, "__lsr_introspector__"):
            # another introspector already owns the funnel — stacking
            # hooks would double-count every compile
            return False
        introspector = self

        def _hooked(*args, **kwargs):
            t0 = time.perf_counter()
            executable = target(*args, **kwargs)
            wall = time.perf_counter() - t0
            try:
                introspector._on_compile(args, kwargs, executable, wall)
            except Exception:  # introspection must never break a compile
                introspector.errors += 1
            return executable

        _hooked.__lsr_introspector__ = introspector
        _hooked.__wrapped__ = target
        self._orig = target
        self._patched_module = compiler
        compiler.compile_or_get_cached = _hooked
        return True

    def uninstall(self) -> None:
        """Restore the pristine funnel — only when the installed hook is
        still ours (someone re-patching after us keeps their patch)."""
        orig, self._orig = self._orig, None
        mod, self._patched_module = self._patched_module, None
        if orig is None or mod is None:
            return
        current = getattr(mod, "compile_or_get_cached", None)
        if getattr(current, "__lsr_introspector__", None) is self:
            mod.compile_or_get_cached = orig

    def _on_compile(self, args, kwargs, executable, wall: float) -> None:
        computation = kwargs.get("computation",
                                 args[1] if len(args) > 1 else None)
        module = _module_name(computation)
        raw_key = self._tracer.current_compile_key()
        key = render_key(raw_key) if raw_key is not None else module
        cost = _cost_entries(executable)
        memory = _memory_stats(executable)
        now = time.time()
        with self._lock:
            self.compile_count += 1
            self.compile_wall_s += wall
            rec = self._records.get((key, module))
            if rec is None:
                if len(self._records) >= self.max_records:
                    self.dropped += 1
                    return
                rec = self._records[(key, module)] = {
                    "key": key, "module": module, "compiles": 0,
                    "compile_wall_s": 0.0, "flops": 0.0,
                    "bytes_accessed": 0.0, "memory": None,
                    "first_time": now, "last_time": now,
                }
            rec["compiles"] += 1
            rec["compile_wall_s"] += wall
            # a recompile of the same geometry replaces the analysis
            # (same program ⇒ same numbers — the stability the tests pin)
            rec["flops"] = cost["flops"]
            rec["bytes_accessed"] = cost["bytes_accessed"]
            if memory is not None:
                rec["memory"] = memory
            rec["last_time"] = now
        obs = self._obs
        obs.counter("compile_count", key=key).inc()
        obs.counter("compile_wall_s", key=key).inc(wall)
        obs.gauge("xla_flops", key=key).set(cost["flops"])
        obs.gauge("xla_bytes_accessed", key=key).set(cost["bytes_accessed"])
        if memory is not None:
            obs.gauge("xla_temp_bytes", key=key).set(
                memory.get("temp_size_in_bytes", 0))

    # -- test/bench seam -----------------------------------------------------

    def note_compiled(self, key: str, module: str, *, flops: float,
                      bytes_accessed: float, wall_s: float = 0.0,
                      memory: dict | None = None) -> None:
        """Record one executable WITHOUT a real compile — the seam the
        roofline-join tests drive known numbers through (everything
        downstream of ``_on_compile``'s capture is shared)."""

        class _Fake:
            def cost_analysis(self):
                return [{"flops": flops, "bytes accessed": bytes_accessed}]

            def get_compiled_memory_stats(self):
                if memory is None:
                    raise NotImplementedError
                return type("MS", (), dict(memory))()

        class _Mod:
            class operation:
                attributes = {"sym_name": module}

        prev_tracer, self._tracer = self._tracer, _FixedKeyTracer(key)
        try:
            self._on_compile((None, _Mod()), {}, _Fake(), wall_s)
        finally:
            self._tracer = prev_tracer

    # -- records / model cross-check -----------------------------------------

    def records(self) -> list[dict]:
        with self._lock:
            return [dict(r) for r in self._records.values()]

    def register_model_cost(self, key: Any,
                            bytes_per_iteration: float | None = None,
                            flops_per_iteration: float | None = None,
                            collective_bytes_per_iteration: float | None
                            = None,
                            ) -> None:
        """Attach the HAND cost model for one compile key (bytes/flops
        one iteration — one sweep — moves), the reference the roofline
        cross-checks XLA's bytes-accessed against.
        ``TrainSegmentTimer.finish`` calls this with
        ``ops.sgd.dsgd_bytes_per_sweep`` / ``dsgd_flops_per_sweep``.
        ``collective_bytes_per_iteration``
        (``dsgd_collective_bytes_per_sweep``) is the rank-sharded
        kernels' per-device interconnect traffic — kept as its own term
        so the roofline prices HBM and wire separately (ISSUE 16)."""
        rendered = render_key(key)
        with self._lock:
            mc = self._model_costs.setdefault(rendered, {})
            if bytes_per_iteration:
                mc["bytes_per_iteration"] = float(bytes_per_iteration)
            if flops_per_iteration:
                mc["flops_per_iteration"] = float(flops_per_iteration)
            if collective_bytes_per_iteration:
                mc["collective_bytes_per_iteration"] = float(
                    collective_bytes_per_iteration)

    def model_costs(self) -> dict:
        with self._lock:
            return {k: dict(v) for k, v in self._model_costs.items()}

    # -- roofline join -------------------------------------------------------

    def roofline(self, hbm_peak_gbs: float | None = None,
                 fp32_peak_tflops: float | None = None) -> dict:
        """The live per-kernel roofline table (the ``/rooflinez``
        body): one row per compile key joining XLA's cost analysis with
        the tracer's measured execute walls and the registered hand
        models. Keys whose spans never executed steady-state rows carry
        the cost analysis alone (wall fields None). The peaks default to
        ``device_peaks()``: None off the described device, and the
        share-of-peak columns with them."""
        if hbm_peak_gbs is None and fp32_peak_tflops is None:
            hbm_peak_gbs, fp32_peak_tflops = device_peaks()
        walls = {render_key(k): v
                 for k, v in self._tracer.key_walls().items()}
        rows = roofline_rows(self.records(), walls, self.model_costs(),
                             hbm_peak_gbs=hbm_peak_gbs,
                             fp32_peak_tflops=fp32_peak_tflops)
        return {
            "time": time.time(),
            "hbm_peak_gbs": hbm_peak_gbs,
            "fp32_peak_tflops": fp32_peak_tflops,
            "compile_count": self.compile_count,
            "compile_wall_s": round(self.compile_wall_s, 4),
            "records": len(self._records),
            "dropped_records": self.dropped,
            "errors": self.errors,
            "rows": rows,
        }

    def publish_roofline(self) -> int:
        """Refresh the joined roofline as registry gauges
        (``xla_pct_of_hbm_peak{key=}`` / ``xla_pct_of_fp32_peak{key=}``
        / ``xla_achieved_gbs{key=}``) so the flight recorder's sampler
        turns them into series. Returns rows published."""
        if not self._obs.enabled:
            return 0
        published = 0
        for row in self.roofline()["rows"]:
            if row["achieved_gbs"] is None:
                continue
            key = row["key"]
            self._obs.gauge("xla_achieved_gbs", key=key).set(
                row["achieved_gbs"])
            # share-of-peak gauges only on the device the peaks describe
            for name in ("pct_of_hbm_peak", "pct_of_fp32_peak"):
                if row[name] is not None:
                    self._obs.gauge(f"xla_{name}", key=key).set(row[name])
            published += 1
        return published

    # -- device-memory telemetry --------------------------------------------

    def sample_device_memory(self, publish: bool = True) -> dict:
        """One sample of per-device memory state + a live-array dtype
        breakdown (the ``device_memory.json`` bundle document).

        ``device.memory_stats()`` is ``None`` on backends without an
        allocator stats surface (CPU) — those devices report
        ``stats: null`` and publish no byte gauges (the graceful-absent
        path the tests pin); ``supported`` says whether ANY local
        device reported stats."""
        import jax

        obs = self._obs if publish else None
        devices = []
        supported = False
        for d in jax.local_devices():
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
            label = f"{d.platform}:{d.id}"
            entry: dict = {"device": label, "stats": None}
            if stats:
                supported = True
                entry["stats"] = {k: int(v) for k, v in stats.items()
                                  if isinstance(v, (int, float))}
                if obs is not None and obs.enabled:
                    for field in ("bytes_in_use", "peak_bytes_in_use",
                                  "bytes_limit"):
                        v = stats.get(field)
                        if v is not None:
                            obs.gauge(f"device_{field}",
                                      device=label).set(v)
            devices.append(entry)
        by_dtype: dict[str, dict] = {}
        total_count = total_bytes = 0
        try:
            live = jax.live_arrays()
        except Exception:
            live = []
        for arr in live:
            try:
                dt = str(arr.dtype)
                nb = int(arr.nbytes)
            except Exception:
                continue
            agg = by_dtype.setdefault(dt, {"count": 0, "bytes": 0})
            agg["count"] += 1
            agg["bytes"] += nb
            total_count += 1
            total_bytes += nb
        if obs is not None and obs.enabled:
            obs.gauge("live_arrays_count").set(total_count)
            obs.gauge("live_arrays_bytes").set(total_bytes)
            for dt, agg in by_dtype.items():
                obs.gauge("live_array_bytes", dtype=dt).set(agg["bytes"])
        return {
            "time": time.time(),
            "supported": supported,
            "devices": devices,
            "live_arrays": {"count": total_count, "bytes": total_bytes,
                            "by_dtype": by_dtype},
        }

    # -- cadence -------------------------------------------------------------

    def _tick(self) -> None:
        self.sample_device_memory()
        self.publish_roofline()

    def start(self, interval_s: float = 1.0) -> "Introspector":
        """Run the device-memory sample + roofline-gauge refresh every
        ``interval_s`` on the shared ``PeriodicTask`` cadence (same
        machinery as the flight recorder's sampler)."""
        from large_scale_recommendation_tpu.obs.health import ensure_periodic

        self._task = ensure_periodic(self._task, self._tick,
                                     float(interval_s),
                                     name="obs-introspect")
        return self

    def stop(self) -> None:
        task, self._task = self._task, None
        if task is not None:
            task.stop()

    @property
    def running(self) -> bool:
        return self._task is not None and self._task.running

    # -- lifecycle -----------------------------------------------------------

    def close(self) -> None:
        self.stop()
        self.uninstall()


class _FixedKeyTracer:
    """Internal: a tracer stand-in whose current_compile_key is fixed —
    what ``note_compiled`` swaps in to drive the shared capture path."""

    def __init__(self, key):
        self._key = key

    def current_compile_key(self):
        return self._key


def roofline_rows(records: list[dict], walls: dict, model_costs: dict,
                  *, hbm_peak_gbs: float | None = None,
                  fp32_peak_tflops: float | None = None) -> list[dict]:
    """The PURE join (pinned against a hand-computed reference in
    tests/test_obs_introspect.py): per compile key, pick the dominant
    executable (max bytes-accessed — a keyed span family compiles
    helper modules too; the big one IS the kernel), sum compile
    count/wall over the family, and price the per-execution wall:

    - ``wall_per_exec``   = execute_total_s / execute_count
    - ``achieved_gbs``    = bytes_accessed / wall_per_exec / 1e9
    - ``pct_of_hbm_peak`` = 100 · achieved_gbs / hbm_peak_gbs (None
      when no peak is given — the device is not the described one)
    - ``achieved_tflops`` / ``pct_of_fp32_peak`` likewise from flops
    - ``xla_vs_model_bytes`` = bytes_accessed / (model bytes ×
      iterations-per-execution) — the hand-model cross-check
    - ``model_collective_bytes_per_exec`` = registered collective bytes ×
      iterations-per-execution — the rank-sharded kernels' interconnect
      term, its OWN key so wire traffic never hides inside the HBM
      number (None for replicated kernels)
    """
    by_key: dict[str, list[dict]] = {}
    for rec in records:
        by_key.setdefault(rec["key"], []).append(rec)
    rows = []
    for key, recs in sorted(by_key.items()):
        dom = max(recs, key=lambda r: (r["bytes_accessed"], r["flops"]))
        compiles = sum(r["compiles"] for r in recs)
        compile_wall = sum(r["compile_wall_s"] for r in recs)
        w = walls.get(key) or {}
        n_exec = int(w.get("execute_count", 0))
        row: dict = {
            "key": key,
            "module": dom["module"],
            "modules": len(recs),
            "compiles": compiles,
            "compile_wall_s": round(compile_wall, 4),
            "xla_flops": dom["flops"],
            "xla_bytes_accessed": dom["bytes_accessed"],
            "memory": dom.get("memory"),
            "execute_count": n_exec,
            "wall_per_exec_s": None,
            "achieved_gbs": None,
            "achieved_tflops": None,
            "pct_of_hbm_peak": None,
            "pct_of_fp32_peak": None,
            "model_bytes_per_exec": None,
            "xla_vs_model_bytes": None,
            "model_collective_bytes_per_exec": None,
        }
        if n_exec > 0:
            wall = w["execute_total_s"] / n_exec
            if wall > 0 and math.isfinite(wall):
                row["wall_per_exec_s"] = wall
                row["achieved_gbs"] = dom["bytes_accessed"] / wall / 1e9
                row["achieved_tflops"] = dom["flops"] / wall / 1e12
                if hbm_peak_gbs:
                    row["pct_of_hbm_peak"] = (
                        100.0 * row["achieved_gbs"] / hbm_peak_gbs)
                if fp32_peak_tflops:
                    row["pct_of_fp32_peak"] = (
                        100.0 * row["achieved_tflops"] / fp32_peak_tflops)
            iters_per_exec = w.get("iterations", n_exec) / n_exec
            mc = model_costs.get(key)
            if mc and mc.get("bytes_per_iteration"):
                model_bytes = mc["bytes_per_iteration"] * iters_per_exec
                row["model_bytes_per_exec"] = model_bytes
                if model_bytes > 0:
                    row["xla_vs_model_bytes"] = (
                        dom["bytes_accessed"] / model_bytes)
            if mc and mc.get("collective_bytes_per_iteration"):
                row["model_collective_bytes_per_exec"] = (
                    mc["collective_bytes_per_iteration"] * iters_per_exec)
        rows.append(row)
    return rows


# --------------------------------------------------------------------------
# Profiler capture layer (the ONE jax.profiler entry point)
# --------------------------------------------------------------------------


@contextlib.contextmanager
def profile_trace(log_dir: str):
    """Trace the XLA/host timeline to ``log_dir`` (TensorBoard format,
    ``tensorboard --logdir`` or xprof opens it). THE one capture layer:
    ``/profilez``, the watchdog postmortem auto-capture, and the legacy
    ``utils.metrics.profile`` shim all run through this lock +
    accounting. Raises ``RuntimeError`` when a capture is already in
    flight (the jax profiler is a process singleton)."""
    global CAPTURE_COUNT
    if not _PROFILE_LOCK.acquire(blocking=False):
        raise RuntimeError("a jax profiler capture is already in progress")
    try:
        import jax

        jax.profiler.start_trace(log_dir)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
            CAPTURE_COUNT += 1
            get_registry().counter("profiler_captures_total").inc()
    finally:
        _PROFILE_LOCK.release()


def capture_profile(out_dir: str, seconds: float = 1.0) -> dict:
    """Record ``seconds`` of whatever the process is doing (all
    threads — serving flushes, training segments) into ``out_dir``.
    The on-demand form behind ``/profilez`` and the watchdog-trip
    auto-capture. Returns ``{dir, seconds, files}``."""
    seconds = max(0.0, float(seconds))
    os.makedirs(out_dir, exist_ok=True)
    with profile_trace(out_dir):
        time.sleep(seconds)
    files = sorted(
        os.path.relpath(os.path.join(root, name), out_dir)
        for root, _, names in os.walk(out_dir) for name in names)
    return {"dir": out_dir, "seconds": seconds, "files": files}


# --------------------------------------------------------------------------
# Module-level default: None (zero-cost), installed by obs.enable_introspection
# --------------------------------------------------------------------------

_INTROSPECTOR: Introspector | None = None


def get_introspector() -> Introspector | None:
    """The installed introspector or ``None`` — producer hooks
    (``TrainSegmentTimer``, bundle writer, ``/rooflinez``) resolve this
    lazily, one ``is not None`` test on cold paths only."""
    return _INTROSPECTOR


def set_introspector(introspector: Introspector | None) -> None:
    global _INTROSPECTOR
    _INTROSPECTOR = introspector
