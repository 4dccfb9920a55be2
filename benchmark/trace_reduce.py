"""From a profiler trace (``.xplane.pb``) to numbers: device busy and idle
time, device time by program (jit) name, the device operations that took
most time, and the idle gaps named after what the host was doing.

Two steps, so that the second can be checked on a small recorded trace:
``read_xplane`` turns the file into plain event tuples with nothing but
``jax.profiler.ProfileData``; ``reduce_trace`` is pure arithmetic on them.

Layout of a TPU trace as this JAX writes it (looked at by hand, PR 24): one
plane per chip named ``/device:TPU:<n>``, whose line ``XLA Modules`` holds
one event per run of a compiled program (named ``jit_<fn>(<fingerprint>)``)
and whose line ``XLA Ops`` holds one event per HLO operation run; the host
is the plane ``/host:CPU``, one line per thread, on which a
``TraceAnnotation`` appears as an event of its name. All start times are
on one clock.
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench/window"
CONTAINER_OPS = ("%while", "%conditional", "%call")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_xplane(path: str, host_prefixes=("bench/", "serving/", "fit/")):
    """``{"devices": {chip: {"modules": [...], "ops": [...]}}, "host":
    [...]}``, every event a ``(name, start_ns, duration_ns)`` tuple. Host
    events are kept only when their name starts with one of
    ``host_prefixes`` (the benchmark's own annotations): a host thread
    line holds thousands of runtime events nobody reads."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[int, dict] = {}
    host = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {}
            for line in plane.lines:
                if line.name in (MODULE_LINE, OPS_LINE):
                    lines[line.name] = [
                        (e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events]
            devices[int(m.group(1))] = {
                "modules": lines.get(MODULE_LINE, []),
                "ops": lines.get(OPS_LINE, [])}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(host_prefixes):
                        host.append((e.name, int(e.start_ns),
                                     int(e.duration_ns)))
    return {"devices": devices, "host": host}


def program_name(module_event_name: str) -> str:
    """``jit__stage1_flat(123)`` -> ``_stage1_flat``."""
    name = module_event_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_name(hlo_text: str) -> str:
    """A short stable name for an ``XLA Ops`` event, whose name is the
    whole HLO instruction: ``%custom-call = (f32[16,40]{...}, ...)
    custom-call(...), custom_call_target="TopK"`` ->
    ``custom-call:TopK f32[16,40]``."""
    head, sep, rest = hlo_text.partition(" = ")
    if not sep:
        return hlo_text[:80]
    name = head.strip().lstrip("%")
    shape = rest.lstrip("(").split("{", 1)[0].split(" ", 1)[0].rstrip(",)")
    target = _TARGET.search(rest)
    if target:
        name += ":" + target.group(1)
    return f"{name} {shape}"


def _union(starts: np.ndarray, ends: np.ndarray):
    """Merged, sorted, disjoint intervals."""
    if len(starts) == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    run_end = np.maximum.accumulate(e)
    new = np.concatenate([[True], s[1:] > run_end[:-1]])
    idx = np.flatnonzero(new)
    return s[idx], np.concatenate([run_end[idx[1:] - 1], run_end[-1:]])


def _clip(events, w0, w1):
    if not events:
        return [], np.zeros(0, np.int64), np.zeros(0, np.int64)
    names = [e[0] for e in events]
    s = np.array([e[1] for e in events], np.int64)
    e = s + np.array([e[2] for e in events], np.int64)
    keep = (e > w0) & (s < w1)
    s, e = np.clip(s[keep], w0, w1), np.clip(e[keep], w0, w1)
    return [n for n, k in zip(names, keep) if k], s, e


def _busy_before(us: np.ndarray, ue: np.ndarray, t: np.ndarray):
    """Busy nanoseconds before each instant ``t``, given disjoint sorted
    busy intervals."""
    if len(us) == 0:
        return np.zeros(len(t), np.int64)
    cum = np.cumsum(ue - us)
    idx = np.searchsorted(us, t, side="right")
    full = np.where(idx > 0, cum[np.maximum(idx - 1, 0)], 0)
    over = np.where(idx > 0, np.maximum(ue[np.maximum(idx - 1, 0)] - t, 0), 0)
    return full - over


def reduce_trace(events: dict, top: int = 10) -> dict:
    """Numbers over the traced window: the ``bench/window`` host span when
    there is one, else the extent of all device events.

    - ``window_s``; ``busy_s``: seconds in which an operation ran on the
      device (union of the ``XLA Ops`` intervals), averaged over the chips;
    - ``program_s`` / ``program_runs``: device seconds and runs by program
      name, from the ``XLA Modules`` line, summed over chips;
    - ``program_per_chip_s``: the same for the busiest chip;
    - ``device_ops``: the ``top`` operations by device seconds, named
      ``<program>/<op>`` (loops and conditionals are left out: the
      operations of their bodies are listed themselves);
    - ``idle_gaps``: idle seconds of chip 0 by the benchmark span they fell
      in (``unattributed``: outside every span);
    - ``program_first_start_s`` / ``span_first_start_s``: first start of
      each program on a device, and of each host span, after the window's
      start; ``span_runs``: host spans by name; ``span_host_s``: for each
      host span its wall minus chip 0's busy time inside it.
    """
    devices = events["devices"]
    if not devices:
        raise ValueError("the trace holds no /device:TPU plane: no "
                         "operation ran on the device")
    host = events["host"]
    wins = [(s, s + d) for n, s, d in host if n == WINDOW_SPAN]
    if wins:
        w0, w1 = wins[0]
    else:
        every = [(s, s + d) for dev in devices.values()
                 for _, s, d in dev["ops"] + dev["modules"]]
        w0, w1 = min(a for a, _ in every), max(b for _, b in every)

    busy, program_s, program_runs, op_s = [], {}, {}, {}
    per_chip, first_start = {}, {}
    busy0 = None
    for chip in sorted(devices):
        dev = devices[chip]
        onames, os_, oe = _clip(dev["ops"], w0, w1)
        mnames, ms, me = _clip(dev["modules"], w0, w1)
        us, ue = _union(os_, oe) if onames else _union(ms, me)
        busy.append(float((ue - us).sum()) / 1e9)
        if busy0 is None:
            busy0 = (us, ue)
        chip_prog = {}
        for n, a, b in zip(mnames, ms, me):
            p = program_name(n)
            first_start[p] = min(first_start.get(p, np.inf), (a - w0) / 1e9)
            chip_prog[p] = chip_prog.get(p, 0.0) + (b - a) / 1e9
            program_runs[p] = program_runs.get(p, 0) + 1
        for p, v in chip_prog.items():
            program_s[p] = program_s.get(p, 0.0) + v
            per_chip[p] = max(per_chip.get(p, 0.0), v)
        # operations, named after the program running when they started
        if onames:
            order = np.argsort(ms, kind="stable")
            ms_sorted = ms[order]
            at = np.searchsorted(ms_sorted, os_, side="right") - 1
            for n, a, b, j in zip(onames, os_, oe, at):
                if n.startswith(CONTAINER_OPS):
                    continue  # its body's operations are events of their own
                prog = "?"
                if j >= 0 and a < me[order[j]]:
                    prog = program_name(mnames[order[j]])
                key = f"{prog}/{op_name(n)}"
                op_s[key] = op_s.get(key, 0.0) + (b - a) / 1e9

    span_runs, span_first, span_host = {}, {}, {}
    hnames, hs, he = _clip([h for h in host if h[0] != WINDOW_SPAN], w0, w1)
    inside = (_busy_before(*busy0, he) - _busy_before(*busy0, hs)
              if hnames else [])
    for n, a, b, busy_ns in zip(hnames, hs, he, inside):
        span_runs[n] = span_runs.get(n, 0) + 1
        span_first[n] = min(span_first.get(n, np.inf), (a - w0) / 1e9)
        span_host.setdefault(n, []).append((b - a - busy_ns) / 1e9)

    # idle time of chip 0, named after the benchmark span it fell in (the
    # spans do not nest apart from the window's own)
    idle_total = (w1 - w0 - float((busy0[1] - busy0[0]).sum())) / 1e9
    gap_s = {n: float(sum(v)) for n, v in span_host.items()}
    rest = idle_total - sum(gap_s.values())
    if rest > 1e-9:
        gap_s["unattributed"] = rest

    def ranked(d):
        return [[k, v] for k, v in sorted(d.items(),
                                          key=lambda kv: -kv[1])[:top]]

    return {"window_s": (w1 - w0) / 1e9,
            "busy_s": float(np.mean(busy)),
            "program_s": program_s, "program_runs": program_runs,
            "program_per_chip_s": per_chip,
            "program_first_start_s": first_start,
            "span_first_start_s": span_first, "span_runs": span_runs,
            "span_host_s": span_host,
            "device_ops": ranked(op_s), "idle_gaps": ranked(gap_s)}
