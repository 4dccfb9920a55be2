"""Say why a closed-loop serving cell's rate differs from run to run
(PERF.md, Findings, PR 28: the bulk cell's spread).

    python3 benchmark/tools/bulk_probe.py --workload <cell> --seed <n> --seconds 51 [--request-users 8192]

One run of the cell through ``run.run_cell``, as the driver makes it, with
two things beside the result: every flush's wall, reduced to the median
flush, the flushes that took a quarter longer than it (the machine stood
still in them) and the milliseconds they took beyond it; and what the
kernel counted over the window: the control group's throttled periods
(``cpu.stat``), the machine's stolen and idle CPU time (``/proc/stat``),
the process's own CPU time and context switches. ``--request-users`` puts
another request size in the traffic file's place, to try one before the
file is changed. A diagnosis by hand, never part of a run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

CPU_FIELDS = ("user", "nice", "system", "idle", "iowait", "irq", "softirq",
              "steal")


def _read(path: str) -> str | None:
    try:
        with open(path) as f:
            return f.read()
    except OSError:
        return None


def kernel_counters() -> dict:
    """What the kernel has counted so far, by a flat name each."""
    out = {}
    for path in ("/sys/fs/cgroup/cpu.stat", "/sys/fs/cgroup/cpu/cpu.stat"):
        for line in (_read(path) or "").splitlines():
            key, _, value = line.partition(" ")
            out["cgroup." + key] = float(value)
    stat = (_read("/proc/stat") or "").splitlines()
    if stat:
        ticks = os.sysconf("SC_CLK_TCK")
        for key, value in zip(CPU_FIELDS, stat[0].split()[1:]):
            out["machine." + key + "_s"] = float(value) / ticks
    for line in (_read("/proc/self/status") or "").splitlines():
        if "ctxt_switches" in line:
            key, _, value = line.partition(":")
            out["process." + key] = float(value)
    times = os.times()
    out["process.user_s"], out["process.system_s"] = times.user, times.system
    return out


def flush_summary(flush_wall_ms, slow: float = 1.25) -> dict:
    """The median flush, and the flushes ``slow`` times as long or longer:
    how many, and the milliseconds they took beyond the median."""
    wall = np.asarray(flush_wall_ms, float)
    p50 = float(np.median(wall))
    over = wall[wall >= slow * p50]
    return {"flushes": int(len(wall)), "flush_p50_ms": p50,
            "flush_p05_ms": float(np.percentile(wall, 5)),
            "flush_p95_ms": float(np.percentile(wall, 95)),
            "flush_sum_s": float(wall.sum()) / 1e3,
            "slow_flushes": int(len(over)),
            "slow_excess_ms": float((over - p50).sum()),
            "slow_ms": [round(float(w), 1) for w in np.sort(over)[::-1][:12]]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--request-users", type=int, default=0)
    ap.add_argument("--cpu", action="store_true",
                    help="a rehearsal off the chip, at the configuration "
                         "file's toy sizes")
    args = ap.parse_args(argv)

    from benchmark import harness, loadgen
    from benchmark.run import run_cell

    cell = harness.resolve_cell(args.workload)
    if args.cpu:
        cell.config = dict(cell.config, **cell.config["toy"])
    if args.request_users:
        cell.traffic = dict(cell.traffic,
                            request_users={"fixed": args.request_users})
    counted = {}
    closed_loop = loadgen.run_closed_loop

    def counting(*a, **kw):
        before = kernel_counters()
        out = closed_loop(*a, **kw)
        after = kernel_counters()
        counted.update({k: after[k] - before[k] for k in after
                        if k in before})
        return out

    loadgen.run_closed_loop = counting
    try:
        line, out = run_cell(args.workload, args.seed, args.seconds, False,
                             require_tpu=not args.cpu, cell=cell)
    finally:
        loadgen.run_closed_loop = closed_loop
    result = json.loads(line)
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "request_users": int(cell.traffic["request_users"]["fixed"]),
        "correct": result["correct"],
        "serve_users_per_s": out["values"]["serve_users_per_s"],
        "setup_s": out["values"]["setup_s"],
        "window_s": out["ctx"]["window_s"],
        "memory_peak_bytes": out["memory_peak_bytes"],
        **flush_summary(out["ctx"]["series"]["flush_wall_ms"]),
        "window_counters": {k: round(v, 3) for k, v in counted.items()
                            if v}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
