"""Runner kind ``fit``: one ``fit_device`` call of a fixed number of
one-sweep segments on planted ratings made from the seed, timed from the
call to the end of the first sweep whose tables meet the configuration's
holdout-RMSE target.

The configuration names the solver, a file ``runners/solvers/<solver>.py``
that builds the fit, and the plain reference it is compared with, a file
``reference/<reference>.py`` (``dsgd_ref`` where it names none). What every
fit shares is here: the data from the seed, the warm-up fit, the window,
the stamps at the sweep ends, the tables in id space, the holdout RMSE and
the target, the comparison and the result's keys.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import compare, datagen, harness
from benchmark.reference.dsgd_ref import holdout_rmse, to_id_space

REFERENCE = "dsgd_ref"  # of a configuration that names none


class SegmentStamps:
    """Stamps the end of every one-sweep segment (after blocking on its
    tables) and keeps the tables. Serves as ``DSGD.evaluator`` (its
    ``on_segment``) and as ``MeshDSGD``'s checkpoint manager (``save``)."""

    def __init__(self, spans):
        self.spans = spans
        self.ends: list[float] = []
        self.tables: list[tuple] = []

    def on_segment(self, U, V, label="segment", step=None):
        import jax

        jax.block_until_ready((U, V))
        self.ends.append(self.spans.now())
        self.tables.append((U, V))

    def save(self, step, arrays, meta=None):
        self.on_segment(arrays["U"], arrays["V"], step=step)


def solver_for(cell):
    """The solver the configuration names: ``runners/solvers/<solver>.py``
    (its contract: ``benchmark/README.md``)."""
    name = cell.config["solver"]
    return harness.load_file(cell.root, "runners/solvers/" + name,
                             f"solver {name!r}")


def control_overrides(solver, control: str | None) -> dict:
    """What ``--control <name>`` overrides in this solver's configuration."""
    if control is None:
        return {}
    if control not in solver.CONTROLS:
        raise SystemExit(f"fit: {solver.__file__} has no control "
                         f"{control!r}, only {sorted(solver.CONTROLS)}")
    return dict(solver.CONTROLS[control])


def sweeps_for(traffic: dict, seconds: float) -> int:
    """The traffic file's sweep count: a fixed amount of work from
    ``full_at_seconds`` up; a shorter ``--seconds`` (a trial run) keeps
    its share of the sweeps, one at least."""
    full = int(traffic["sweeps"])
    share = seconds / float(traffic["full_at_seconds"])
    return max(1, min(full, int(full * share)))


def id_space(model, tables, num_users: int, num_items: int):
    """The program's tables, row space -> id space, through the index the
    program returned; and per side which ids it has seen."""
    import jax.numpy as jnp

    def side(index, n):
        rows = np.zeros(n, np.int32)
        seen = np.zeros(n, bool)
        rows[index.sorted_ids] = index.sorted_rows
        seen[index.sorted_ids] = True
        return jnp.asarray(rows), jnp.asarray(seen)

    ru, su = side(model.users, num_users)
    ri, si = side(model.items, num_items)
    out = [(to_id_space(jnp.asarray(U, jnp.float32), ru),
            to_id_space(jnp.asarray(V, jnp.float32), ri))
           for U, V in tables]
    return out, (su, si)


def gather_to_one_device(tables):
    """Sharded tables (the ring's) onto the first device, for scoring."""
    import jax

    dev = jax.local_devices()[0]
    return [tuple(jax.device_put(x, dev) for x in t) for t in tables]


def run(cell, seed: int, seconds: float, trace: bool, device: dict,
        control: str | None = None) -> dict:
    import jax

    cfg, traffic = cell.config, cell.traffic
    on_chip = device["platform"] == "tpu"
    n_ref = int(traffic["reference_sweeps"])
    solver = solver_for(cell)
    reference = harness.reference_for(cell, REFERENCE)
    overrides = control_overrides(solver, control)

    # -- set-up: data from the seed, then every shape warmed by running it
    (u, i, r), (hu, hi, hr) = datagen.planted_ratings(
        seed, num_users=cfg["num_users"], num_items=cfg["num_items"],
        nnz=cfg["nnz"], rank=cfg["planted_rank"], noise=cfg["noise"],
        skew_lam=cfg["skew_lam"])
    jax.block_until_ready((r, hr))
    n_train = int(u.shape[0])
    sweeps = sweeps_for(traffic, seconds)
    window = harness.Window(trace, harness.trace_dir_for(cell.name),
                            strict=on_chip)
    warm = SegmentStamps(window.spans)
    model = solver.make_fit(cfg, 1, warm, cell.chips, **overrides)(u, i, r)
    jax.block_until_ready((model.U, model.V))
    del model, warm
    gc.collect()

    # -- the window: one fit_device call
    stamps = SegmentStamps(window.spans)
    fit = solver.make_fit(cfg, sweeps, stamps, cell.chips, **overrides)
    with window.measure():
        with window.spans.span("fit/fit_device"):
            model = fit(u, i, r)
            jax.block_until_ready((model.U, model.V))
    t0, wall = window.t0, window.wall
    peak = harness.memory_peak_bytes()
    per_device_peak = harness.per_device_peak_bytes()
    reduced = window.reduce()

    # -- score every segment's tables on the holdout (after the call)
    tables = stamps.tables
    if cell.chips > 1:
        tables = gather_to_one_device(tables)
    prog_id, seen = id_space(model, tables, cfg["num_users"],
                             cfg["num_items"])
    del model, fit, tables
    stamps.tables = []
    rmse = [float(holdout_rmse(U, V, *seen, hu, hi, hr))
            for U, V in prog_id]
    target = float(cfg["target_rmse"])
    hit = next((j for j, x in enumerate(rmse) if x <= target), None)
    cut = sweeps < int(traffic["sweeps"])
    failed = int(hit is None)
    if hit is None and not cut:
        print(f"fit: holdout RMSE {rmse} never reached the target {target} "
              f"in {sweeps} sweeps: a failed run", flush=True)
    reached = hit if hit is not None else sweeps - 1
    ends = [t - t0 for t in stamps.ends]
    print(f"fit: {sweeps} sweeps, wall {wall:.3f}s, sweep ends "
          f"{[round(x, 3) for x in ends]}, holdout RMSE "
          f"{[round(x, 5) for x in rmse]}, target {target}", flush=True)

    # -- the comparison, once the window has closed and the peak is read
    prog_id = prog_id[:n_ref]
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference.fit(u, i, r, cfg, min(n_ref, sweeps))
    ref_rmse = [float(holdout_rmse(U, V, *ref["seen"], hu, hi, hr))
                for U, V in ref["sweeps"]]
    numbers = compare.fit_numbers(prog_id, rmse, ref, ref_rmse)
    print(f"fit: reference {time.perf_counter() - t_ref:.1f}s, its RMSE "
          f"{[round(x, 5) for x in ref_rmse]}", flush=True)
    numbers = {k: v for k, v in numbers.items() if k in cfg["limits"]}
    correct, compared = compare.judge(numbers, cfg["limits"])

    values = {
        "time_to_target_s": ends[reached],
        "train_ratings_per_s": n_train * sweeps / wall,
        "setup_s": window.setup_s,
    }
    sizes = {"nnz_train": n_train, "num_users": cfg["num_users"],
             "num_items": cfg["num_items"], "rank": cfg["num_factors"],
             **solver.sizes(cfg)}
    ctx = {
        "trace": reduced, "chips": cell.chips, "window_s": wall,
        "peaks": harness.peaks_for(device),
        "series": {}, "counters": {
            "sweeps_to_target": None if hit is None else hit + 1,
            "sweeps_done": sweeps},
        "sizes": sizes, "sweep_flops": solver.sweep_flops(sizes),
    }
    return {"correct": correct and window.compiles.count == 0,
            "compared": compared, "attempted": 1,
            "failed": failed if not cut else 0,
            "fatal": ("target not reached" if failed and not cut else None),
            "values": values, "ctx": ctx,
            "memory_peak_bytes": peak, "reduced": reduced,
            "compiles_in_window": window.compiles.count,
            "notes": {"sweeps": sweeps, "window_cut": cut,
                      "holdout_rmse": rmse, "sweep_ends_s": ends,
                      "per_device_peak_bytes": per_device_peak,
                      **ref.get("notes", {})}}
