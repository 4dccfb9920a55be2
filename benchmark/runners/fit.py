"""Runner kind ``fit``: one ``fit_device`` call of a fixed number of
one-sweep segments on planted ratings made from the seed, timed from the
call to the end of the first sweep whose tables meet the configuration's
holdout-RMSE target.

The configuration names the solver: ``dsgd`` (``DSGD.fit_device`` on one
chip; sweep ends are stamped through its ``evaluator.on_segment`` hook) or
``mesh_dsgd`` (``MeshDSGD.fit_device`` over ``chips`` devices; it has no
such hook, so the sweep ends come from a checkpoint manager of the
benchmark's own, which stamps each one-sweep save and keeps the shards).
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import compare, datagen, harness
from benchmark.reference import dsgd_ref


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


def solver_config(cfg: dict, iterations: int, **overrides) -> dict:
    kw = dict(num_factors=cfg["num_factors"], lambda_=cfg["lambda"],
              iterations=iterations, learning_rate=cfg["learning_rate"],
              lr_schedule=cfg["lr_schedule"], seed=cfg["solver_seed"],
              minibatch_size=cfg["minibatch_size"],
              init_scale=cfg["init_scale"],
              collision_mode=cfg["collision_mode"],
              minibatch_sort=cfg["minibatch_sort"],
              factor_dtype=cfg["factor_dtype"])
    kw.update(overrides)
    return kw


def make_fit(cfg: dict, iterations: int, stamps, chips: int, **overrides):
    """``fit(u, i, r) -> MFModel`` through the solver the configuration
    names, one sweep per segment."""
    nu, ni = cfg["num_users"], cfg["num_items"]
    kw = solver_config(cfg, iterations, **overrides)
    if cfg["solver"] == "dsgd":
        from large_scale_recommendation_tpu.models.dsgd import (
            DSGD,
            DSGDConfig,
        )

        solver = DSGD(DSGDConfig(num_blocks=cfg["num_blocks"], **kw))
        solver.evaluator = stamps
        return lambda u, i, r: solver.fit_device(
            u, i, r, nu, ni, checkpoint_every=1)
    if cfg["solver"] == "mesh_dsgd":
        import jax

        from large_scale_recommendation_tpu.parallel import (
            MeshDSGD,
            Partitioner,
        )
        from large_scale_recommendation_tpu.parallel.dsgd_mesh import (
            MeshDSGDConfig,
        )

        if cfg["num_blocks"] != chips:
            raise ValueError("mesh_dsgd: the configuration's num_blocks is "
                             f"{cfg['num_blocks']}, the cell has {chips} "
                             "chips; the ring has one block per chip")
        part = Partitioner(devices=jax.local_devices()[:chips])
        solver = MeshDSGD(MeshDSGDConfig(**kw), partitioner=part)
        return lambda u, i, r: solver.fit_device(
            u, i, r, nu, ni, checkpoint_manager=stamps, checkpoint_every=1)
    raise ValueError(f"unknown solver {cfg['solver']!r}")


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
    out = [(dsgd_ref.to_id_space(jnp.asarray(U, jnp.float32), ru),
            dsgd_ref.to_id_space(jnp.asarray(V, jnp.float32), ri))
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
    overrides = {}
    if control == "bf16":
        overrides["factor_dtype"] = "bfloat16"

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
    model = make_fit(cfg, 1, warm, cell.chips, **overrides)(u, i, r)
    jax.block_until_ready((model.U, model.V))
    del model, warm
    gc.collect()

    # -- the window: one fit_device call
    stamps = SegmentStamps(window.spans)
    fit = make_fit(cfg, sweeps, stamps, cell.chips, **overrides)
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
    rmse = [float(dsgd_ref.holdout_rmse(U, V, *seen, hu, hi, hr))
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
    ref = dsgd_ref.fit(u, i, r, cfg, min(n_ref, sweeps))
    ref_rmse = [float(dsgd_ref.holdout_rmse(U, V, *ref["seen"], hu, hi, hr))
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
    ctx = {
        "trace": reduced, "chips": cell.chips, "window_s": wall,
        "peaks": harness.peaks_for(device),
        "series": {}, "counters": {
            "sweeps_to_target": None if hit is None else hit + 1,
            "sweeps_done": sweeps},
        "sizes": {"nnz_train": n_train, "num_users": cfg["num_users"],
                  "num_items": cfg["num_items"],
                  "rank": cfg["num_factors"],
                  "num_blocks": cfg["num_blocks"]},
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
                      "bmax": ref["bmax"]}}
