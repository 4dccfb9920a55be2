"""Runner kind ``fit_rank``: one ``fit_device`` call of a fixed number of
one-sweep segments on planted implicit-feedback interactions made from the
seed, timed from the call to the end of the first sweep whose tables meet
the configuration's expected-percentile-rank target on held-out
interactions (lower is better; 0.5 is chance).

It is the ``fit`` kind with other data and another quality: the solver and
reference lookups, the warm-up fit, the window, the stamps at the sweep
ends, the tables in id space and the four compared numbers are
``runners/fit.py``'s and ``compare.fit_numbers``'s, with the rank in the
place of the holdout RMSE. The data are this file's own
(``planted_interactions``; ``datagen.py`` makes ratings), and the rank is
the reference file's (``expected_percentile_rank``), computed for both
sides after the window has closed. The program's own rank
(``obs.PercentileRankEvaluator``, the ``on_segment`` hook a deployment
would hang on the fit) scores the target sweep's tables too, and the line
carries how far it lies from the reference's (``program_rank_gap``); it
decides nothing.
"""

from __future__ import annotations

import gc
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import compare, harness
from benchmark.datagen import seed_key
from benchmark.runners.fit import (
    SegmentStamps,
    control_overrides,
    id_space,
    solver_for,
    sweeps_for,
)

REFERENCE = "ials_ref"  # of a configuration that names none

# -- the data -----------------------------------------------------------------


@partial(jax.jit, static_argnames=(
    "num_users", "num_items", "n_keep", "draws", "genres", "item_skew",
    "item_offset", "user_floor", "user_sigma", "primary_share",
    "secondary_share", "count_shape", "count_cap"))
def _planted(key, *, num_users, num_items, n_keep, draws, genres, item_skew,
             item_offset, user_floor, user_sigma, primary_share,
             secondary_share, count_shape, count_cap):
    """``draws`` draws of a (user, item) pair, merged into unique pairs with
    a play count each, in random order; the first ``n_keep`` of them and how
    many unique pairs there were."""
    k_deg, k_g1, k_g2, k_mix, k_pick, k_item, k_tail, k_order = (
        jax.random.split(key, 8))
    # how many draws a user makes: a floor plus a lognormal share of the rest
    x = jnp.exp(user_sigma * jax.random.normal(k_deg, (num_users,)))
    spare = draws - user_floor * num_users
    deg = user_floor + jnp.floor(spare * (x / jnp.sum(x))).astype(jnp.int32)
    deg = deg + (jnp.arange(num_users) < draws - jnp.sum(deg))
    user = jnp.repeat(jnp.arange(num_users, dtype=jnp.int32), deg,
                      total_repeat_length=draws)
    # a user's taste: a first genre taken with probability m, a second with
    # `secondary_share` of the rest, any genre otherwise (rank `genres`)
    lo, hi = primary_share
    g1 = jax.random.randint(k_g1, (num_users,), 0, genres)
    g2 = jax.random.randint(k_g2, (num_users,), 0, genres)
    m = jax.random.uniform(k_mix, (num_users,), minval=lo, maxval=hi)
    taste = (g1 | (g2 << 8) | (jnp.floor(m * 32767.0).astype(jnp.int32) << 16)
             )[user]
    m = (taste >> 16).astype(jnp.float32) / 32767.0
    a, b = jax.random.uniform(k_pick, (2, draws))
    genre = jnp.where(
        a < m, taste & 0xFF,
        jnp.where(a < m + (1.0 - m) * secondary_share, (taste >> 8) & 0xFF,
                  jnp.floor(b * genres).astype(jnp.int32)))
    # a genre's items by popularity: place p has weight (p + offset)^-skew,
    # drawn through the inverse of its continuous distribution; the item of
    # place p in genre g is p * genres + g, so low ids are popular
    per = -(-num_items // genres)
    e = 1.0 - item_skew
    c0, c1 = item_offset ** e, (per + item_offset) ** e
    v = jax.random.uniform(k_item, (draws,))
    place = jnp.floor((c0 + v * (c1 - c0)) ** (1.0 / e) - item_offset)
    place = jnp.clip(place.astype(jnp.int32), 0, per - 1)
    item = (place * genres + genre) % num_items
    # merge the draws of one pair: its play count is how many draws fell on
    # it, less one, plus a Pareto tail (most pairs read 1)
    user, item = jax.lax.sort((user, item), num_keys=2)
    at = jnp.arange(draws, dtype=jnp.int32)
    first = ((at == 0) | (user != jnp.roll(user, 1))
             | (item != jnp.roll(item, 1)))
    next_first = jax.lax.cummin(
        jnp.where(first, at, draws)[::-1])[::-1]  # the next run's start,
    run = jnp.concatenate(                        # seen from inside a run
        [next_first[1:], jnp.full(1, draws, jnp.int32)]) - at
    tail = jnp.floor(jax.random.uniform(
        k_tail, (draws,), minval=1e-7) ** (-1.0 / count_shape))
    count = jnp.minimum(run - 1 + tail.astype(jnp.int32), count_cap)
    # unique pairs in random order, the merged draws behind them
    order = jnp.where(first, jax.random.bits(k_order, (draws,)) >> 1,
                      jnp.uint32(1 << 31))
    _, user, item, count = jax.lax.sort((order, user, item, count),
                                        num_keys=1)
    return (user[:n_keep], item[:n_keep],
            count[:n_keep].astype(jnp.float32), jnp.sum(first))


def planted_interactions(seed: int, cfg: dict, holdout_share: float = 0.05):
    """``((u, i, r), (hu, hi, hr))`` on the device: ``nnz`` unique (user,
    item) pairs with a play count each, dense int32 ids, float32 counts,
    split 95/5 at random. Positives follow a planted affinity of rank
    ``genres`` (each user mixes two genres and a background) times a
    popularity skew inside each genre; a user makes at least ``user_floor``
    draws; play counts are heavy-tailed with most equal to 1."""
    nnz = int(cfg["nnz"])
    n_hold = int(round(nnz * holdout_share))
    u, i, r, unique = _planted(
        seed_key(seed), num_users=int(cfg["num_users"]),
        num_items=int(cfg["num_items"]), n_keep=nnz,
        draws=int(nnz * float(cfg["oversample"])), genres=int(cfg["genres"]),
        item_skew=float(cfg["item_skew"]),
        item_offset=float(cfg["item_offset"]),
        user_floor=int(cfg["user_floor"]),
        user_sigma=float(cfg["user_sigma"]),
        primary_share=tuple(float(x) for x in cfg["primary_share"]),
        secondary_share=float(cfg["secondary_share"]),
        count_shape=float(cfg["count_shape"]),
        count_cap=int(cfg["count_cap"]))
    if int(unique) < nnz:
        raise SystemExit(f"fit_rank: {int(cfg['oversample'] * nnz)} draws "
                         f"gave {int(unique)} unique pairs, fewer than nnz "
                         f"{nnz}: raise the configuration's oversample")
    n_train = nnz - n_hold
    return ((u[:n_train], i[:n_train], r[:n_train]),
            (u[n_train:], i[n_train:], r[n_train:]))


# -- the run ------------------------------------------------------------------


def program_counters(solver, make_and_run):
    """Run ``make_and_run()`` (the warm-up fit) with the program's metrics
    registry live and hand its snapshot to the solver file's ``counters``:
    what the program itself counted of its plan. A program that counts
    nothing there (the parent of the PR that added the count) gives {}."""
    from large_scale_recommendation_tpu import obs

    registry, _ = obs.enable()
    try:
        make_and_run()
        return dict(solver.counters(registry.snapshot()["metrics"]))
    finally:
        obs.disable()


def program_rank(U_id, V_id, seen, hold):
    """The same tables ranked by the program's own evaluator, over the
    pairs the reference counts (user and item both seen in training). A
    program without one (the parent of the PR that added it) gives None."""
    try:
        from large_scale_recommendation_tpu.obs import PercentileRankEvaluator
    except ImportError:
        return None
    hu, hi, hr = (np.asarray(a) for a in hold)
    su, si = (np.asarray(a) for a in seen)
    return PercentileRankEvaluator(hu, hi, hr * (su[hu] & si[hi])).on_segment(
        U_id, V_id, label="benchmark")


def run(cell, seed: int, seconds: float, trace: bool, device: dict,
        control: str | None = None) -> dict:
    cfg, traffic = cell.config, cell.traffic
    on_chip = device["platform"] == "tpu"
    n_ref = int(traffic["reference_sweeps"])
    solver = solver_for(cell)
    reference = harness.reference_for(cell, REFERENCE)
    overrides = control_overrides(solver, control)

    # -- set-up: data from the seed, then every shape warmed by running it
    (u, i, r), hold = planted_interactions(seed, cfg)
    jax.block_until_ready((r, hold))
    n_train = int(u.shape[0])
    sweeps = sweeps_for(traffic, seconds)
    window = harness.Window(trace, harness.trace_dir_for(cell.name),
                            strict=on_chip)

    def warm_up():
        model = solver.make_fit(cfg, 1, SegmentStamps(window.spans),
                                cell.chips, **overrides)(u, i, r)
        jax.block_until_ready((model.U, model.V))

    counted = program_counters(solver, warm_up)
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

    # -- rank the held-out interactions by every segment's tables
    prog_id, seen = id_space(model, stamps.tables, cfg["num_users"],
                             cfg["num_items"])
    del model, fit
    stamps.tables = []
    rank = [reference.expected_percentile_rank(U, V, *seen, *hold)
            for U, V in prog_id]
    target = float(cfg["target_rank"])
    hit = next((j for j, x in enumerate(rank) if x <= target), None)
    cut = sweeps < int(traffic["sweeps"])
    failed = int(hit is None)
    if hit is None and not cut:
        print(f"fit_rank: expected percentile rank {rank} never reached the "
              f"target {target} in {sweeps} sweeps: a failed run", flush=True)
    reached = hit if hit is not None else sweeps - 1
    ends = [t - t0 for t in stamps.ends]
    print(f"fit_rank: {sweeps} sweeps, wall {wall:.3f}s, sweep ends "
          f"{[round(x, 3) for x in ends]}, expected percentile rank "
          f"{[round(x, 5) for x in rank]}, target {target}", flush=True)
    own = program_rank(*prog_id[reached], seen, hold)
    own_gap = None if own is None else abs(own - rank[reached])
    if own is not None:
        print(f"fit_rank: the program's evaluator ranks sweep {reached + 1} "
              f"at {own:.8f}, {own_gap:.3g} from the reference's function",
              flush=True)

    # -- the comparison, once the window has closed and the peak is read
    prog_id = prog_id[:n_ref]
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference.fit(u, i, r, cfg, min(n_ref, sweeps))
    ref_rank = [reference.expected_percentile_rank(U, V, *ref["seen"], *hold)
                for U, V in ref["sweeps"]]
    numbers = compare.fit_numbers(prog_id, rank, ref, ref_rank)
    print(f"fit_rank: reference {time.perf_counter() - t_ref:.1f}s, its rank "
          f"{[round(x, 5) for x in ref_rank]}", flush=True)
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
            "sweeps_done": sweeps, **counted},
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
                      "expected_percentile_rank": rank, "sweep_ends_s": ends,
                      "per_device_peak_bytes": per_device_peak,
                      "program_counters": counted,
                      "program_rank": own, "program_rank_gap": own_gap,
                      **ref.get("notes", {})}}
