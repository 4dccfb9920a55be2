"""chip_smoke.py: the main path, once, on the chip — the quickest proof that
the system still starts there.

ONE process drives one TPU chip (or all four of a host) through the entry
points a user calls, at the full width of the flagship — the ML-25M shape,
162,541 x 59,047 at rank 128, with the fit cells' settings
(``dsgd_config``), random weights from a seed:

1. train   ``DSGD.fit_device`` for a few sweeps; holdout RMSE is finite and
           lower after the last sweep than after the first.
2. serve   ``ServingEngine`` over the trained 59,047 x 128 catalog, mixed
           request sizes through ``submit``/``flush``: the exact path's ids
           equal ``model.recommend``; the two-stage retriever's recall is
           measured against it.
3. mesh    with four or more devices: ``MeshDSGD`` on the 4x1 mesh, the
           2x2 rank-sharded mesh against its 2x1 twin, one ``MeshALS``
           round and mesh serving — every placed array proven to sit on
           four distinct devices.

It fails (non-zero exit, no result line) when JAX finds no TPU, when the
package is not beside it, or when any leg raises or any check fails: no
``try/except`` records an error and carries on. On success the last
stdout line is ``{"ok": true, "device": {...}}`` with the device as JAX
reports it. Numbers printed on the way are observations of one run, not
metrics. There is no CPU mode: ``tests/test_chip_smoke.py`` rehearses the
legs on the CPU at a toy size, which proves nothing about the chip.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.metadata
import json
import os
import sys
import time

import numpy as np

# Chip-to-chip agreement bounds, set from what the chip showed (PERF.md
# Findings). After one sweep from identical initial factors the 2x2
# rank-sharded mesh differed from its 2x1 twin by 5.2e-8 on factor
# entries of magnitude 0.110: 5e-7 relative, a few f32 ulps — the order
# of the psum. The holdout RMSEs were equal to the last printed digit.
# The bounds leave ~20x room over that and none for a real disagreement
# (one bf16 pass would be ~4e-3 relative).
FACTORS_MAX_REL = 1e-5         # max|d factors| / max|factors|
HOLDOUT_RMSE_MAX_ABS = 1e-5
TWO_STAGE_MIN_RECALL = 0.90    # int8 stage 1 + f32 rescore vs exact top-10
MEMORY_SPREAD_MAX = 2.0        # max/min of per-device bytes a mesh fit adds


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The flagship at full width. Vocabulary and rank are the point of
    the smoke; only ``nnz`` and depth may ever be cut, and the output
    says so when they are. The CPU rehearsal test passes a toy instance."""

    num_users: int | None = None   # None: the ML-25M shape, 162,541
    num_items: int | None = None   # None: 59,047
    nnz: int = 25_000_095
    rank: int = 128
    sweeps: int = 4
    blocks: int = 8                # the fit cells': k = 8, minibatch 32768
    minibatch: int = 32768
    als_nnz: int = 1_000_000
    request_sizes: tuple = (1, 7, 64, 300, 1500)


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond, what: str) -> None:
    """A failed check ends the smoke: it is an exception, not a record."""
    if not cond:
        raise AssertionError(f"chip_smoke check failed: {what}")
    log(f"  ok: {what}")


def dsgd_config(s: Sizes, **overrides):
    """The fit cells' settings (``benchmark/configs/netflix100m-r128*.json``;
    tests/test_chip_smoke.py holds the two to each other)."""
    from large_scale_recommendation_tpu.models.dsgd import DSGDConfig

    kw = dict(num_factors=s.rank, lambda_=0.1, iterations=s.sweeps,
              learning_rate=0.3, lr_schedule="warm_boost", seed=0,
              minibatch_size=s.minibatch, init_scale=0.08,
              collision_mode="mean", minibatch_sort="item",
              num_blocks=s.blocks)
    kw.update(overrides)
    return DSGDConfig(**kw)


class SegmentTables:
    """``DSGD.evaluator`` hook: keeps each one-sweep segment's tables, so
    the holdout RMSE after every sweep can be scored once the fit has
    returned the id -> row index that scoring needs."""

    def __init__(self):
        self.tables = []

    def on_segment(self, U, V, label="segment", step=None):
        self.tables.append((step, U, V))


def holdout_rmse(model, U, V, holdout) -> float:
    from large_scale_recommendation_tpu.models.mf import MFModel

    return MFModel(U=U, V=V, users=model.users,
                   items=model.items).rmse(holdout)


# --------------------------------------------------------------------------
# legs
# --------------------------------------------------------------------------


def generate(s: Sizes):
    """The bench's workload, generated on device: only a PRNG key crosses
    the host link. Returns the device COO triple, the host holdout and
    the vocabulary."""
    import jax

    from large_scale_recommendation_tpu.core.types import Ratings
    from large_scale_recommendation_tpu.data.device_blocking import (
        synthetic_like_device,
    )

    t0 = time.perf_counter()
    (u, i, r), (hu, hi, hr), (nu, ni) = synthetic_like_device(
        "ml-25m", nnz=s.nnz, rank=16, noise=0.1, seed=0, skew_lam=2.0,
        num_users=s.num_users, num_items=s.num_items)
    jax.block_until_ready(r)
    holdout = Ratings.from_arrays(np.asarray(hu), np.asarray(hi),
                                  np.asarray(hr))
    log(f"generate: {nu} x {ni}, nnz {s.nnz} ({int(u.shape[0])} train, "
        f"{holdout.n} holdout) in {time.perf_counter() - t0:.1f}s")
    return (u, i, r), holdout, (nu, ni)


def leg_train(s: Sizes, data, holdout, vocab):
    """Flagship training through ``DSGD.fit_device``, one sweep per
    segment so every sweep's holdout RMSE can be read."""
    from large_scale_recommendation_tpu.models.dsgd import DSGD

    log(f"[train] DSGD.fit_device rank {s.rank}, k={s.blocks}, minibatch "
        f"{s.minibatch}, {s.sweeps} sweeps")
    solver = DSGD(dsgd_config(s))
    solver.evaluator = SegmentTables()
    t0 = time.perf_counter()
    model = solver.fit_device(*data, *vocab, checkpoint_every=1)
    curve = [holdout_rmse(model, U, V, holdout)
             for _, U, V in solver.evaluator.tables]
    wall = time.perf_counter() - t0
    log(f"  holdout RMSE per sweep: {[round(x, 4) for x in curve]} "
        f"(fit + scoring {wall:.1f}s)")
    check(model.U.shape[1] == s.rank and model.V.shape[1] == s.rank
          and model.U.shape[0] >= vocab[0] and model.V.shape[0] >= vocab[1],
          f"factor tables are full width: U {tuple(model.U.shape)}, "
          f"V {tuple(model.V.shape)}")
    check(len(curve) == s.sweeps and all(np.isfinite(curve)),
          "holdout RMSE is finite after every sweep")
    check(curve[-1] < curve[0],
          f"holdout RMSE fell: {curve[0]:.4f} after sweep 1 -> "
          f"{curve[-1]:.4f} after sweep {s.sweeps}")
    return model


def make_requests(s: Sizes, num_users: int):
    rng = np.random.default_rng(0)
    return [rng.integers(0, num_users, n).astype(np.int64)
            for n in s.request_sizes]


def recall_at_k(got_ids, want_ids) -> float:
    hits = total = 0
    for g, w in zip(got_ids, want_ids):
        for grow, wrow in zip(g, w):
            wset = set(int(x) for x in wrow if x >= 0)
            hits += len(wset & set(int(x) for x in grow))
            total += len(wset)
    return hits / max(total, 1)


def leg_serve(s: Sizes, model, num_users: int, mesh=None) -> None:
    """Mixed-size requests through ``submit``/``flush``: exact path
    against ``model.recommend`` on the same device, then the two-stage
    retriever against the exact answers."""
    from large_scale_recommendation_tpu.serving.engine import ServingEngine

    where = "default partitioner" if mesh is None else f"{mesh!r}"
    log(f"[serve] ServingEngine k=10 over {int(model.V.shape[0])} x "
        f"{int(model.V.shape[1])} catalog rows, requests of "
        f"{list(s.request_sizes)} users, {where}")
    requests = make_requests(s, num_users)
    want = [model.recommend(ids, k=10) for ids in requests]

    def serve(engine):
        t0 = time.perf_counter()
        tickets = [engine.submit(ids) for ids in requests]
        results = engine.flush()
        return ([results[t] for t in tickets],
                time.perf_counter() - t0)

    exact, wall = serve(ServingEngine(model, k=10, mesh=mesh))
    log(f"  exact path: {sum(s.request_sizes)} users in {wall:.2f}s "
        "(compiles included)")
    mismatched = 0
    max_dscore = 0.0
    for (ids, scores), (wids, wscores), req in zip(exact, want, requests):
        check(ids.shape == (len(req), 10) and scores.shape == ids.shape
              and bool(np.isfinite(scores).all()),
              f"request of {len(req)}: [n, 10] ids and finite scores")
        mismatched += int((ids != wids).sum())
        max_dscore = max(max_dscore,
                         float(np.max(np.abs(scores - wscores))))
    log(f"  exact vs model.recommend: {mismatched} differing ids, "
        f"max|d score| {max_dscore:.3e}")
    check(mismatched == 0,
          "exact top-10 ids equal model.recommend for every request")

    fast, wall = serve(ServingEngine(model, k=10, mesh=mesh,
                                     retrieval="two_stage"))
    recall = recall_at_k([ids for ids, _ in fast],
                         [ids for ids, _ in exact])
    log(f"  two-stage path: recall@10 vs exact {recall:.4f} in "
        f"{wall:.2f}s (compiles included)")
    check(all(ids.shape == (len(req), 10)
              and bool(np.isfinite(sc).all())
              for (ids, sc), req in zip(fast, requests)),
          "two-stage answers are [n, 10] with finite scores")
    check(recall >= TWO_STAGE_MIN_RECALL,
          f"two-stage recall@10 >= {TWO_STAGE_MIN_RECALL}")


# -- the mesh leg -------------------------------------------------------------


def memory_stat(devices, field: str) -> list[int] | None:
    """One allocator statistic per device; None where the platform keeps
    none (CPU devices — never the case on the chip, and checked)."""
    gc.collect()
    stats = [d.memory_stats() for d in devices]
    if any(st is None for st in stats):
        return None
    return [int(st[field]) for st in stats]


def recording_partitioner(**kw):
    """A ``Partitioner`` that remembers what the solvers asked it to
    place, so the smoke can inspect the arrays the fit really used — the
    strata never leave ``MeshDSGD._train_segments`` otherwise."""
    from large_scale_recommendation_tpu.parallel.partitioner import (
        Partitioner,
    )

    class Recording(Partitioner):
        def __init__(self, **kw):
            super().__init__(**kw)
            self.placed: list[tuple[tuple, object]] = []

        def place(self, x, *logical):
            out = super().place(x, *logical)
            self.placed.append((logical, out))
            return out

    return Recording(**kw)


def check_sharded(name: str, x, part, logical: tuple) -> None:
    """``x`` has one shard per mesh device, on distinct devices, of the
    shape the rules table gives its logical axes."""
    shards = x.addressable_shards
    devices = {sh.device for sh in shards}
    want = x.sharding.shard_shape(x.shape)
    n = part.mesh.devices.size
    divisor = [1] * x.ndim
    for dim, ax in enumerate(logical):
        phys = None if ax is None else part.physical_axis(ax)
        if phys is not None:
            divisor[dim] = int(part.mesh.shape[phys])
    expect = tuple(d // q for d, q in zip(x.shape, divisor))
    check(len(shards) == n and len(devices) == n
          and all(sh.data.shape == want for sh in shards)
          and want == expect,
          f"{name} {tuple(x.shape)} {logical}: {len(shards)} shards of "
          f"{want} on {len(devices)} distinct devices")


def leg_mesh(s: Sizes, data, holdout, vocab) -> None:
    """The same path on four chips, one process: 4x1 ring, 2x2
    rank-sharded mesh against its 2x1 twin, one MeshALS round, mesh
    serving. Placement is proven on the arrays the fits used."""
    import jax

    from large_scale_recommendation_tpu.core.types import Ratings
    from large_scale_recommendation_tpu.models.als import ALSConfig
    from large_scale_recommendation_tpu.parallel.als_mesh import MeshALS
    from large_scale_recommendation_tpu.parallel.dsgd_mesh import (
        MeshDSGD,
        MeshDSGDConfig,
    )
    from large_scale_recommendation_tpu.parallel.partitioner import (
        Partitioner,
    )

    devices = jax.devices()[:4]
    log(f"[mesh] devices {[str(d) for d in devices]}")

    def mesh_config(iterations, **overrides):
        c = dsgd_config(s, iterations=iterations, **overrides)
        return MeshDSGDConfig(**{
            f.name: getattr(c, f.name)
            for f in dataclasses.fields(MeshDSGDConfig)})

    # -- 4x1: the stratum ring ------------------------------------------------
    part = recording_partitioner(num_devices=4)
    before = memory_stat(devices, "bytes_in_use")
    if devices[0].platform == "tpu":
        check(before is not None, "the chips report allocator statistics")
    t0 = time.perf_counter()
    model = MeshDSGD(mesh_config(s.sweeps), partitioner=part).fit_device(
        *data, *vocab)
    jax.block_until_ready((model.U, model.V))
    wall = time.perf_counter() - t0
    rmse = holdout_rmse(model, model.U, model.V, holdout)
    log(f"  4x1 MeshDSGD.fit_device: {s.sweeps} sweeps, k=4, holdout RMSE "
        f"{rmse:.4f}, fit {wall:.1f}s")
    check(np.isfinite(rmse), "4x1 holdout RMSE is finite")
    check_sharded("U", model.U, part, ("users", "rank"))
    check_sharded("V", model.V, part, ("items", "rank"))
    for logical, arr in part.placed:
        check_sharded("placed", arr, part, logical)
    check(sum(1 for lg, _ in part.placed if lg == ("ratings",)) >= 4,
          "the four strata arrays went through the partitioner")
    if before is not None:
        after = memory_stat(devices, "bytes_in_use")
        added = [a - b for a, b in zip(after, before)]
        log(f"  bytes_in_use per device: {after}; added by the fit "
            f"(tables + strata held): {added}; peak_bytes_in_use: "
            f"{memory_stat(devices, 'peak_bytes_in_use')}")
        check(min(added) > 0
              and max(added) / min(added) <= MEMORY_SPREAD_MAX,
              "the fit's resident bytes are spread over all four devices "
              f"(max/min <= {MEMORY_SPREAD_MAX})")
    part.placed.clear()

    leg_serve(s, model, vocab[0], mesh=part)
    del model

    # -- 2x2: rank-sharded factors (the psum of partial dots) -----------------
    # constant eta 0.1: at k=2 the bench's warm_boost step takes the 2x2
    # mesh AND its 2x1 twin to NaN in one sweep (a four-chip run;
    # single-device k=2 does the same on the CPU, so it is the step size,
    # not the mesh)
    stable = mesh_config(1, learning_rate=0.1, lr_schedule="constant")
    part22 = recording_partitioner(num_devices=4, model_parallel=2)
    m22 = MeshDSGD(stable, partitioner=part22).fit_device(*data, *vocab)
    check_sharded("2x2 U", m22.U, part22, ("users", "rank"))
    check_sharded("2x2 V", m22.V, part22, ("items", "rank"))
    for logical, arr in part22.placed:
        check_sharded("2x2 placed", arr, part22, logical)
    part22.placed.clear()
    m21 = MeshDSGD(stable, partitioner=Partitioner(
        num_devices=2)).fit_device(*data, *vocab)
    r22 = holdout_rmse(m22, m22.U, m22.V, holdout)
    r21 = holdout_rmse(m21, m21.U, m21.V, holdout)
    d = max(float(np.max(np.abs(np.asarray(m22.U) - np.asarray(m21.U)))),
            float(np.max(np.abs(np.asarray(m22.V) - np.asarray(m21.V)))))
    scale = float(np.max(np.abs(np.asarray(m21.U))))
    log(f"  2x2 (model_parallel=2) one sweep: holdout RMSE {r22:.5f}; 2x1 "
        f"twin {r21:.5f}; max|d factors| {d:.3e} (max|U| {scale:.3f})")
    check(np.isfinite(r22) and d <= FACTORS_MAX_REL * scale
          and abs(r22 - r21) <= HOLDOUT_RMSE_MAX_ABS,
          f"2x2 agrees with its 2x1 twin within {FACTORS_MAX_REL} of the "
          "factors' magnitude")
    del m22, m21

    # -- MeshALS: one round at a reduced nnz ----------------------------------
    n = min(s.als_nnz, int(data[0].shape[0]))
    sub = Ratings.from_arrays(*(np.asarray(a[:n]) for a in data))
    part_als = recording_partitioner(num_devices=4)
    t0 = time.perf_counter()
    als = MeshALS(ALSConfig(num_factors=s.rank, lambda_=0.1, iterations=1,
                            seed=0), partitioner=part_als).fit(sub)
    jax.block_until_ready((als.U, als.V))
    als_rmse = als.rmse(sub)
    spread = float(np.std(sub.to_numpy()[2]))
    log(f"  MeshALS one round on {n} ratings: train RMSE {als_rmse:.4f} "
        f"(rating std {spread:.4f}), {time.perf_counter() - t0:.1f}s")
    check_sharded("ALS U", als.U, part_als, ("users", "rank"))
    check_sharded("ALS V", als.V, part_als, ("items", "rank"))
    check(np.isfinite(als_rmse) and als_rmse < spread,
          "MeshALS fitted its ratings better than their mean")


# --------------------------------------------------------------------------


def run(s: Sizes) -> None:
    import jax

    data, holdout, vocab = generate(s)
    model = leg_train(s, data, holdout, vocab)
    leg_serve(s, model, vocab[0])
    del model
    if len(jax.devices()) >= 4:
        leg_mesh(s, data, holdout, vocab)
    else:
        log(f"[mesh] skipped: {len(jax.devices())} device(s), the mesh "
            "leg needs 4 (no virtual devices are substituted)")


def main() -> int:
    t_start = time.perf_counter()
    import jax
    import jaxlib

    from large_scale_recommendation_tpu.obs.introspect import Introspector
    from large_scale_recommendation_tpu.utils.platform import (
        device_summary,
        enable_compilation_cache,
    )

    def cache_entries() -> int:  # jax creates the directory on first write
        return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    cache_dir = enable_compilation_cache()
    device = device_summary()
    entries = cache_entries()
    log(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, libtpu "
        f"{importlib.metadata.version('libtpu')}; platform "
        f"{device['platform']}, device_kind {device['kind']}, device_count "
        f"{device['count']}; compile cache {cache_dir} ({entries} entries)")
    if device["platform"] != "tpu":
        print(f"chip_smoke: no accelerator — JAX's platform is "
              f"{device['platform']!r} ({device['kind']}), and this smoke "
              "runs on a TPU only", file=sys.stderr)
        return 1

    witness = Introspector()
    check(witness.install(), "compile funnel hooked")
    try:
        run(Sizes())
    finally:
        witness.uninstall()
    check(witness.errors == 0, "the compile witness saw every module")
    log(f"compiles {witness.compile_count} taking "
        f"{witness.compile_wall_s:.1f}s (cache hits included); compile "
        f"cache entries added {cache_entries() - entries}; total wall "
        f"{time.perf_counter() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
