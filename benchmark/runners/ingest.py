"""Runner kind ``ingest``: a replica replays its retained rating log as fast
as it can. The log (``streams.EventLog``, one partition) is written whole
during set-up from planted ratings made from the seed; the program's own
``StreamingDriver`` tails it back to back into ``OnlineMF.partial_fit(
offset=...)`` on tables registered and loaded before the stream starts (a
replica restarted from its model). The measured window is one ``run()`` of
the driver to ``block_until_ready`` on both tables after the last batch.

Set-up, in the order that keeps the device under the tables' size plus a
little: the ratings (made on the device, kept on the host), the log, the
starting tables (made on the device by ``datagen.serving_factors``, kept on
the host, loaded into the registered rows a step at a time), then the first
``warmup_batches`` micro-batches of the log through the same driver, which
compiles everything the window runs. The window continues from there; the
reference follows from the very start.

``correct``: the plain reference (``reference/online_ref.py``) follows the
warm-up and the first ``reference_batches`` micro-batches of the window
from the same starting tables; ``check_rows`` rows a side and the first
``stamp_holdout`` held-out ratings' RMSE, stamped at the ends of window
batches 1 and ``reference_batches`` through the driver's ``on_batch`` hook,
are compared as the fit cells' tables are (``compare.fit_numbers``). The
guarantees are two integers held to 0: ratings written and not applied,
and the distance of ``consumed_offsets[0]`` from the log's head. The
held-out RMSE after the last batch, read once the window has closed, has a
target that catches a stream that diverges (the configuration's
``assumed`` says why nothing finer).

A parameter is the traffic file's unless the configuration (its ``toy``)
overrides it.
"""

from __future__ import annotations

import dataclasses
import gc
import os
import shutil
import time

import numpy as np

from benchmark import compare, datagen, harness

REFERENCE = "online_ref"  # of a configuration that names none
PARTITION = 0
SEAM_SERIES = {"fit/online/prepare": "online_prepare_s",
               "fit/online/source": "online_source_wait_s"}


def param(cell, name: str):
    return cell.config.get(name, cell.traffic.get(name))


def batches_for(cell, seconds: float, trace: bool) -> tuple[int, bool]:
    """``(micro-batches in the window, whether that is fewer than the
    traffic file's)``: the file's count from ``full_at_seconds`` up, the
    proportional share below, in whole 64s and 64 at least; a traced run
    keeps ``traced_batches`` (a trace of the whole window would hold 5,000
    device operations a batch)."""
    full = int(param(cell, "batches"))
    share = seconds / float(param(cell, "full_at_seconds"))
    n = max(64, min(full, int(full * share) // 64 * 64))
    if trace:
        n = min(n, int(param(cell, "traced_batches")))
    return min(n, full), n < full


def nnz_for(train: int, holdout_share: float) -> int:
    """The fewest ratings of which ``datagen.planted_ratings`` leaves at
    least ``train`` for training."""
    nnz = int(train / (1.0 - holdout_share))
    while nnz - int(round(nnz * holdout_share)) < train:
        nnz += 1
    return nnz


@dataclasses.dataclass(frozen=True)
class Bf16SGDUpdater:
    """The low-precision control (``--control bf16``), through the
    program's own updater seam: the plain SGD step formed from rows rounded
    to bfloat16, the nearest precision below the float32 the configuration
    states. Never a field of the configuration."""

    learning_rate: float

    def delta(self, ratings, u, v, *, weights=None, omega_u=None,
              omega_v=None, t=1, pred=None):
        import jax.numpy as jnp

        u = u.astype(jnp.bfloat16).astype(jnp.float32)
        v = v.astype(jnp.bfloat16).astype(jnp.float32)
        e = ratings - jnp.sum(u * v, axis=1)
        if weights is not None:
            e = e * weights
        step = jnp.float32(self.learning_rate) * e[:, None]
        return step * v, step * u


CONTROLS = {"bf16": Bf16SGDUpdater}


def control_updater(control: str | None, cfg: dict):
    if control is None:
        return None
    if control not in CONTROLS:
        raise SystemExit(f"ingest: no control {control!r}, only "
                         f"{sorted(CONTROLS)}")
    return CONTROLS[control](float(cfg["learning_rate"]))


def program():
    """The program's entry points. This runner reads the live tables in
    place (``borrowed()``, below); a program whose tables cannot be read so
    (a parent of PR 35, which also copies both tables a micro-batch) cannot
    run the cell: the run ends here, before any input is made."""
    from large_scale_recommendation_tpu.data.tables import (
        GrowableFactorTable,
    )

    if not hasattr(GrowableFactorTable, "borrowed"):
        raise SystemExit(
            "ingest: this program's GrowableFactorTable has no borrowed(): "
            "the runner cannot read its tables in place")
    from large_scale_recommendation_tpu.models.online import (
        OnlineMF,
        OnlineMFConfig,
    )
    from large_scale_recommendation_tpu.streams import (
        EventLog,
        StreamingDriver,
        StreamingDriverConfig,
    )

    return (OnlineMF, OnlineMFConfig, EventLog, StreamingDriver,
            StreamingDriverConfig)


def stream_dir_for(cell) -> str:
    """Inside the checkout, a directory per process (git-ignored, removed
    when the run ends)."""
    return os.path.join(harness.ROOT, ".bench_stream",
                        f"{cell.name}-{os.getpid()}")


def make_stream(cell, seed: int, n_batches: int):
    """``(u, i, r)`` on the host, ``n_batches`` whole micro-batches in
    arrival order, and the held-out ratings on the device."""
    import jax

    cfg = cell.config
    total = n_batches * int(param(cell, "micro_batch_records"))
    share = float(param(cell, "holdout_share"))
    (u, i, r), hold = datagen.planted_ratings(
        seed, num_users=cfg["num_users"], num_items=cfg["num_items"],
        nnz=nnz_for(total, share), rank=cfg["planted_rank"],
        noise=cfg["noise"], skew_lam=cfg["skew_lam"], holdout_share=share)
    host = tuple(np.asarray(x[:total]) for x in (u, i, r))
    del u, i, r
    jax.block_until_ready(hold)
    return host, hold


def starting_tables(cell, seed: int):
    """The tables the stream starts from, on the host: float32 N(0, 1/rank)
    from the seed, a key other than the planted factors'."""
    cfg = cell.config
    U, V = datagen.serving_factors(
        seed + 1, num_users=cfg["num_users"], num_items=cfg["num_items"],
        rank=cfg["num_factors"])
    return np.asarray(U), np.asarray(V)


def compared_ids(cell, seed: int, stream, warm: int, n_ref: int):
    """The checked ids a side, drawn among those the compared batches (the
    window's first ``n_ref``) touch."""
    mbr = int(param(cell, "micro_batch_records"))
    n = int(param(cell, "check_rows"))
    compared = slice(warm * mbr, (warm + n_ref) * mbr)
    return (check_ids(seed, stream[0][compared], mbr, n),
            check_ids(seed + 1, stream[1][compared], mbr, n))


def check_ids(seed: int, ids: np.ndarray, first: int, n: int) -> np.ndarray:
    """``n`` ids drawn from the seed among those the compared batches touch
    (``ids``: theirs, in arrival order), a quarter of them among the first
    ``first`` (the first compared batch's)."""
    rng = np.random.default_rng(seed)
    early = np.unique(ids[:first])
    late = np.unique(ids)
    n = min(n, late.size)
    a = rng.choice(early, min(n // 4, early.size), replace=False)
    rest = np.setdiff1d(late, a, assume_unique=True)
    b = rng.choice(rest, n - a.size, replace=False)
    return np.sort(np.concatenate([a, b]))


class Stamps:
    """Called by the driver after every micro-batch (its ``on_batch``
    hook). At the ends of the batches in ``at`` (1-based, counted from
    ``arm``) it dispatches one gather of the checked rows and the score of
    the stamp's holdout, from the tables as they stand, borrowed: nothing
    is waited for, nothing is copied."""

    def __init__(self, model, u_rows, i_rows, hold):
        import jax
        import jax.numpy as jnp

        from benchmark.reference.online_ref import holdout_sse

        self.model = model
        self.rows = (jnp.asarray(u_rows, jnp.int32),
                     jnp.asarray(i_rows, jnp.int32))
        self.hold = hold
        self.at: set[int] = set()
        self.count = 0
        self.taken: dict[int, tuple] = {}
        self.times: list[float] = []
        self.gather = jax.jit(lambda U, V, cu, ci: (U[cu], V[ci]))
        self.score = holdout_sse

    def arm(self, at) -> None:
        self.at, self.count, self.taken = set(at), 0, {}
        self.times = [time.perf_counter()]

    def on_batch(self, batch) -> None:
        self.count += 1
        self.times.append(time.perf_counter())
        if self.count in self.at:
            with self.model.users.borrowed() as U, \
                    self.model.items.borrowed() as V:
                self.taken[self.count] = self.gather(U, V, *self.rows) + (
                    self.score(U, V, *self.hold),)


def batch_gaps(times: list) -> dict:
    """The host's pace through the window, from one clock read a
    micro-batch (untraced runs have nothing else): the median and the
    largest gaps between the ends of consecutive batches, in ms, how many
    took over twice the median, and the sum of what the gaps took over it. The host runs ahead of the device
    until the runtime holds it back, so a steady gap is the device's time
    a batch and a long one is a stall of whichever side."""
    gaps = np.diff(np.asarray(times)) * 1e3
    if gaps.size == 0:
        return {}
    order = np.argsort(-gaps)[:5]
    median = float(np.median(gaps))
    return {"p50_ms": median,
            "over_twice_p50": int((gaps > 2 * median).sum()),
            "excess_ms": float(np.maximum(gaps - median, 0.0).sum()),
            "largest": [[int(k) + 1, float(gaps[k])] for k in order]}


def seam_walls(trace_dir: str) -> dict:
    """Wall seconds of each ``SEAM_SERIES`` seam in the capture, by series
    name: the host plane alone is read (``Window.reduce`` reads the file
    for everything else and deletes it)."""
    from jax.profiler import ProfileData

    from benchmark import trace_reduce

    out = {series: [] for series in SEAM_SERIES.values()}
    data = ProfileData.from_file(trace_reduce.find_xplane(trace_dir))
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                series = SEAM_SERIES.get(e.name)
                if series is not None:
                    out[series].append(e.duration_ns / 1e9)
    return out


def reference_side(cell, reference, tables, stream, hold, rows, warm: int,
                   n_ref: int, fault=None):
    """The reference from the starting tables through the warm-up and the
    first ``n_ref`` batches of the window: ``{"init", "sweeps", "seen"}`` as
    ``compare.fit_numbers`` reads them (over the checked rows) and the
    stamps' holdout RMSE."""
    import jax.numpy as jnp

    from benchmark.reference.online_ref import holdout_sse, rmse

    u, i, r = stream
    mbr = int(param(cell, "micro_batch_records"))
    cu, ci = (jnp.asarray(x, jnp.int32) for x in rows)
    taken = {}

    def on_batch(b, U, V):
        if b - warm in (0, 1, n_ref):
            taken[b - warm] = (U[cu], V[ci], holdout_sse(U, V, *hold))

    batches = ((u[a:a + mbr], i[a:a + mbr], r[a:a + mbr])
               for a in range(0, (warm + n_ref) * mbr, mbr))
    U, V = reference.follow(jnp.asarray(tables[0]), jnp.asarray(tables[1]),
                            batches, cell.config, on_batch, fault=fault)
    del U, V
    stamps = sorted({1, n_ref})
    n_hold = int(hold[0].shape[0])
    seen = (jnp.ones(len(rows[0]), bool), jnp.ones(len(rows[1]), bool))
    return ({"init": taken[0][:2], "seen": seen,
             "sweeps": [taken[s][:2] for s in stamps]},
            [rmse(taken[s][2], n_hold) for s in stamps])


def run(cell, seed: int, seconds: float, trace: bool, device: dict,
        control: str | None = None) -> dict:
    import jax

    from benchmark.reference.online_ref import holdout_sse, rmse

    cfg = cell.config
    on_chip = device["platform"] == "tpu"
    (OnlineMF, OnlineMFConfig, EventLog, StreamingDriver,
     StreamingDriverConfig) = program()
    reference = harness.reference_for(cell, REFERENCE)
    updater = control_updater(control, cfg)
    mbr = int(param(cell, "micro_batch_records"))
    warm = int(param(cell, "warmup_batches"))
    batches, cut = batches_for(cell, seconds, trace)
    n_ref = min(int(param(cell, "reference_batches")), batches)
    rank = int(cfg["num_factors"])

    # -- set-up: the ratings and the log
    stream, hold = make_stream(cell, seed, warm + batches)
    u, i, r = stream
    stamp_hold = tuple(x[:int(param(cell, "stamp_holdout"))] for x in hold)
    directory = stream_dir_for(cell)
    shutil.rmtree(directory, ignore_errors=True)
    log = EventLog(os.path.join(directory, "log"),
                   segment_records=int(param(cell, "segment_records")))
    for a in range(0, len(u), 1 << 22):
        log.append_arrays(PARTITION, u[a:a + (1 << 22)],
                          i[a:a + (1 << 22)], r[a:a + (1 << 22)])
    head = log.end_offset(PARTITION)

    try:
        # -- the model: every id registered, the starting tables loaded
        tables = starting_tables(cell, seed)
        model = OnlineMF(OnlineMFConfig(
            num_factors=rank, learning_rate=float(cfg["learning_rate"]),
            minibatch_size=int(cfg["minibatch_size"]),
            collision_mode=cfg["collision_mode"]), updater=updater)
        for table, host in ((model.users, tables[0]),
                            (model.items, tables[1])):
            table.load_rows(table.ensure(np.arange(len(host))), host)
        ids = compared_ids(cell, seed, stream, warm, n_ref)
        rows = (model.users.rows_for(ids[0])[0],
                model.items.rows_for(ids[1])[0])
        stamps = Stamps(model, rows[0], rows[1], stamp_hold)
        driver = StreamingDriver(
            model, log, os.path.join(directory, "ckpt"),
            partition=PARTITION,
            config=StreamingDriverConfig(
                batch_records=mbr, checkpoint_every=None,
                queue_capacity=int(param(cell, "queue_capacity")),
                queue_policy=param(cell, "queue_policy"),
                emit_updates=False),
            on_batch=stamps.on_batch)

        # -- warm-up: the log's first batches, through the same driver
        stamps.arm({1})
        driver.run(max_batches=warm)
        with model.users.borrowed() as U, model.items.borrowed() as V:
            jax.block_until_ready((U, V, stamps.taken))
            float(holdout_sse(U, V, *hold))  # the end's score, compiled
        window = harness.Window(trace, harness.trace_dir_for(cell.name),
                                strict=on_chip)
        applied0 = driver.records_processed
        stamps.arm({1, n_ref})

        # -- the window: the rest of the log, back to back
        with window.measure():
            with window.spans.span("fit/ingest"):
                n_run = driver.run()
                with model.users.borrowed() as U, \
                        model.items.borrowed() as V:
                    jax.block_until_ready((U, V))
        wall = window.wall
        applied = driver.records_processed - applied0
        consumed = model.consumed_offsets.get(PARTITION, 0)
        with model.users.borrowed() as U, model.items.borrowed() as V:
            end_rmse = rmse(holdout_sse(U, V, *hold), int(hold[0].shape[0]))
        peak = harness.memory_peak_bytes()
        series = seam_walls(window.trace_dir) if trace else {}
        reduced = window.reduce()
        n_stamp = int(stamp_hold[0].shape[0])
        taken = {b: (np.asarray(U), np.asarray(V), rmse(sse, n_stamp))
                 for b, (U, V, sse) in stamps.taken.items()}
        table_bytes = (model.users.device_bytes, model.items.device_bytes)
        gaps = batch_gaps(stamps.times)
        del driver, stamps, model
        gc.collect()
    finally:
        log.close()
        shutil.rmtree(directory, ignore_errors=True)

    stamp_rmse = {b: taken[b][2] for b in sorted(taken)}
    target = float(cfg["target_rmse"])
    failed = int(not end_rmse <= target)
    print(f"ingest: {n_run} micro-batches of {mbr}, wall {wall:.3f}s, "
          f"holdout RMSE at batches {stamp_rmse} (the stamps' "
          f"{n_stamp}) and {end_rmse:.5f} at the end (all), target "
          f"{target}", flush=True)

    # -- the comparison, once the window has closed and the peak is read
    t_ref = time.perf_counter()
    ref, ref_rmse = reference_side(cell, reference, tables, stream,
                                   stamp_hold, ids, warm, n_ref)
    import jax.numpy as jnp

    firsts = sorted({1, n_ref})
    prog = [tuple(jnp.asarray(x) for x in taken[b][:2]) for b in firsts]
    numbers = compare.fit_numbers(prog, [taken[b][2] for b in firsts],
                                  ref, ref_rmse)
    print(f"ingest: reference {time.perf_counter() - t_ref:.1f}s, its "
          f"RMSE {[round(x, 6) for x in ref_rmse]}", flush=True)
    numbers["ratings_missing"] = abs(batches * mbr - applied)
    numbers["offset_behind_head"] = abs(head - consumed)
    numbers = {k: v for k, v in numbers.items() if k in cfg["limits"]}
    correct, compared_out = compare.judge(numbers, cfg["limits"])

    values = {"train_ratings_per_s": applied / wall,
              "setup_s": window.setup_s}
    sizes = {"rank": rank, "micro_batch_records": mbr,
             "num_users": cfg["num_users"], "num_items": cfg["num_items"]}
    ctx = {"trace": reduced, "chips": cell.chips, "window_s": wall,
           "peaks": harness.peaks_for(device), "series": series,
           "counters": {"sweeps_done": 1, "batches": n_run,
                        "ratings_applied": applied},
           "sizes": sizes,
           # DSGD's count (benchmark/counts.py::sweep_flops), since it is
           # DSGD's update: 6 x rank FLOP a rating applied
           "sweep_flops": 6 * rank * applied}
    return {"correct": correct and window.compiles.count == 0,
            "compared": compared_out, "attempted": 1,
            "failed": failed if not cut else 0,
            "fatal": ("target not reached" if failed and not cut else None),
            "values": values, "ctx": ctx, "memory_peak_bytes": peak,
            "reduced": reduced,
            "compiles_in_window": window.compiles.count,
            "notes": {"batches": n_run, "window_cut": cut,
                      "ratings_applied": applied, "log_head": head,
                      "consumed_offset": consumed,
                      "stamp_rmse": stamp_rmse, "end_rmse": end_rmse,
                      "reference_rmse": ref_rmse,
                      "table_bytes": table_bytes, "batch_gaps": gaps,
                      "reference": reference.__name__.rsplit(".", 1)[-1]}}
