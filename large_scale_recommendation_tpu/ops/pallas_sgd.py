"""Pallas DSGD kernels: VMEM-staged factor slices, double-buffered.

The measured ceiling of the XLA kernel is the per-row HBM gather/scatter:
random 512-byte rows stream at ~5 GB/s effective (~0.6% of HBM peak,
docs/PERF.md "Kernel facts") because every row access is an HBM-latency
round trip. This kernel attacks that ceiling with the one structural fact
the XLA gather cannot exploit: in the DSGD blocked layout each
(stratum, block) visit touches only a CONTIGUOUS row range of U and of V
(``data.blocking`` deals rows block-major — the whole point of the
stratum schedule, DSGDforMF.scala:337-344 ≙ the visit order). So:

    1. DMA the block's U-rows and V-rows HBM→VMEM as two big contiguous
       copies (streams at full HBM bandwidth, not per-row latency);
    2. run every minibatch of the block against the VMEM-resident slices —
       gather, delta, scatter all VMEM-local;
    3. DMA the updated slices back.

Per-sweep HBM traffic drops from ~2 row-latency round trips per rating to
one contiguous read+write of each factor row per block visit plus the COO
stream — at ML-25M shape ~2 GB/sweep, ~100× less latency-bound work than
the measured gather path.

Two in-kernel gather strategies are built (the hardware question is which
one runs faster on v5e — measure, don't argue; scripts/pallas_probe.py).
Both are written against what Mosaic ACTUALLY lowers — verified chip-free
by AOT compilation against a v5e topology (scripts/pallas_aot.py; the
round-4 draft used ``jnp.take`` row-subset gathers and value-level
``dynamic_slice``, and Mosaic rejects both — see docs/PERF.md "Mosaic
lowering verdicts"):

- ``gather="take"``: the same-shape ``dynamic_gather`` trick. Mosaic's
  only vectorized gather is ``take_along_axis`` where input, indices and
  output shapes all MATCH (lax.gather_p lowering rule, jax
  _src/pallas/mosaic/lowering.py — `tpu.dynamic_gather`). A row-subset
  gather ([mb] rows out of [rpb]) is therefore expressed by padding the
  index vector up to the table height, broadcasting it across lanes,
  gathering [rpb, r]→[rpb, r], and statically slicing the first mb rows.
  AOT VERDICT: lowers, but Mosaic's backend rejects it at every realistic
  table height — ``tpu.dynamic_gather`` cannot span vregs along the
  gather dimension ("Multiple source vregs along gather dimension", i.e.
  sublane gathers reach at most 8 rows). Kept for parity testing and for
  future Mosaic versions; NOT the production path.
- ``gather="loop"`` (default): per-entry row copies ref→ref through a
  VMEM scratch, with row numbers read as SCALARS from an SMEM copy of
  the index block (dynamic addressing is only lowerable through Refs,
  never on values). AOT VERDICT: compiles for v5e at the k ≥ 32 ML-25M
  geometries (the historical k=16 point OOM'd this round under the 2×
  stream buffering, docs/MOSAIC_AOT.json) — the production path.

Scatter is a per-entry read-modify-write ``fori_loop`` on the VMEM slice
either way — deltas are first stored to VMEM scratch so every dynamic
index touches a Ref: sequential within the minibatch, so duplicate rows
accumulate EXACTLY like the XLA kernel's ``.at[].add`` (and unlike a
"last write wins" bulk store). Minibatch boundaries see each other's
writes through the VMEM slice, matching ``lax.scan`` semantics in
``ops.sgd``.

Layout: per-entry streams are delivered as FULL [n_mb, mb] arrays (block
== array shape — the only per-minibatch-addressable delivery Mosaic's
(8, 128) block-tiling rule accepts when n_mb > 1); the kernel slices
minibatch g's row itself and relayouts it to an [mb, 1] sublane column so
the delta math is elementwise against the gathered factor rows. The
row-index streams go to SMEM (scalar loop addressing) and, in take mode
only, additionally to VMEM (vectorized gather operand).

The updater math is the λ/ω-regularized SGD rule inlined (the bench
configuration, ``core.updaters.RegularizedSGDUpdater`` with per-row ω
scaling and precomputed collision scales); parity is pinned against
``ops.sgd.sgd_minibatch_update`` in tests/test_pallas_sgd.py (explicit
interpret mode on CPU); the compiled kernels run against the XLA kernel
chip to chip in chip_smoke.py.

VMEM budget: U-slice [rpb_u, r] + V-slice [rpb_v, r] + the [mb, r]
scratch tiles (gathered u, v in loop mode; deltas du, dv always) + the
full stream arrays (6 f32 + in take mode 2 i32, 4 bytes × e each —
DOUBLE-buffered by the Pallas pipeline even at a constant index map,
AOT-measured) must fit ~16 MB; at rank 128 that means k ≥ 32 blocks
for the ML-25M shape (the historical k=16 point OOMs under the 2×
stream buffering — recorded negative, docs/MOSAIC_AOT.json). The flat
row indices ride as single-buffered scalar-prefetch SMEM against v5e's
1.0 MB scoped budget, capping block-visit nnz at ~115K. The wrapper
checks both.

Double-buffered stratum pipeline (ISSUE 6 tentpole, the CuMF_SGD
memory-locality recipe): ``pallas_stratum_sweep`` processes ALL k block
visits of one stratum in a single ``pallas_call`` with grid
``(k, n_mb)``, every operand left in HBM (``pl.ANY``) and moved by
MANUAL ``make_async_copy`` DMAs into two scratch slots — visit p
computes out of slot p%2 while slot (p+1)%2 receives visit p+1's U/V
slices, stream block and row indices, and visit p−1's updated slices
flush back behind the first minibatch of compute. Mosaic's implicit
operand pipeline cannot express this schedule: its block-tiling rule
rejects the per-visit SMEM index blocks outright (``(1, 2e)`` blocks of
a ``[k², 2e]`` array — AOT-measured, docs/MOSAIC_AOT.json) and it
buffers in+out slices separately (4 slice buffers where the manual RMW
slots need 2). Slot parity is compiled out: the whole per-visit body is
emitted once per parity under ``pl.when(p % 2 == par)`` so every
ref access is statically addressed — only the DMA source/destination
offsets are runtime values (the Gemulla diagonal: U block p, V block
(p+s) mod k, driven by the scalar-prefetch stratum id). Within a
stratum every block is row-disjoint in BOTH users and items (the whole
point of the Gemulla schedule), so the overlapped fetches/flushes can
never alias. The serial HBM↔VMEM copy the per-block path pays on every
visit is hidden behind one minibatch of compute (~4 µs of DMA vs
≥50 µs of gather/scatter per 2048-entry minibatch at rank 128).
``dsgd_train_pallas(pipeline=...)`` routes: ``None`` (default)
auto-selects the pipelined kernel whenever the doubled buffers fit the
VMEM/SMEM budgets (the price of overlap: 2× the slice footprint plus
Mosaic's minibatch-scaled vector temporaries — at ML-25M rank 128 the
AOT-calibrated operating points are k=32 at mb ≤ 1024 or k=64 at
mb 2048, f32 or bf16; ``stratum_pipeline_budget``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the budgets both kernels are held to (AOT-calibrated on v5e,
# docs/MOSAIC_AOT.json): 14 MB of modeled VMEM state — the k=16 ML-25M
# geometry modeled at 14.98 MB and still OOM'd the 16 MB VMEM stack —
# and 900 KB of the 1.0 MB scoped SMEM
VMEM_BUDGET_MB = 14
SMEM_BUDGET_KB = 900


def require_mosaic_platform(platform: str, interpret: bool,
                            what: str) -> None:
    """``kernel='pallas'`` compiles through Mosaic, which only a TPU
    runs. Interpretation is the CALLER's explicit choice
    (``pallas_interpret=True`` on the config — tests do): it skips the
    VMEM/SMEM/alignment guards, so a run that slid into it unasked would
    hide every geometry error along with the device."""
    if not interpret and platform != "tpu":
        raise RuntimeError(
            f"{what}: kernel='pallas' needs a TPU (Mosaic) and the "
            f"devices are {platform!r}; pass pallas_interpret=True to "
            "run the Pallas interpreter on purpose")


def validate_pallas_contract(updater, collision: str, has_inv: bool):
    """The ``kernel='pallas'`` routing contract, shared by the
    single-device (models.dsgd) and mesh (parallel.dsgd_mesh) routes so
    they cannot drift: the kernel inlines the λ/ω RegularizedSGDUpdater
    rule and consumes precomputed collision scales."""
    missing = [a for a in ("learning_rate", "lambda_", "schedule")
               if not hasattr(updater, a)]
    if missing or collision != "mean" or not has_inv:
        raise ValueError(
            "kernel='pallas' inlines the λ/ω RegularizedSGDUpdater rule "
            "and the precomputed collision scales; it requires an updater "
            f"with learning_rate/lambda_/schedule (missing: {missing}), "
            "collision_mode='mean' and precompute_collisions=True")


def _gather_rows(tbl_ref, idx_col, mb: int, rank: int):
    """Gather ``mb`` arbitrary rows of a VMEM table via Mosaic's only
    vectorized gather: same-shape ``take_along_axis`` (tpu.dynamic_gather).
    ``idx_col`` is the [mb, 1] int32 row-index column; the index vector is
    padded up to the table height (pad rows re-read row 0 — discarded by
    the static slice below), broadcast across lanes, gathered, and the
    first mb rows kept."""
    x = tbl_ref[...]
    n = x.shape[0]
    if mb > n:  # tiny-table case (tests): pad the TABLE up to mb rows
        x = jnp.concatenate(
            [x, jnp.zeros((mb - n, rank), x.dtype)], axis=0)
        n = mb
    if n > mb:
        idx_col = jnp.concatenate(
            [idx_col, jnp.zeros((n - mb, 1), idx_col.dtype)], axis=0)
    idxb = jnp.broadcast_to(idx_col, (n, rank))
    out = jnp.take_along_axis(x, idxb, axis=0, mode="promise_in_bounds")
    return out[:mb]


def _sweep_kernel(*refs, lam: float, mb: int, rank: int,
                  n_mb: int, gather: str, half: bool):
    """One grid step = one minibatch. u_out/v_out are the VMEM-resident
    block slices, persistent across grid steps (constant index_map).

    Stream delivery (AOT-verified — docs/PERF.md "Mosaic lowering
    verdicts"): per-minibatch blocks like [1, mb] or [mb, 1] violate
    Mosaic's (8, 128) block-tiling requirement whenever n_mb > 1, so every
    stream arrives as a FULL [n_mb, mb] array (block == array shape, which
    the tiling rule exempts) and the kernel slices minibatch g itself — a
    dynamic sublane-start row slice plus a (1, mb)→(mb, 1) relayout, both
    of which Mosaic lowers. urs/irs are the flat SCALAR-PREFETCH copies of
    the row indices (read as ``ref[g·mb + j]``): prefetch operands are
    single-buffered SMEM, where regular SMEM operands are double-buffered
    by the pipeline — 2× the footprint, measured as the SMEM OOM
    that broke the k=16 lowering (docs/MOSAIC_AOT.json). urv/irv are the
    VMEM index copies (vectorized gather operand, take mode only);
    gu/gv/du/dv are [mb, rank] VMEM scratch so every dynamically-indexed
    access goes through a Ref (value-level dynamic_slice has no Mosaic
    lowering rule).

    ``half=True`` (bf16 factor storage, the ALX recipe): u_out/v_out are
    bf16 — the halved HBM↔VMEM DMA is the point — and uw/vw are f32 work
    copies of the slices; every gather/delta/scatter runs against the f32
    work refs so gradient accumulation and duplicate-row semantics stay
    exact, with ONE downcast back into the bf16 outputs on the last grid
    step."""
    it = iter(refs)
    urs_ref, irs_ref = next(it), next(it)  # scalar prefetch (flat [e])
    lr_ref = next(it)  # [1] scalar prefetch — the schedule-evaluated η
    # for this visit (runtime scalar so decaying schedules don't
    # recompile)
    urv_ref, irv_ref = ((next(it), next(it)) if gather == "take"
                        else (None, None))
    (vals_ref, w_ref, icu_ref, icv_ref, ou_ref, ov_ref,
     u_hbm, v_hbm, u_out, v_out) = (next(it) for _ in range(10))
    uw_ref, vw_ref = ((next(it), next(it)) if half else (u_out, v_out))
    gu_ref, gv_ref = ((next(it), next(it)) if gather != "take"
                      else (None, None))
    du_ref, dv_ref, sems = next(it), next(it), next(it)

    g = pl.program_id(0)

    # -- step 0: stage the block's factor slices HBM→VMEM (contiguous;
    # at half width when the tables are bf16), then upcast to the f32
    # work slices --------------------------------------------------------
    @pl.when(g == 0)
    def _stage():
        cu = pltpu.make_async_copy(u_hbm, u_out, sems.at[0])
        cv = pltpu.make_async_copy(v_hbm, v_out, sems.at[1])
        cu.start()
        cv.start()
        cu.wait()
        cv.wait()
        if half:
            uw_ref[...] = u_out[...].astype(jnp.float32)
            vw_ref[...] = v_out[...].astype(jnp.float32)

    def col(ref):  # minibatch g's stream as an [mb, 1] sublane column
        return jnp.reshape(ref[pl.ds(g, 1), :], (mb, 1))

    if gather == "take":
        u = _gather_rows(uw_ref, col(urv_ref), mb, rank)
        v = _gather_rows(vw_ref, col(irv_ref), mb, rank)
    else:  # "loop": per-entry ref→ref row copies, SMEM scalar addressing

        def load_rows(j, _):
            gu_ref[pl.ds(j, 1), :] = uw_ref[pl.ds(urs_ref[g * mb + j], 1), :]
            gv_ref[pl.ds(j, 1), :] = vw_ref[pl.ds(irs_ref[g * mb + j], 1), :]
            return 0

        jax.lax.fori_loop(0, mb, load_rows, 0)
        u = gu_ref[...]
        v = gv_ref[...]

    # -- delta: the λ/ω rule (core.updaters.RegularizedSGDUpdater),
    # vectorized over the minibatch — one fused reduction + elementwise.
    # All per-entry streams become [mb, 1] columns: entry on sublanes, the
    # same axis as the gathered rows, so everything is elementwise -------
    w = col(w_ref)
    e = (col(vals_ref) - jnp.sum(u * v, axis=-1, keepdims=True)) * w
    t_lr = lr_ref[0]
    gu = jnp.maximum(col(ou_ref), 1.0)
    gv = jnp.maximum(col(ov_ref), 1.0)
    du_ref[...] = (t_lr * (e * v - (lam / gu) * u * w)) * col(icu_ref)
    dv_ref[...] = (t_lr * (e * u - (lam / gv) * v * w)) * col(icv_ref)

    # -- scatter: sequential per-entry RMW on the f32 slice — duplicates
    # accumulate exactly like .at[].add ------------------------------------
    def rmw(j, _):
        row_u = urs_ref[g * mb + j]
        uw_ref[pl.ds(row_u, 1), :] += du_ref[pl.ds(j, 1), :]
        row_v = irs_ref[g * mb + j]
        vw_ref[pl.ds(row_v, 1), :] += dv_ref[pl.ds(j, 1), :]
        return 0

    jax.lax.fori_loop(0, mb, rmw, 0)

    if half:  # one downcast into the bf16 outputs, last grid step only
        @pl.when(g == n_mb - 1)
        def _downcast():
            u_out[...] = uw_ref[...].astype(u_out.dtype)
            v_out[...] = vw_ref[...].astype(v_out.dtype)


def pallas_block_sweep(
    U_blk: jax.Array,  # f32|bf16[rpb_u, r] — the block's contiguous U rows
    V_blk: jax.Array,  # f32|bf16[rpb_v, r]
    ur_local: jax.Array,  # int32[E] block-LOCAL user rows
    ir_local: jax.Array,
    vals: jax.Array,  # f32[E]
    w: jax.Array,  # f32[E] (0 = padding no-op)
    icu: jax.Array,  # f32[E] precomputed 1/occurrence collision scales
    icv: jax.Array,
    omega_u: jax.Array,  # f32[rpb_u] per-row ω for the λ/ω rule
    omega_v: jax.Array,
    *,
    lr: float | jax.Array,
    lam: float,
    minibatch: int,
    gather: str = "loop",
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Sweep one rating block with VMEM-resident factor slices.

    Returns the updated (U_blk, V_blk) in the INPUT dtype. f32 tables
    reproduce ``ops.sgd.sgd_block_sweep`` exactly (RegularizedSGDUpdater
    (lr, lam) constant-schedule rule, precomputed collision scales);
    bf16 tables DMA at half width and compute against an f32 VMEM work
    copy — the training half of the ALX bf16-storage/f32-accumulation
    recipe (serving/ALS had it first).
    """
    e = ur_local.shape[0]
    if e % minibatch != 0:
        raise ValueError(f"block nnz {e} not divisible by mb {minibatch}")
    if U_blk.dtype != V_blk.dtype:
        raise ValueError(
            f"U/V dtype mismatch: {U_blk.dtype} vs {V_blk.dtype}")
    if U_blk.dtype not in (jnp.float32, jnp.bfloat16):
        raise ValueError(
            f"factor dtype {U_blk.dtype} unsupported; float32 or bfloat16")
    half = U_blk.dtype == jnp.bfloat16
    fac_bytes = 2 if half else 4
    rank = int(U_blk.shape[-1])
    n_mb = e // minibatch
    rows_uv = int(U_blk.shape[0]) + int(V_blk.shape[0])
    # VMEM budget: resident slices (+ the f32 work copies in bf16 mode)
    # + [mb, rank] scratch tiles + the full stream arrays — which the
    # Pallas pipeline DOUBLE-BUFFERS even at a constant index map (the
    # ×2 below; measured via AOT SMEM accounting, docs/MOSAIC_AOT.json)
    # — + the take-only extras.
    rpb_max = max(int(U_blk.shape[0]), int(V_blk.shape[0]))
    take = gather == "take"
    # take: + 2 idx streams in VMEM + the transient padded [rpb, rank]
    # index/output pair (larger side only — the two gathers are
    # sequential); loop: + 2 gather scratch tiles (du/dv counted always)
    transient = (2 * rpb_max * rank + 2 * e) * 4 if take else 0
    n_scratch = 2 if take else 4
    slices = rows_uv * rank * fac_bytes + (
        rows_uv * rank * 4 if half else 0)
    vmem_mb = (slices + (n_scratch * minibatch * rank + 2 * 6 * e) * 4
               + transient) / 2**20
    if vmem_mb > VMEM_BUDGET_MB and not interpret:
        raise ValueError(
            f"~{vmem_mb:.1f} MB of VMEM-resident state (slices + scratch "
            "tiles + stream arrays"
            + (" + take-gather transients" if gather == "take" else "")
            + ") exceeds the ~16 MB budget; use more blocks (smaller row "
            "slices), a smaller minibatch, a smaller rank, or "
            "gather='loop'")
    # SMEM budget: the row indices ride as SCALAR-PREFETCH operands —
    # single-buffered, unlike regular SMEM operands which the pipeline
    # double-buffers (what broke the k=16 lowering, docs/MOSAIC_AOT.json)
    smem_kb = 2 * e * 4 / 1024
    if smem_kb > SMEM_BUDGET_KB and not interpret:
        raise ValueError(
            f"~{smem_kb:.0f} KB of SMEM-resident row indices (2 × {e} "
            "int32) exceeds the ~1 MB v5e scoped-SMEM budget; use more "
            "blocks (fewer ratings per block visit)")

    # ω gathered host-side per entry would defeat the point; gather the
    # per-ROW omegas inside the kernel instead — they are part of the
    # resident slices' row metadata. (Streamed per-minibatch here: the
    # per-entry gather of ω is fused into the delta math by XLA in the
    # reference kernel too, so streaming it keeps the comparison honest.)
    ou_entry = omega_u[ur_local]
    ov_entry = omega_v[ir_local]

    # Streams are delivered as FULL [n_mb, mb] arrays (block == array —
    # the only per-minibatch-addressable shape Mosaic's block-tiling rule
    # accepts for n_mb > 1; the kernel row-slices minibatch g itself).
    def rows(a, dt):
        return jnp.asarray(a, dt).reshape(n_mb, minibatch)

    fullspec = lambda: pl.BlockSpec((n_mb, minibatch),
                                    lambda g, *_: (0, 0))
    kernel = functools.partial(
        _sweep_kernel, lam=lam, mb=minibatch, rank=rank,
        n_mb=n_mb, gather=gather, half=half)
    ur32 = jnp.asarray(ur_local, jnp.int32)
    ir32 = jnp.asarray(ir_local, jnp.int32)
    # scalar-prefetch operands: flat row indices + the runtime η (a
    # python float stays one compile; a schedule-evaluated traced scalar
    # (dsgd_train_pallas) reuses the SAME compiled kernel across sweeps)
    operands = [ur32.reshape(e), ir32.reshape(e),
                jnp.asarray(lr, jnp.float32).reshape(1)]
    in_specs = []
    if take:  # VMEM index copies: the vectorized gather operand
        in_specs += [fullspec(), fullspec()]
        operands += [rows(ur32, jnp.int32), rows(ir32, jnp.int32)]
    in_specs += [fullspec()] * 6 + [
        pl.BlockSpec(memory_space=pl.ANY),  # U_blk stays in HBM
        pl.BlockSpec(memory_space=pl.ANY),  # V_blk stays in HBM
    ]
    operands += [
        rows(vals, jnp.float32), rows(w, jnp.float32),
        rows(icu, jnp.float32), rows(icv, jnp.float32),
        rows(ou_entry, jnp.float32), rows(ov_entry, jnp.float32),
        U_blk, V_blk,
    ]
    scratch = ([pltpu.VMEM(U_blk.shape, jnp.float32),  # f32 work slices
                pltpu.VMEM(V_blk.shape, jnp.float32)] if half else [])
    scratch += ([] if take else
                [pltpu.VMEM((minibatch, rank), jnp.float32),  # gathered u
                 pltpu.VMEM((minibatch, rank), jnp.float32)])  # gathered v
    scratch += [
        pltpu.VMEM((minibatch, rank), jnp.float32),  # du
        pltpu.VMEM((minibatch, rank), jnp.float32),  # dv
        pltpu.SemaphoreType.DMA((2,)),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_mb,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec(U_blk.shape, lambda g, *_: (0, 0)),  # VMEM,
            pl.BlockSpec(V_blk.shape, lambda g, *_: (0, 0)),  # persistent
        ],
        scratch_shapes=scratch,
    )
    # vma: propagate the mesh axes the inputs vary over, so the kernel
    # composes with shard_map under check_vma (the mesh kernel="pallas"
    # route); outside shard_map this is the empty set
    def out(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    vma=jax.typeof(a).vma)

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[out(U_blk), out(V_blk)],
        interpret=interpret,
    )(*operands)


def _stratum_kernel(*refs, lam: float, mb: int, rank: int, n_mb: int,
                    k: int, half: bool):
    """One grid step = minibatch g of block visit p (grid ``(k, n_mb)``).

    Every operand lives in HBM (``pl.ANY``); the kernel moves bytes with
    MANUAL double-buffered DMAs (the guide's canonical pattern — two
    scratch slots, visit p computes out of slot p%2):

    - at (p, 0): wait slot p%2's fetch (started one visit ago; visit 0
      warm-starts its own), then — in bf16 mode — upcast the slice pair
      into the f32 work refs;
    - at (p, min(1, n_mb−1)): wait visit p−1's flush of the OTHER slot
      (it had minibatch 0 of compute to drain), then start visit p+1's
      fetch into it — U block p+1, V block (p+1+s) mod k, stream block
      and row indices, all sliced from HBM at runtime offsets driven by
      the scalar-prefetch stratum id;
    - at (p, n_mb−1): downcast (bf16) back into the slot pair and start
      its flush VMEM→HBM; the LAST visit also drains it so no DMA
      outlives the kernel.

    Within a stratum every visit is row-disjoint in BOTH tables
    (Gemulla), so overlapped fetches/flushes never alias in HBM; slot
    reuse hazards are exactly the two semaphore waits above.

    Slot parity is static: the whole per-visit body is emitted once per
    parity under ``pl.when(p % 2 == par)``, so every VMEM/SMEM access is
    statically addressed (the same restriction the per-block kernel
    obeys: dynamic addressing only ever through ``pl.ds`` row slices).

    Row indices land in SMEM scratch as the visit's whole [2, e] plane
    (scalar loop addressing, read as ``idx[0|1, g·mb + j]``); the stream
    block in VMEM (vals/w/icu/icv/ωu/ωv stacked on the sublane axis —
    minibatch g of stream c is the dynamic row slice at c·n_mb+g, the
    same relayout the per-block kernel uses).

    ``half=True``: bf16 slot buffers (the halved HBM↔VMEM DMA is the
    point) with ONE f32 work pair uw/vw seeded at g==0 and downcast at
    g==n_mb−1 — gradient accumulation and duplicate-row scatter stay
    exact f32. f32 mode computes in the slot buffers directly."""
    it = iter(refs)
    s_ref, lr_ref = next(it), next(it)  # scalar prefetch
    idx_hbm, str_hbm, u_hbm, v_hbm, u_out, v_out = (next(it)
                                                    for _ in range(6))
    u_bufs = (next(it), next(it))  # per-slot factor slices (store dtype)
    v_bufs = (next(it), next(it))
    s_bufs = (next(it), next(it))  # per-slot stream blocks
    i_bufs = (next(it), next(it))  # per-slot SMEM [2, e] row indices
    uw_ref, vw_ref = ((next(it), next(it)) if half else (None, None))
    gu_ref, gv_ref, du_ref, dv_ref = (next(it) for _ in range(4))
    fetch_sems, flush_sems = next(it), next(it)

    s = s_ref[0]
    p = pl.program_id(0)
    g = pl.program_id(1)
    # the step at which the look-ahead fetch starts: after one minibatch
    # of compute (so visit p−1's flush has had work to hide behind) —
    # except at n_mb == 1, where step 0 is all there is
    ahead_g = min(1, n_mb - 1)

    # Every DMA moves a FULL leading-dim plane of a ≥3-D HBM operand
    # (tables arrive as [k, rpb, r], indices as [k², 2, e], streams as
    # [k², 6·n_mb, mb]): full-plane slices start on tile boundaries for
    # any rpb/e, where row-range slices of a 2-D table (and single-row
    # slices of the [2, e] index plane) are misaligned whenever the
    # offset is not a tile multiple — both Mosaic-rejected, AOT-measured
    # (docs/MOSAIC_AOT.json "Slice shape must be aligned"/"DMA source
    # and target shape mismatch" rounds).
    def fetch(pv, sl):
        """The 4 DMAs that stage visit ``pv`` into slot ``sl``."""
        q = (pv + s) % k
        vrow = s * k + pv
        return (
            pltpu.make_async_copy(u_hbm.at[pv], u_bufs[sl],
                                  fetch_sems.at[sl, 0]),
            pltpu.make_async_copy(v_hbm.at[q], v_bufs[sl],
                                  fetch_sems.at[sl, 1]),
            pltpu.make_async_copy(str_hbm.at[vrow], s_bufs[sl],
                                  fetch_sems.at[sl, 2]),
            pltpu.make_async_copy(idx_hbm.at[vrow], i_bufs[sl],
                                  fetch_sems.at[sl, 3]),
        )

    def flush(pv, sl):
        """The 2 DMAs that write slot ``sl``'s updated slices back to
        visit ``pv``'s HBM planes."""
        q = (pv + s) % k
        return (
            pltpu.make_async_copy(u_bufs[sl], u_out.at[pv],
                                  flush_sems.at[sl, 0]),
            pltpu.make_async_copy(v_bufs[sl], v_out.at[q],
                                  flush_sems.at[sl, 1]),
        )

    for par in (0, 1):

        @pl.when(jax.lax.rem(p, 2) == par)
        def _visit(par=par):
            ub, vb = u_bufs[par], v_bufs[par]
            sb = s_bufs[par]
            idx = i_bufs[par]
            uwr = uw_ref if half else ub
            vwr = vw_ref if half else vb

            @pl.when(g == 0)
            def _arrive():
                @pl.when(p == 0)
                def _warm():  # visit 0 fetches for itself (no overlap)
                    for c in fetch(0, 0):
                        c.start()

                for c in fetch(p, par):
                    c.wait()
                if half:
                    uwr[...] = ub[...].astype(jnp.float32)
                    vwr[...] = vb[...].astype(jnp.float32)

            @pl.when(g == ahead_g)
            def _ahead():
                # slot 1−par is free only once visit p−1's flush drained
                # (it had minibatch 0 of this visit to overlap with)
                @pl.when(p >= 1)
                def _reclaim():
                    for c in flush(p - 1, 1 - par):
                        c.wait()

                @pl.when(p + 1 < k)
                def _prefetch():
                    for c in fetch(p + 1, 1 - par):
                        c.start()

            def col(c):  # stream c, minibatch g, as [mb, 1] column
                return jnp.reshape(sb[pl.ds(c * n_mb + g, 1), :], (mb, 1))

            # -- gather: per-entry ref→ref row copies, SMEM scalars ------
            def load_rows(j, _):
                gu_ref[pl.ds(j, 1), :] = uwr[pl.ds(idx[0, g * mb + j], 1), :]
                gv_ref[pl.ds(j, 1), :] = vwr[pl.ds(idx[1, g * mb + j], 1), :]
                return 0

            jax.lax.fori_loop(0, mb, load_rows, 0)
            u = gu_ref[...]
            v = gv_ref[...]

            # -- delta: the λ/ω rule, identical to _sweep_kernel ---------
            w = col(1)
            err = (col(0) - jnp.sum(u * v, axis=-1, keepdims=True)) * w
            t_lr = lr_ref[0]
            gu = jnp.maximum(col(4), 1.0)
            gv = jnp.maximum(col(5), 1.0)
            du_ref[...] = (t_lr * (err * v - (lam / gu) * u * w)) * col(2)
            dv_ref[...] = (t_lr * (err * u - (lam / gv) * v * w)) * col(3)

            # -- scatter: sequential per-entry RMW — duplicates add ------
            def rmw(j, _):
                uwr[pl.ds(idx[0, g * mb + j], 1), :] += \
                    du_ref[pl.ds(j, 1), :]
                vwr[pl.ds(idx[1, g * mb + j], 1), :] += \
                    dv_ref[pl.ds(j, 1), :]
                return 0

            jax.lax.fori_loop(0, mb, rmw, 0)

            @pl.when(g == n_mb - 1)
            def _depart():
                if half:  # one downcast into the slot pair per visit
                    ub[...] = uwr[...].astype(ub.dtype)
                    vb[...] = vwr[...].astype(vb.dtype)
                for c in flush(p, par):
                    c.start()

                @pl.when(p == k - 1)
                def _drain():  # no DMA may outlive the kernel
                    for c in flush(p, par):
                        c.wait()


def stratum_pipeline_budget(rpb_u: int, rpb_v: int, rank: int, e: int,
                            minibatch: int,
                            fac_bytes: int) -> tuple[float, float]:
    """(vmem_mb, smem_kb) the pipelined stratum kernel needs.

    Manual double buffering: two slots, each holding one U/V slice pair
    (store dtype — the slot is both DMA landing zone and RMW target, so
    there is no separate in/out copy) + one stream block; the row
    indices land in SMEM (two slots × two streams). The f32 work pair
    exists only at fac_bytes == 2."""
    half = fac_bytes == 2
    align = 16 if half else 8

    def pad(n, m):
        return -(-n // m) * m

    rows = pad(rpb_u, align) + pad(rpb_v, align)  # DMA tile alignment
    rows6 = pad(6 * (e // minibatch), 8)          # stream sublanes
    vmem = (2 * rows * rank * fac_bytes          # 2 slot slice pairs
            + (rows * rank * 4 if half else 0)   # f32 work pair
            + 2 * rows6 * minibatch * 4          # 2 slot stream blocks
            + 4 * minibatch * rank * 4           # gu/gv/du/dv tiles
            # Mosaic's live vector temporaries in the delta math,
            # calibrated by AOT bisection: ML-25M k=32 modeled 11.9 MB
            # sans this term yet OOM'd the 16 MB VMEM stack at mb 2048,
            # while mb 1024 (9.9 MB sans) compiled — the overhead scales
            # with the minibatch tile, ~2 live [mb, rank] f32 values in
            # EACH of the two parity-duplicated visit bodies
            + 4 * minibatch * rank * 4)
    smem = 2 * 2 * e * 4                         # 2 slots × [2, e]
    return vmem / 2**20, smem / 1024


def pallas_stratum_sweep(
    U: jax.Array,  # f32|bf16[k·rpb_u, r] — the FULL user table
    V: jax.Array,  # f32|bf16[k·rpb_v, r]
    idx: jax.Array,  # int32[k·k, 2, e] visit-major block-LOCAL rows
    #                  (row s·k+p = visit p of stratum s: [u rows, i rows])
    streams: jax.Array,  # f32[k·k, 6·n_mb, mb] stacked per-entry streams
    #                      (vals, w, icu, icv, ωu, ωv on the sublane axis)
    s: jax.Array | int,  # stratum id (runtime scalar — one compile)
    *,
    lr: float | jax.Array,
    lam: float,
    minibatch: int,
    num_blocks: int,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Sweep ONE stratum — all k row-disjoint block visits — in a single
    pallas_call with double-buffered HBM↔VMEM slice/stream pipelining.

    Semantics ≡ k sequential ``pallas_block_sweep`` calls on the
    stratum's blocks (the per-visit order p = 0..k−1 of
    ``dsgd_train_pallas``); the difference is purely WHEN bytes move:
    visit p+1's operands are in flight while visit p computes. Returns
    the updated full (U, V) in the input dtype — every table row is
    copied through VMEM exactly once per stratum (touched or not),
    which is the contiguous-traffic model ``dsgd_bytes_per_sweep``
    prices; every U block and every V block is visited exactly once per
    stratum, so the outputs are fully written. Loop gather only (the
    take path is dead on current Mosaic).
    """
    k = num_blocks
    rank = int(U.shape[-1])
    if U.dtype != V.dtype:
        raise ValueError(f"U/V dtype mismatch: {U.dtype} vs {V.dtype}")
    if U.dtype not in (jnp.float32, jnp.bfloat16):
        raise ValueError(
            f"factor dtype {U.dtype} unsupported; float32 or bfloat16")
    half = U.dtype == jnp.bfloat16
    fac_bytes = 2 if half else 4
    if int(U.shape[0]) % k or int(V.shape[0]) % k:
        raise ValueError(
            f"table rows ({U.shape[0]}, {V.shape[0]}) must be divisible "
            f"by num_blocks={k}")
    rpb_u = int(U.shape[0]) // k
    rpb_v = int(V.shape[0]) // k
    e = int(idx.shape[-1])
    if e % minibatch != 0:
        raise ValueError(f"visit nnz {e} not divisible by mb {minibatch}")
    n_mb = e // minibatch
    rows6 = -(-6 * n_mb // 8) * 8  # stream sublanes, f32-tile padded
    if tuple(idx.shape) != (k * k, 2, e):
        raise ValueError(f"idx shape {idx.shape} != ({k * k}, 2, {e})")
    if tuple(streams.shape) != (k * k, rows6, minibatch):
        raise ValueError(
            f"streams shape {streams.shape} != "
            f"({k * k}, {rows6}, {minibatch}) — build the operands with "
            "build_stratum_operands")
    # slot buffers are whole VMEM memrefs and the DMA endpoints must
    # match shapes EXACTLY, so the per-block row counts must land on
    # sublane-tile boundaries ((8, 128) f32 / (16, 128) bf16 — Mosaic
    # rounds the scratch memref up otherwise, AOT-measured);
    # dsgd_train_pallas pads the tables before calling
    align = 16 if half else 8
    if (rpb_u % align or rpb_v % align) and not interpret:
        raise ValueError(
            f"rows-per-block ({rpb_u}, {rpb_v}) must be multiples of "
            f"{align} for the {U.dtype} pipelined kernel (DMA tile "
            "alignment) — pad the tables (dsgd_train_pallas does)")
    vmem_mb, smem_kb = stratum_pipeline_budget(
        rpb_u, rpb_v, rank, e, minibatch, fac_bytes)
    if vmem_mb > VMEM_BUDGET_MB and not interpret:
        raise ValueError(
            f"~{vmem_mb:.1f} MB of double-buffered VMEM state (2 slot "
            "slice pairs + 2 slot stream blocks + scratch tiles) exceeds "
            "the ~14 MB pipelined budget; use more blocks, a smaller "
            "minibatch, a smaller rank, or bf16 factors "
            "(factor_dtype='bfloat16')")
    if smem_kb > SMEM_BUDGET_KB and not interpret:
        raise ValueError(
            f"~{smem_kb:.0f} KB of double-buffered SMEM row indices "
            f"(2 slots × 2 × [{e}] int32) exceeds the ~1 MB v5e scoped "
            "budget; use more blocks (fewer ratings per visit)")

    kernel = functools.partial(
        _stratum_kernel, lam=lam, mb=minibatch, rank=rank, n_mb=n_mb,
        k=k, half=half)
    # every operand stays in HBM; the kernel's manual DMAs slice one
    # FULL leading-dim plane per visit (the diagonal rotation: U block
    # p, V block (p+s) mod k, stream/index row s·k+p) — the tables go
    # in as [k, rpb, r] so those planes are tile-aligned for ANY rpb
    # (row-range slices of the 2-D layout are not; AOT-measured).
    # pl.ANY leaves the operand where XLA put it — HBM for these tables;
    # chip_smoke.py runs this kernel compiled at the ML-25M geometry,
    # where tables on the 16 MB VMEM stack could not even allocate
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    store = jnp.bfloat16 if half else jnp.float32
    scratch = [
        pltpu.VMEM((rpb_u, rank), store),  # slot-0/1 factor slices
        pltpu.VMEM((rpb_u, rank), store),
        pltpu.VMEM((rpb_v, rank), store),
        pltpu.VMEM((rpb_v, rank), store),
        pltpu.VMEM((rows6, minibatch), jnp.float32),  # slot streams
        pltpu.VMEM((rows6, minibatch), jnp.float32),
        pltpu.SMEM((2, e), jnp.int32),  # slot row indices (u row 0, i 1)
        pltpu.SMEM((2, e), jnp.int32),
    ]
    scratch += ([pltpu.VMEM((rpb_u, rank), jnp.float32),  # f32 work pair
                 pltpu.VMEM((rpb_v, rank), jnp.float32)] if half else [])
    scratch += [
        pltpu.VMEM((minibatch, rank), jnp.float32),  # gathered u
        pltpu.VMEM((minibatch, rank), jnp.float32),  # gathered v
        pltpu.VMEM((minibatch, rank), jnp.float32),  # du
        pltpu.VMEM((minibatch, rank), jnp.float32),  # dv
        pltpu.SemaphoreType.DMA((2, 4)),  # per-slot fetch semaphores
        pltpu.SemaphoreType.DMA((2, 2)),  # per-slot flush semaphores
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(k, n_mb),
        in_specs=[any_spec] * 4,
        out_specs=[any_spec] * 2,
        scratch_shapes=scratch,
    )
    U3, V3 = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((k, rpb_u, rank), U.dtype),
                   jax.ShapeDtypeStruct((k, rpb_v, rank), V.dtype)],
        interpret=interpret,
    )(jnp.asarray(s, jnp.int32).reshape(1),
      jnp.asarray(lr, jnp.float32).reshape(1),
      idx, streams,
      U.reshape(k, rpb_u, rank), V.reshape(k, rpb_v, rank))
    return U3.reshape(U.shape), V3.reshape(V.shape)


def build_stratum_operands(su, si, sv, sw, icu, icv, omega_u, omega_v,
                           *, num_blocks: int, rpb_u: int, rpb_v: int,
                           minibatch: int):
    """The visit-major operand layout of ``pallas_stratum_sweep`` from
    the standard stratum-major arrays: block-LOCAL clamped row indices
    ``[k², 2e]`` and the stacked per-entry streams ``[k², 6·n_mb, mb]``.
    Built once per jitted training call (outside the stratum scan), so
    per-sweep HBM traffic is exactly the slices + one stream read."""
    k = num_blocks
    b = int(su.shape[-1])
    n_mb = b // minibatch
    p_arr = jnp.arange(k, dtype=jnp.int32)
    q_arr = (p_arr[None, :] + jnp.arange(k, dtype=jnp.int32)[:, None]) % k
    # clamp: weight-0 PADDING entries carry global row 0 → negative local
    # index for blocks p>0; their deltas are zero but a negative dynamic
    # store is unspecified in Mosaic (same rule as dsgd_train_pallas)
    ur_l = jnp.maximum(su - (p_arr * rpb_u)[None, :, None], 0)
    ir_l = jnp.maximum(si - (q_arr * rpb_v)[:, :, None], 0)
    idx = jnp.stack(
        [ur_l.reshape(k * k, b), ir_l.reshape(k * k, b)],
        axis=1).astype(jnp.int32)
    ou_e = jnp.asarray(omega_u, jnp.float32)[su]
    ov_e = jnp.asarray(omega_v, jnp.float32)[si]
    streams = jnp.stack(
        [jnp.asarray(a, jnp.float32) for a in
         (sv, sw, icu, icv, ou_e, ov_e)], axis=2)  # [k, k, 6, b]
    streams = streams.reshape(k * k, 6 * n_mb, minibatch)
    # pad the sublane dim to the f32 tile multiple: the VMEM slot buffer
    # is rounded up to whole (8, 128) tiles as a memref, and a manual
    # DMA needs both endpoint shapes EQUAL (AOT-measured "DMA source and
    # target shape mismatch")
    rows6 = -(-6 * n_mb // 8) * 8
    if rows6 != 6 * n_mb:
        streams = jnp.pad(
            streams, ((0, 0), (0, rows6 - 6 * n_mb), (0, 0)))
    return idx, streams


@functools.partial(jax.jit, static_argnames=("rank", "mb", "rpb_u",
                                             "rpb_v", "e", "sort"))
def _probe_inputs(key, rank: int, mb: int, rpb_u: int, rpb_v: int,
                  e: int, sort: bool):
    """Generate the probe workload ON DEVICE — nothing but a PRNG key
    crosses the host link."""
    from large_scale_recommendation_tpu.data.device_blocking import (
        truncated_exp_ids,
    )

    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    ur = truncated_exp_ids(k1, 2.0, rpb_u, e)
    ir = truncated_exp_ids(k2, 2.0, rpb_v, e)
    if sort:
        ur2 = ur.reshape(-1, mb)
        order = jnp.argsort(ur2, axis=1, stable=True)
        ur = jnp.take_along_axis(ur2, order, axis=1).reshape(-1)
        ir = jnp.take_along_axis(ir.reshape(-1, mb), order,
                                 axis=1).reshape(-1)
    vals = jax.random.normal(k3, (e,), jnp.float32)
    w = jnp.ones(e, jnp.float32)
    U = 0.1 * jax.random.normal(k4, (rpb_u, rank), jnp.float32)
    V = 0.1 * jax.random.normal(k5, (rpb_v, rank), jnp.float32)
    ou = jnp.maximum(
        jnp.zeros(rpb_u, jnp.float32).at[ur].add(1.0), 1.0)
    ov = jnp.maximum(
        jnp.zeros(rpb_v, jnp.float32).at[ir].add(1.0), 1.0)

    def batch_inv(rows, nrows):
        r2 = rows.reshape(-1, mb)
        counts = jax.vmap(
            lambda r: jnp.zeros(nrows, jnp.float32).at[r].add(1.0))(r2)
        inv = 1.0 / jnp.take_along_axis(counts, r2, axis=1)
        return inv.reshape(-1)

    return (ur, ir, vals, w, batch_inv(ur, rpb_u), batch_inv(ir, rpb_v),
            ou, ov, U, V)


def probe_variants(rank: int = 128, mb: int = 2048, rpb_u: int = 5080,
                   rpb_v: int = 1848, nnz: int = 24576, reps: int = 5,
                   seed: int = 0, sort: bool = False,
                   interpret: bool = False,
                   sweeps: int = 1,
                   variants: tuple = ("xla", "pallas_take",
                                      "pallas_loop")) -> dict:
    """Measure the XLA kernel vs both Pallas gather variants on ONE
    realistic (stratum, block) visit on the CURRENT device; returns
    ``{variant: ratings_per_s | "FAILED <err>"}``. Run by
    scripts/pallas_probe.py — a Mosaic lowering
    failure is recorded as a measured negative, not hidden. All inputs
    are generated on device: only the PRNG key crosses the link.
    Defaults model one ML-25M block visit at k=32 — the production
    operating point since the k=16 geometry OOM'd under the pipeline's
    2× stream buffering (docs/MOSAIC_AOT.json). ``interpret=True`` is
    the caller's explicit CPU rehearsal (``require_mosaic_platform``).

    ``sweeps`` repeats the block sweep INSIDE one jitted call
    (fori_loop-carried factors), amortizing the per-call dispatch so the
    number is the kernel's and not the host loop's."""
    import time

    from large_scale_recommendation_tpu.core.updaters import (
        RegularizedSGDUpdater,
        constant_lr,
    )
    from large_scale_recommendation_tpu.ops import sgd as sgd_ops

    if any(v.startswith("pallas") for v in variants):
        require_mosaic_platform(jax.devices()[0].platform, interpret,
                                "probe_variants")
    e = nnz - nnz % mb
    lr, lam = 0.1, 0.1
    (urd, ird, valsd, wd, icud, icvd, oud, ovd, Ud, Vd) = _probe_inputs(
        jax.random.PRNGKey(seed), rank, mb, rpb_u, rpb_v, e, sort)
    jax.block_until_ready(Ud)

    upd = RegularizedSGDUpdater(learning_rate=lr, lambda_=lam,
                                schedule=constant_lr)

    def loop(body):
        return jax.jit(lambda: jax.lax.fori_loop(
            0, sweeps, lambda _, uv: body(*uv), (Ud, Vd)))

    all_variants = {
        "xla": loop(lambda u, v: sgd_ops.sgd_block_sweep(
            u, v, urd, ird, valsd, wd, oud, ovd, upd, 1, mb, "mean",
            icud, icvd)),
        "pallas_take": loop(lambda u, v: pallas_block_sweep(
            u, v, urd, ird, valsd, wd, icud, icvd, oud, ovd,
            lr=lr, lam=lam, minibatch=mb, gather="take",
            interpret=interpret)),
        "pallas_loop": loop(lambda u, v: pallas_block_sweep(
            u, v, urd, ird, valsd, wd, icud, icvd, oud, ovd,
            lr=lr, lam=lam, minibatch=mb, gather="loop",
            interpret=interpret)),
    }
    from large_scale_recommendation_tpu.obs.registry import get_registry
    from large_scale_recommendation_tpu.obs.trace import get_tracer

    obs = get_registry()
    tracer = get_tracer()
    sort_lbl = str(bool(sort)).lower()
    out: dict = {}
    for label in variants:
        fn = all_variants[label]
        try:
            # the warm-up call carries the compile — its span (keyed per
            # variant/shape) labels "compile" in the exported trace, so
            # a Perfetto view separates Mosaic/XLA compile wall from the
            # kernel's steady-state reps
            with tracer.span(f"pallas_probe/{label}",
                             key=("pallas_probe", label, rank, mb, sort),
                             rank=rank, mb=mb) as sp:
                # block HERE, not via sp.out: the null tracer's span
                # drops .out without blocking, and the deferred device
                # error must surface inside this try to be recorded as
                # a FAILED variant (and the timed reps must not overlap
                # a still-running warm-up)
                r = fn()
                jax.block_until_ready(r)
                sp.out = r
        except Exception as ex:
            out[label] = f"FAILED {type(ex).__name__}: {str(ex)[:200]}"
            if obs.enabled:
                obs.counter("pallas_probe_failures_total",
                            variant=label).inc()
            continue
        walls = []
        for _ in range(reps):
            with tracer.span(f"pallas_probe/{label}",
                             key=("pallas_probe", label, rank, mb, sort),
                             rank=rank, mb=mb) as sp:
                t0 = time.perf_counter()
                r = fn()
                jax.block_until_ready(r)
                walls.append(time.perf_counter() - t0)
                sp.out = r
        out[label] = round(e * sweeps / min(walls), 1)
        if obs.enabled:
            obs.gauge("pallas_probe_ratings_per_s", variant=label,
                      rank=rank, sorted=sort_lbl).set(out[label])
            for w in walls:
                obs.histogram("pallas_probe_sweep_s",
                              variant=label).observe(w / sweeps)
    return out


def pallas_route(rpb_u: int, rpb_v: int, rank: int, e: int,
                 minibatch: int, fac_bytes: int, gather: str = "loop",
                 interpret: bool = False) -> str:
    """Which kernel ``dsgd_train_pallas(pipeline=None)`` runs at this
    geometry: ``"stratum_pipeline"`` (``pallas_stratum_sweep``, the
    double-buffered kernel) when the doubled buffers fit the VMEM/SMEM
    budgets, else ``"per_block"`` (``pallas_block_sweep`` per visit).
    The model layer records the answer (``DSGD.kernel_route``) so a run
    says which kernel it was — at the bench's own k=32 / mb 2048 f32
    geometry the model prices 15.9 MB > 14 and the route is per_block."""
    vmem_mb, smem_kb = stratum_pipeline_budget(
        rpb_u, rpb_v, rank, e, minibatch, fac_bytes)
    fits = vmem_mb <= VMEM_BUDGET_MB and smem_kb <= SMEM_BUDGET_KB
    return ("stratum_pipeline" if gather == "loop" and (interpret or fits)
            else "per_block")


@functools.partial(jax.jit, static_argnames=(
    "lr", "lam", "minibatch", "num_blocks", "iterations", "gather",
    "interpret", "schedule", "pipeline"))
def dsgd_train_pallas(
    U: jax.Array,  # f32[k*rpb_u, r]
    V: jax.Array,  # f32[k*rpb_v, r]
    su: jax.Array,  # int32[k, k, b] stratum-major GLOBAL user rows
    si: jax.Array,
    sv: jax.Array,
    sw: jax.Array,
    omega_u: jax.Array,  # f32[k*rpb_u]
    omega_v: jax.Array,
    icu: jax.Array,  # precomputed collision scales [k, k, b]
    icv: jax.Array,
    *,
    lr: float,
    lam: float,
    minibatch: int,
    num_blocks: int,
    iterations: int,
    gather: str = "loop",
    interpret: bool = False,
    schedule=None,
    t0: jax.Array | int = 0,
    pipeline: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Full DSGD training through the VMEM-staged Pallas kernel — the
    drop-in twin of ``ops.sgd.dsgd_train`` (same stratum-major layout from
    ``data.blocking`` / ``data.device_blocking``), so a measured kernel win
    on hardware can be exercised on the WHOLE training loop immediately.

    ``pipeline`` selects the double-buffered stratum kernel
    (``pallas_stratum_sweep``: one pallas_call per stratum, visit p+1's
    slices/streams in flight while visit p computes). ``None`` (default)
    auto-selects it whenever gather == "loop" and the doubled buffers
    fit the VMEM/SMEM budgets, falling back to the sequential per-block
    path otherwise; ``True`` requires it (budget violations raise);
    ``False`` forces the per-block path. Both orders are numerically
    IDENTICAL — pinned by tests — because strata are processed in the
    same p = 0..k−1 visit order; only the copy/compute overlap differs.

    Visit order: for each sweep, strata s = 0..k-1; within a stratum the
    k disjoint blocks run sequentially p = 0..k-1 — the block visits of
    ``dsgd_train``, one for one, for every ``minibatch`` that divides the
    block size — pinned by tests at ``minibatch == b`` and
    ``minibatch < b``.

    ``schedule`` (static, same callables as ``core.updaters``) and ``t0``
    give full LR-schedule parity with the XLA path: the per-sweep η is
    evaluated OUTSIDE the kernel at trace level (t = visit // k² + 1 + t0,
    the ``dsgd_train`` superstep convention) and enters the kernel as a
    runtime SMEM scalar — so a decaying schedule costs zero recompiles.
    ``schedule=None`` keeps the constant-η behavior.

    Each block visit slices the block's contiguous factor-row ranges,
    runs the Pallas sweep against them, and writes them back — under one
    ``lax.scan`` so the whole run is a single XLA computation.
    """
    k = num_blocks
    rank = int(U.shape[-1])
    if int(U.shape[0]) % k or int(V.shape[0]) % k:
        # the blocked layout guarantees divisibility; a hand-built table
        # that misses it would silently misalign every block slice
        raise ValueError(
            f"table rows ({U.shape[0]}, {V.shape[0]}) must be divisible "
            f"by num_blocks={k} — use the data.blocking / "
            "data.device_blocking layouts")
    rpb_u = int(U.shape[0]) // k
    rpb_v = int(V.shape[0]) // k

    if pipeline is None:
        pipeline = pallas_route(
            rpb_u, rpb_v, rank, int(su.shape[-1]), minibatch,
            2 if U.dtype == jnp.bfloat16 else 4, gather,
            interpret) == "stratum_pipeline"
    if pipeline:
        if gather != "loop":
            raise ValueError(
                "pipeline=True supports gather='loop' only (the take "
                "path is dead on current Mosaic)")
        idx, streams = build_stratum_operands(
            su, si, sv, sw, icu, icv, omega_u, omega_v,
            num_blocks=k, rpb_u=rpb_u, rpb_v=rpb_v, minibatch=minibatch)
        # pad each block's rows up to the sublane-tile multiple (8 f32 /
        # 16 bf16): the kernel's DMA endpoints must match the VMEM slot
        # memref exactly, and Mosaic rounds that memref up to whole
        # tiles. Pad rows are streamed through VMEM untouched (local
        # indices never reach them) and stripped after the scan — once
        # per jitted call, not per sweep.
        align = 16 if U.dtype == jnp.bfloat16 else 8
        rpb_u2 = -(-rpb_u // align) * align
        rpb_v2 = -(-rpb_v // align) * align

        def pad_blocks(T, rpb, rpb2):
            if rpb2 == rpb:
                return T
            return jnp.pad(T.reshape(k, rpb, rank),
                           ((0, 0), (0, rpb2 - rpb),
                            (0, 0))).reshape(k * rpb2, rank)

        Up = pad_blocks(U, rpb_u, rpb_u2)
        Vp = pad_blocks(V, rpb_v, rpb_v2)

        def stratum(carry, sv_idx):
            U, V = carry
            s, v_idx = sv_idx[0], sv_idx[1]
            t = v_idx // k + 1 + jnp.asarray(t0, jnp.int32)
            lr_t = (jnp.float32(lr) if schedule is None
                    else schedule(jnp.float32(lr), t))
            U, V = pallas_stratum_sweep(
                U, V, idx, streams, s, lr=lr_t, lam=lam,
                minibatch=minibatch, num_blocks=k, interpret=interpret)
            return (U, V), None

        ss = jnp.tile(jnp.arange(k, dtype=jnp.int32), iterations)
        vs = jnp.arange(iterations * k, dtype=jnp.int32)
        (Up, Vp), _ = jax.lax.scan(
            stratum, (Up, Vp), jnp.stack([ss, vs], axis=1))

        def strip(T, rpb, rpb2):
            if rpb2 == rpb:
                return T
            return T.reshape(k, rpb2, rank)[:, :rpb, :].reshape(
                k * rpb, rank)

        return strip(Up, rpb_u, rpb_u2), strip(Vp, rpb_v, rpb_v2)

    def visit(carry, sp):
        U, V = carry
        s, p, v_idx = sp[0], sp[1], sp[2]
        # superstep convention of dsgd_train: t advances once per SWEEP
        # (k strata × k blocks = k² visits), continuing from t0 on
        # checkpoint segments
        t = v_idx // (k * k) + 1 + jnp.asarray(t0, jnp.int32)
        lr_t = (jnp.float32(lr) if schedule is None
                else schedule(jnp.float32(lr), t))
        q = (p + s) % k
        # clamp: weight-0 PADDING entries carry global row 0, which maps
        # to a NEGATIVE local index for blocks p>0 — their deltas are zero
        # either way, but a negative dynamic store is unspecified in
        # Mosaic (interpret mode clamps; real TPU may corrupt VMEM)
        ur_l = jnp.maximum(su[s, p] - p * rpb_u, 0)
        ir_l = jnp.maximum(si[s, p] - q * rpb_v, 0)
        U_blk = jax.lax.dynamic_slice(U, (p * rpb_u, 0), (rpb_u, rank))
        V_blk = jax.lax.dynamic_slice(V, (q * rpb_v, 0), (rpb_v, rank))
        ou_blk = jax.lax.dynamic_slice(omega_u, (p * rpb_u,), (rpb_u,))
        ov_blk = jax.lax.dynamic_slice(omega_v, (q * rpb_v,), (rpb_v,))
        Ub, Vb = pallas_block_sweep(
            U_blk, V_blk, ur_l, ir_l, sv[s, p], sw[s, p],
            icu[s, p], icv[s, p], ou_blk, ov_blk,
            lr=lr_t, lam=lam, minibatch=minibatch, gather=gather,
            interpret=interpret)
        U = jax.lax.dynamic_update_slice(U, Ub, (p * rpb_u, 0))
        V = jax.lax.dynamic_update_slice(V, Vb, (q * rpb_v, 0))
        return (U, V), None

    ss = jnp.tile(jnp.repeat(jnp.arange(k, dtype=jnp.int32), k), iterations)
    ps = jnp.tile(jnp.tile(jnp.arange(k, dtype=jnp.int32), k), iterations)
    vs = jnp.arange(iterations * k * k, dtype=jnp.int32)
    (U, V), _ = jax.lax.scan(visit, (U, V), jnp.stack([ss, ps, vs], axis=1))
    return U, V
