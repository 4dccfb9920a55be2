"""ALS's normal-equation solve with the batch along the lanes.

XLA's TPU expansion of ``cholesky`` and ``triangular_solve`` walks a batch
of small matrices one after another: at rank 128 a system cost 14.0 us to
factorize and 10.2 us to substitute in batches of 512 to 4096 alike, 30
GFLOP/s of float32 (PERF.md, Findings, PR 33). Here one system is one
LANE: a tile of 128 systems is laid out ``[k, k, 128]`` (row, column,
system), so every step of the column recurrence is a full-width vector
operation over 128 systems at once and nothing is ever reduced across
lanes.

The factorization is the row-by-row (left-looking) form of the plain
Cholesky recurrence, in float32 throughout. Step ``j`` builds row ``j`` of
``L^T`` (which is column ``j`` of ``L``):

    r      = A[j, c0:] - sum_{m<j} L^T[m, j] * L^T[m, c0:]    (in registers)
    L^T[j] = r * (1 / sqrt(r[j]))

with ``c0 = 8 * (j // 8)``: whole sublane groups, so the entries of a row
left of its diagonal inside the diagonal's group are computed and never
read. The subtractions happen in the order ``m = 0 .. j-1``, the order of
the textbook right-looking form; the row is scaled by the reciprocal of
the pivot as LAPACK's ``potf2`` scales it (one exact float32 division a
row, no approximate reciprocal). The accumulator of a row stays in vector
registers, so the inner loop loads and multiplies and stores nothing:
k^3 / 48 multiply-subtract pairs of one vector register a tile, a third of
what updating the whole symmetric trailing matrix in place would take.
Forward substitution is column oriented on the ``[k, 128]`` right-hand
side, back substitution a masked reduction over rows; ``L`` never leaves
VMEM and only ``x`` goes back to HBM.

A system whose matrix is ``c * I`` with ``b = 0`` solves to exactly 0 (the
padding-row contract of ``ops.als``); the tile's own padding systems are
identity matrices.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128  # systems a tile: one a lane
_GROUP = 8  # rows of a float32 vector register

# What a tile may ask of VMEM (v5e holds 128 MiB; the default scoped limit
# of 16 MiB is raised to what ``lanes_vmem_bytes`` counts plus a margin for
# Mosaic's own temporaries). Rank 128 needs 24.4 MiB, rank 160 38 MiB;
# rank 192 (54.8 MiB) and up stay with XLA.
LANES_VMEM_BUDGET = 48 << 20
_VMEM_MARGIN = 8 << 20


def lanes_vmem_bytes(k: int) -> int:
    """VMEM of one grid step at rank ``k``: the ``[k, k, 128]`` tile of A
    twice (the pipeline's two buffers) and ``L^T`` once; ``b`` and ``x``
    twice each, the pivots' reciprocals, ``y`` and the substitution's
    working vector once."""
    return 4 * LANES * (3 * k * k + 7 * k)


def lanes_fits(k: int) -> bool:
    """Whether the kernel takes rank ``k``: whole sublane groups, and a
    tile inside ``LANES_VMEM_BUDGET``."""
    return k % _GROUP == 0 and lanes_vmem_bytes(k) <= LANES_VMEM_BUDGET


def _kernel(a_ref, b_ref, x_ref, lt_ref, dinv_ref, y_ref, v_ref, *, k: int):
    groups = k // _GROUP

    def row_of(j, c0):
        """Row ``j`` of ``L^T`` from column ``c0`` on: a multiply-subtract
        a finished row, the accumulator carried in registers."""

        def minus(m, r):
            return r - lt_ref[m, pl.ds(j, 1), :] * lt_ref[m, c0:, :]

        def minus_group(g, r):
            m0 = pl.multiple_of(g * _GROUP, _GROUP)
            for t in range(_GROUP):
                r = minus(m0 + t, r)
            return r

        r = jax.lax.fori_loop(0, c0 // _GROUP, minus_group, a_ref[j, c0:, :])
        return jax.lax.fori_loop(c0, j, minus, r)

    for g in range(groups):
        c0 = g * _GROUP

        def factor_row(jj, _, c0=c0):
            j = c0 + jj
            r = row_of(j, c0)
            # the pivot is one sublane of ``r`` at a traced offset: only a
            # ref can be read there
            lt_ref[j, c0:, :] = r
            inv = 1.0 / jnp.sqrt(lt_ref[j, pl.ds(j, 1), :])
            dinv_ref[pl.ds(j, 1), :] = inv
            lt_ref[j, c0:, :] = r * inv
            return 0

        jax.lax.fori_loop(0, _GROUP, factor_row, 0)

    # L y = b, a column of L (a row of L^T) a step; the rows of ``v`` a step
    # has passed hold what nobody reads
    v_ref[...] = b_ref[...]
    for g in range(groups):
        c0 = g * _GROUP

        def forward(jj, _, c0=c0):
            j = c0 + jj
            yj = v_ref[pl.ds(j, 1), :] * dinv_ref[pl.ds(j, 1), :]
            y_ref[pl.ds(j, 1), :] = yj
            v_ref[c0:, :] = v_ref[c0:, :] - yj * lt_ref[j, c0:, :]
            return 0

        jax.lax.fori_loop(0, _GROUP, forward, 0)

    # L^T x = y from the last row up: x[j] = (y[j] - sum_{c>j} L^T[j, c]
    # x[c]) / L[j, j], the sum masked to the rows already solved
    for g in reversed(range(groups)):
        c0 = g * _GROUP
        rows = c0 + jax.lax.broadcasted_iota(jnp.int32, (k - c0, LANES), 0)

        def backward(t, _, c0=c0, rows=rows):
            j = c0 + _GROUP - 1 - t
            dot = jnp.sum(
                jnp.where(rows > j, lt_ref[j, c0:, :] * v_ref[c0:, :], 0.0),
                axis=0, keepdims=True)
            v_ref[pl.ds(j, 1), :] = (
                (y_ref[pl.ds(j, 1), :] - dot) * dinv_ref[pl.ds(j, 1), :])
            return 0

        jax.lax.fori_loop(0, _GROUP, backward, 0)
    x_ref[...] = v_ref[...]


@functools.partial(jax.jit, static_argnames="interpret")
def _solve_tiles(Mt: jax.Array, bt: jax.Array, interpret: bool = False):
    """The kernel over ``Mt: f32[tiles, k, k, 128]``, ``bt: f32[tiles, k,
    128]``. Jitted so that the kernel is traced once a rank and tile count
    and not once a program that solves: tracing it takes 1.6 s on the
    chip's host, a fit has some thirty ``_solve_bucket`` programs, and all
    but a few of them solve four tiles a chunk (PERF.md, Findings, PR
    34)."""
    tiles, k, _, _ = Mt.shape
    vec = pltpu.VMEM((k, LANES), jnp.float32)
    return pl.pallas_call(
        functools.partial(_kernel, k=k),
        out_shape=jax.ShapeDtypeStruct((tiles, k, LANES), jnp.float32),
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec((None, k, k, LANES), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((None, k, LANES), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, k, LANES), lambda i: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((k, k, LANES), jnp.float32),
                        vec, vec, vec],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=lanes_vmem_bytes(k) + _VMEM_MARGIN),
        interpret=interpret,
        name="als_solve_lanes",
    )(Mt, bt)


def solve_lanes(M: jax.Array, b: jax.Array, *,
                interpret: bool = False) -> jax.Array:
    """Solve ``M x = b`` for every symmetric positive definite
    ``M: f32[n, k, k]`` (the ridge already on its diagonal) and
    ``b: f32[n, k]``, by Cholesky factorization and two substitutions.
    ``interpret`` runs the kernel in Pallas's interpreter (tests, on a
    CPU)."""
    n, k, _ = M.shape
    if not lanes_fits(k):
        raise ValueError(f"rank {k} is outside the lanes kernel: "
                         f"{lanes_vmem_bytes(k)} bytes of VMEM a tile "
                         f"against {LANES_VMEM_BUDGET}, or not a multiple "
                         f"of {_GROUP}")
    tiles = -(-n // LANES)
    extra = tiles * LANES - n
    if extra:
        M = jnp.concatenate(
            [M, jnp.broadcast_to(jnp.eye(k, dtype=M.dtype), (extra, k, k))])
        b = jnp.concatenate([b, jnp.zeros((extra, k), b.dtype)])
    # one system a lane, a tile contiguous in HBM
    Mt = M.reshape(tiles, LANES, k, k).transpose(0, 2, 3, 1)
    bt = b.reshape(tiles, LANES, k).transpose(0, 2, 1)
    xt = _solve_tiles(Mt, bt, interpret=interpret)
    return xt.transpose(0, 2, 1).reshape(tiles * LANES, k)[:n]
