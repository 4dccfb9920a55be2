"""Plain reference for the ALS fit cells: alternating least squares with
the weighted-lambda ridge of ALS-WR (Zhou, Wilkinson, Schreiber and Pan,
"Large-scale Parallel Collaborative Filtering for the Netflix Prize", AAIM
2008), in float32 ``jax.numpy`` under ``default_matmul_precision
("highest")``. It imports nothing of the program and takes nothing the
program made: from the COO ratings and the configuration's numbers it does
its own sort by row, its own row blocks, its own initial table and its own
sweeps, and the comparison holds the program's tables against them.

Semantics (what the configuration file fixes). With V fixed, for every user
``u`` with rated set ``O_u`` and ``n_u = |O_u|`` solve

    (sum_{i in O_u} v_i v_i^T + lambda * s_u * I) x_u = sum_{i in O_u} r_ui v_i

with ``s_u = n_u`` under ``reg_mode`` ``"als_wr"`` and ``s_u = 1`` under
``"direct"`` (MLlib ``ALS.train``'s plain ridge); then, with the new U
fixed, the same for every item. That is one sweep. A row never rated stays
zero. The initial V is the seed rule of the program's
``PseudoRandomFactorInitializer``, written out again here: the row of id
``x`` is ``init_scale * uniform(fold_in(PRNGKey(0), x))``, never-rated rows
zero. U has no initial value that matters (the first half-step solves it
from V), so its ``init`` is zero: the norm of U's change after a sweep is
the norm of U.

Layout, none of it the program's: the ratings are sorted by row once per
side (a stable ``lax.sort`` that carries partner and value), so that a
row's ratings are one contiguous run; rows are taken in id order in blocks
of ``B`` rows, ``B`` sized so that a block's ``[B, k, k]`` Gram matrices fit
``_BLOCK_BYTES``. A block's Gram matrices and right-hand sides accumulate
over windows of ``_WINDOW`` ratings a row (``[B, _WINDOW, k]`` rows
gathered a step) until the block's longest run is done; slots past a row's
run weigh 0. No power-of-two classes, no chunk plan, and every shape is the
same whatever the seed. Each block's systems are solved by a Cholesky
factorization and two triangular solves.

Departures from the paper: the data are planted low-rank scores plus noise
(the Prize's ratings are not redistributable), so ``lambda`` is the
configuration's, chosen for scores of standard deviation 0.25 and not for
stars 1-5 (the paper's 0.065 does not carry over); the paper initialises
the item table with the items' mean ratings and small random numbers, this
one with the program's seeded uniform rule, since the comparison needs both
sides to start from the same table.

``fault`` plants the fault the correctness control is read against.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

_BLOCK_BYTES = 256 << 20
_WINDOW = 64


@partial(jax.jit, static_argnames=("rank",))
def init_rows(ids, scale, *, rank):
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        jax.random.PRNGKey(0), ids)
    return scale * jax.vmap(
        lambda k: jax.random.uniform(k, (rank,), dtype=jnp.float32))(keys)


@partial(jax.jit, static_argnames=("num_rows",))
def _sort_by_row(rows, other, vals, *, num_rows):
    """The side's ratings in row order, and per row its count and the start
    of its run."""
    _, other_s, vals_s = jax.lax.sort((rows, other, vals), num_keys=1,
                                      is_stable=True)
    counts = jnp.zeros(num_rows, jnp.int32).at[rows].add(1)
    return other_s, vals_s, counts, jnp.cumsum(counts) - counts


@partial(jax.jit, static_argnames=("fault",))
def half_step(fixed, other_s, vals_s, counts, starts, ridge, *, fault=None):
    """Solve every row of one side against the ``fixed`` table.
    ``fault="half_batch"`` leaves every second rating of each row out of
    both sums."""
    if fault not in (None, "half_batch"):
        raise ValueError(f"reference has no fault {fault!r}")
    n, k = counts.shape[0], fixed.shape[1]
    block = max(1, min(n, _BLOCK_BYTES // (k * k * 4)))
    blocks = -(-n // block)
    pad = blocks * block - n
    lane = jnp.arange(_WINDOW, dtype=jnp.int32)[None, :]
    last = other_s.shape[0] - 1
    eye = jnp.eye(k, dtype=jnp.float32)

    def solve_block(x):
        c, s, rg = x

        def window(j, Ab):
            at = j * _WINDOW + lane  # where in each row's run
            keep = at < c[:, None]
            if fault == "half_batch":
                keep = keep & (at % 2 == 0)
            pos = jnp.minimum(s[:, None] + at, last)
            w = keep.astype(jnp.float32)
            g = fixed[other_s[pos]] * w[..., None]
            return (Ab[0] + jnp.einsum("blk,blm->bkm", g, g),
                    Ab[1] + jnp.einsum("blk,bl->bk", g, vals_s[pos] * w))

        A, b = jax.lax.fori_loop(
            0, -(-jnp.max(c) // _WINDOW), window,
            (jnp.zeros((block, k, k), jnp.float32),
             jnp.zeros((block, k), jnp.float32)))
        chol = jnp.linalg.cholesky(A + rg[:, None, None] * eye)
        y = jax.lax.linalg.triangular_solve(
            chol, b[..., None], left_side=True, lower=True)
        x = jax.lax.linalg.triangular_solve(
            chol, y, left_side=True, lower=True, transpose_a=True)
        return jnp.where(c[:, None] > 0, x[..., 0], 0.0)

    def shaped(a, fill):
        return jnp.pad(a, (0, pad), constant_values=fill).reshape(
            blocks, block)

    with jax.default_matmul_precision("highest"):
        out = jax.lax.map(solve_block, (shaped(counts, 0), shaped(starts, 0),
                                        shaped(ridge, 1.0)))
    return out.reshape(blocks * block, k)[:n]


def _side(rows, other, vals, num_rows, cfg):
    other_s, vals_s, counts, starts = _sort_by_row(rows, other, vals,
                                                   num_rows=num_rows)
    lam = jnp.float32(cfg["lambda"])
    if cfg["reg_mode"] == "als_wr":
        ridge = lam * jnp.maximum(counts, 1).astype(jnp.float32)
    elif cfg["reg_mode"] == "direct":
        ridge = jnp.full(num_rows, lam, jnp.float32)
    else:
        raise ValueError(f"reference has no reg_mode {cfg['reg_mode']!r}")
    solve = partial(half_step, other_s=other_s, vals_s=vals_s, counts=counts,
                    starts=starts, ridge=ridge)
    return solve, counts > 0


def fit(u, i, r, cfg: dict, sweeps: int, *, fault=None):
    """The reference fit: ``sweeps`` sweeps from its own init. Returns the
    initial tables and the tables after each sweep, in ID space (rows are
    ids here), per side the mask of ids seen in training, and its name for
    the result line."""
    nu, ni, k = cfg["num_users"], cfg["num_items"], cfg["num_factors"]
    solve_users, seen_u = _side(u, i, r, nu, cfg)
    solve_items, seen_i = _side(i, u, r, ni, cfg)
    V = init_rows(jnp.arange(ni, dtype=jnp.int32),
                  jnp.float32(cfg["init_scale"]), rank=k)
    V = V * seen_i[:, None]
    out = {"init": (jnp.zeros((nu, k), jnp.float32), V),
           "seen": (seen_u, seen_i), "sweeps": [],
           "notes": {"reference": "als_ref"}}
    for _ in range(sweeps):
        U = solve_users(V, fault=fault)
        V = solve_items(U, fault=fault)
        out["sweeps"].append((U, V))
    return out
