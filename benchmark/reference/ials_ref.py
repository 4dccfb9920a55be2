"""Plain reference for the implicit-feedback fit cells: weighted matrix
factorization by alternating least squares (Hu, Koren and Volinsky,
"Collaborative Filtering for Implicit Feedback Datasets", ICDM 2008), in
float32 ``jax.numpy`` under ``default_matmul_precision("highest")``, and the
paper's quality measure, the expected percentile rank (its eq. 8). It
imports nothing of the program and takes nothing the program made: from the
COO interactions and the configuration's numbers it does its own sort by
row, its own row blocks, its own initial table and its own sweeps, and the
comparison holds the program's tables against them.

Semantics (what the configuration file fixes). An interaction ``(u, i)``
with count ``r_ui > 0`` has preference ``p_ui = 1`` and confidence ``c_ui =
1 + alpha * r_ui``; every pair never observed has preference 0 and
confidence 1. With the item table Y fixed, for every user ``u`` (the paper's
eq. 4, with ``Y^T C^u Y = Y^T Y + Y^T (C^u - I) Y``)

    (Y^T Y + sum_{i in O_u} alpha r_ui y_i y_i^T + lambda I) x_u
        = sum_{i in O_u} (1 + alpha r_ui) y_i

then, with the new X fixed, the same for every item (eq. 5). That is one
sweep. ``Y^T Y`` is computed once a half-step over the whole fixed table. A
row with no interaction solves to zero. The initial Y is the seed rule of
the program's ``PseudoRandomFactorInitializer``, written out again here:
the row of id ``x`` is ``init_scale * uniform(fold_in(PRNGKey(0), x))``,
never-seen rows zero. X has no initial value that matters (the first
half-step solves it from Y), so its ``init`` is zero: the norm of X's
change after a sweep is the norm of X.

Layout, none of it the program's: the interactions are sorted by row once
per side (a stable ``lax.sort`` that carries partner and count), so that a
row's interactions are one contiguous run; rows are taken in id order in
blocks of ``_ROWS``. A block's Gram matrices and right-hand sides accumulate
over windows of ``_WINDOW`` interactions a row (``[_ROWS, _WINDOW, k]`` rows
gathered a step) until the block's longest run is done; slots past a row's
run weigh 0. So a block costs its rows times its longest row: the blocks are
small because rows here run from about 20 interactions to tens of thousands,
and the planted item ids come in the order of their popularity, so that the
rows of a block are about as long as each other (on ids in another order the
reference is slower, not wrong). No power-of-two classes, no chunk plan, and
every shape is the same whatever the seed. Each block's systems are solved
by a Cholesky factorization and two triangular solves.

Departures from the paper: the data are planted (the Taste Profile is not in
the repository), so ``alpha`` and ``lambda`` are the configuration's, chosen
for the planted counts (the paper's alpha is 40 on its own data); the paper
does not say how its tables start, this one starts from the program's
seeded uniform rule, since the comparison needs both sides to start from
the same table; the paper's rank list leaves out programmes watched in the
training period, this one ranks the whole catalog (a user's training items
rank near the top on both sides of the comparison and cost a held-out item
about one place in a thousand); a tie counts half a place, so that a user
whose scores are all equal reads 0.5 and not 0.

``fault`` plants the fault the correctness control is read against.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_ROWS = 512    # rows a block: [_ROWS, k, k] Gram matrices
_WINDOW = 64   # interactions of each row a step: [_ROWS, _WINDOW, k]
_PAIRS = 4096  # held-out pairs a block of the rank: [_PAIRS, num_items]


@partial(jax.jit, static_argnames=("rank",))
def init_rows(ids, scale, *, rank):
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        jax.random.PRNGKey(0), ids)
    return scale * jax.vmap(
        lambda k: jax.random.uniform(k, (rank,), dtype=jnp.float32))(keys)


@partial(jax.jit, static_argnames=("num_rows",))
def _sort_by_row(rows, other, vals, *, num_rows):
    """The side's interactions in row order, and per row its count and the
    start of its run."""
    _, other_s, vals_s = jax.lax.sort((rows, other, vals), num_keys=1,
                                      is_stable=True)
    counts = jnp.zeros(num_rows, jnp.int32).at[rows].add(1)
    # room for a whole window at the last interaction
    return (jnp.pad(other_s, (0, _WINDOW)), jnp.pad(vals_s, (0, _WINDOW)),
            counts, jnp.cumsum(counts) - counts)


@partial(jax.jit, static_argnames=("fault",))
def half_step(fixed, other_s, vals_s, counts, starts, alpha, lam, *,
              fault=None):
    """Solve every row of one side against the ``fixed`` table.
    ``fault="half_batch"`` leaves every second interaction of each row out
    of both sums."""
    if fault not in (None, "half_batch"):
        raise ValueError(f"reference has no fault {fault!r}")
    n, k = counts.shape[0], fixed.shape[1]
    blocks = -(-n // _ROWS)
    pad = blocks * _ROWS - n
    lane = jnp.arange(_WINDOW, dtype=jnp.int32)[None, :]
    eye = jnp.eye(k, dtype=jnp.float32)

    def windows(a, first):
        # one contiguous read a row (a run past its end is weighed 0)
        return jax.vmap(
            lambda f: jax.lax.dynamic_slice(a, (f,), (_WINDOW,)))(first)

    def solve_block(x, shared):
        c, s = x

        def window(j, Ab):
            at = j * _WINDOW + lane  # where in each row's run
            keep = at < c[:, None]
            if fault == "half_batch":
                keep = keep & (at % 2 == 0)
            y = fixed[windows(other_s, s + j * _WINDOW)]
            ar = alpha * windows(vals_s, s + j * _WINDOW)  # c_ui - 1
            conf = jnp.where(keep, 1.0 + ar, 0.0)
            ar = jnp.where(keep, ar, 0.0)
            return (Ab[0] + jnp.einsum("blk,blm->bkm", y * ar[..., None], y),
                    Ab[1] + jnp.einsum("blk,bl->bk", y, conf))

        A, b = jax.lax.fori_loop(
            0, -(-jnp.max(c) // _WINDOW), window,
            (jnp.zeros((_ROWS, k, k), jnp.float32),
             jnp.zeros((_ROWS, k), jnp.float32)))
        chol = jnp.linalg.cholesky(A + shared + lam * eye)
        y = jax.lax.linalg.triangular_solve(
            chol, b[..., None], left_side=True, lower=True)
        x = jax.lax.linalg.triangular_solve(
            chol, y, left_side=True, lower=True, transpose_a=True)
        return jnp.where(c[:, None] > 0, x[..., 0], 0.0)

    def shaped(a):
        return jnp.pad(a, (0, pad)).reshape(blocks, _ROWS)

    with jax.default_matmul_precision("highest"):
        shared = fixed.T @ fixed
        out = jax.lax.map(partial(solve_block, shared=shared),
                          (shaped(counts), shaped(starts)))
    return out.reshape(blocks * _ROWS, k)[:n]


def _side(rows, other, vals, num_rows, cfg):
    other_s, vals_s, counts, starts = _sort_by_row(
        rows, other, vals, num_rows=num_rows)
    solve = partial(half_step, other_s=other_s, vals_s=vals_s,
                    counts=counts, starts=starts,
                    alpha=jnp.float32(cfg["alpha"]),
                    lam=jnp.float32(cfg["lambda"]))
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
           "notes": {"reference": "ials_ref"}}
    for _ in range(sweeps):
        U = solve_users(V, fault=fault)
        V = solve_items(U, fault=fault)
        out["sweeps"].append((U, V))
    return out


@jax.jit
def _rank_sums(U_id, V_id, seen_u, seen_i, hu, hi, hr):
    """Per block of held-out pairs: the sum of ``r_ui * rank_ui`` and the
    sum of ``r_ui``, over the pairs whose user and item were both seen in
    training (the others are not predictions)."""
    n = hu.shape[0]
    nb = -(-n // _PAIRS)
    pad = nb * _PAIRS - n
    w = hr * (seen_u[hu] & seen_i[hi])
    hu, hi, w = (jnp.pad(a, (0, pad)).reshape(nb, _PAIRS)
                 for a in (hu, hi, w))
    places = jnp.float32(V_id.shape[0] - 1)

    def block(x):
        bu, bi, bw = x
        scores = U_id[bu] @ V_id.T
        own = jnp.take_along_axis(scores, bi[:, None], axis=1)
        above = jnp.sum(scores > own, axis=1).astype(jnp.float32)
        ties = jnp.sum(scores == own, axis=1).astype(jnp.float32) - 1.0
        rank = (above + 0.5 * ties) / places
        return jnp.sum(bw * rank), jnp.sum(bw)

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(block, (hu, hi, w))


def expected_percentile_rank(U_id, V_id, seen_u, seen_i, hu, hi, hr) -> float:
    """The paper's eq. 8: ``sum r_ui rank_ui / sum r_ui`` over the held-out
    interactions, ``rank_ui`` the share of the catalog's other items that
    user ``u`` scores above item ``i`` (0 the top of the list, 1 the end;
    0.5 what a random model reads, lower is better). Block sums in float32
    on the device, their totals in float64 on the host."""
    num, den = _rank_sums(U_id, V_id, seen_u, seen_i, hu, hi,
                          jnp.asarray(hr, jnp.float32))
    return float(np.asarray(num, np.float64).sum()
                 / max(np.asarray(den, np.float64).sum(), 1e-30))
