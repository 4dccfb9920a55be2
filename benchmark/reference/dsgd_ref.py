"""Plain reference for the fit cells: stratified SGD matrix factorization
(Gemulla et al., DSGD) as the configuration states it, in float32
``jax.numpy``. It imports nothing of the program and takes nothing the
program made: from the COO ratings and the configuration's numbers it does
its own blocking, its own initial factors and its own sweeps, and the
comparison holds the program's tables against them.

Semantics (what the configuration file fixes):

- rows: each side's ids are dealt to ``k`` blocks hottest first in
  serpentine order, ties broken by a seeded permutation
  (``fold_in(PRNGKey(solver_seed), 10 | 11)``); block ``b`` holds rows
  ``[b * rpb, (b + 1) * rpb)``;
- strata: rating ``(u, i)`` belongs to stratum ``(iblk - ublk) mod k`` and
  bucket ``(stratum, ublk)``; buckets are padded with weight-0 slots to a
  common multiple of the minibatch; the order inside a bucket is a seeded
  permutation (``fold_in(.., 12)``) under a stable sort by bucket; each
  minibatch is then sorted by item row (stable);
- init: row of id ``x`` is ``init_scale * uniform(fold_in(PRNGKey(0), x))``;
- a sweep visits strata 0..k-1, each as one flat run of minibatches; a
  minibatch gathers its rows, forms ``e = r - <u, v>``, the deltas
  ``-lr * (lambda / omega * x - e * y)``, divides each delta by the weighted
  number of times its row occurs in the minibatch (collision "mean") and
  scatter-adds; ``lr`` is ``2.5 * learning_rate`` for the first two sweeps
  (``warm_boost``), then ``learning_rate``.

``fault`` plants the faults the correctness control is read against.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def rows_per_block(n_ids: int, k: int, multiple: int = 8) -> int:
    rpb = max(-(-n_ids // k), 1)
    return -(-rpb // multiple) * multiple


@partial(jax.jit, static_argnames=("k", "rpb"))
def _deal_rows(key, counts, *, k, rpb):
    n = counts.shape[0]
    perm = jax.random.permutation(key, n)
    order = perm[jnp.argsort(-counts[perm], stable=True)]
    ar = jnp.arange(n, dtype=jnp.int32)
    rnd, pos = ar // k, ar % k
    block = jnp.where(rnd % 2 == 0, pos, k - 1 - pos)
    row_of_id = jnp.zeros(n, jnp.int32).at[order].set(block * rpb + rnd)
    omega = jnp.zeros(k * rpb, jnp.float32).at[row_of_id].set(
        counts.astype(jnp.float32))
    id_of_row = jnp.zeros(k * rpb, jnp.int32).at[row_of_id].set(ar)
    return row_of_id, omega, id_of_row


@partial(jax.jit, static_argnames=("k", "rpb_u", "rpb_v"))
def _bucket(key, u, i, r, row_of_u, row_of_i, *, k, rpb_u, rpb_v):
    urow, irow = row_of_u[u], row_of_i[i]
    ublk, iblk = urow // rpb_u, irow // rpb_v
    flat = (((iblk - ublk) % k) * k + ublk).astype(jnp.int32)
    sizes = jnp.zeros(k * k, jnp.int32).at[flat].add(1)
    perm = jax.random.permutation(key, flat.shape[0])
    order = perm[jnp.argsort(flat[perm], stable=True)]
    return sizes, flat[order], urow[order], irow[order], r[order]


@partial(jax.jit, static_argnames=("k", "bmax", "mb", "sort_side"))
def _lay_out(flat_s, urow_s, irow_s, vals_s, sizes, *, k, bmax, mb,
             sort_side):
    n = flat_s.shape[0]
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              jnp.cumsum(sizes)[:-1]])
    dest = flat_s * bmax + jnp.arange(n, dtype=jnp.int32) - starts[flat_s]
    total = k * k * bmax
    su = jnp.zeros(total, jnp.int32).at[dest].set(urow_s)
    si = jnp.zeros(total, jnp.int32).at[dest].set(irow_s)
    sv = jnp.zeros(total, jnp.float32).at[dest].set(vals_s)
    sw = jnp.zeros(total, jnp.float32).at[dest].set(1.0)
    su, si, sv, sw = (a.reshape(-1, mb) for a in (su, si, sv, sw))
    if sort_side is not None:
        order = jnp.argsort(su if sort_side == "user" else si, axis=-1,
                            stable=True)
        su, si, sv, sw = (jnp.take_along_axis(a, order, axis=-1)
                          for a in (su, si, sv, sw))
    return su, si, sv, sw


def block_layout(u, i, r, *, num_users, num_items, k, minibatch,
                 solver_seed, sort_side):
    """Own blocking of the COO ratings. Returns the minibatch-major layout
    ``su, si, sv, sw`` (each ``[k * k * bmax / minibatch, minibatch]``, in
    the order a sweep visits them), ``omega_u, omega_v``, and per side
    ``row_of_id`` and ``id_of_row``."""
    base = jax.random.PRNGKey(int(solver_seed))
    rpb_u = rows_per_block(num_users, k)
    rpb_v = rows_per_block(num_items, k)
    counts_u = jnp.zeros(num_users, jnp.int32).at[u].add(1)
    counts_v = jnp.zeros(num_items, jnp.int32).at[i].add(1)
    row_of_u, omega_u, id_of_ur = _deal_rows(
        jax.random.fold_in(base, 10), counts_u, k=k, rpb=rpb_u)
    row_of_i, omega_v, id_of_ir = _deal_rows(
        jax.random.fold_in(base, 11), counts_v, k=k, rpb=rpb_v)
    sizes, flat_s, urow_s, irow_s, vals_s = _bucket(
        jax.random.fold_in(base, 12), u, i, r, row_of_u, row_of_i,
        k=k, rpb_u=rpb_u, rpb_v=rpb_v)
    bmax = max(int(np.asarray(sizes).max()), 1)
    bmax = -(-bmax // minibatch) * minibatch
    su, si, sv, sw = _lay_out(flat_s, urow_s, irow_s, vals_s, sizes, k=k,
                              bmax=bmax, mb=minibatch, sort_side=sort_side)
    return {"su": su, "si": si, "sv": sv, "sw": sw,
            "omega_u": omega_u, "omega_v": omega_v,
            "row_of_user": row_of_u, "row_of_item": row_of_i,
            "id_of_user_row": id_of_ur, "id_of_item_row": id_of_ir,
            "bmax": bmax}


@partial(jax.jit, static_argnames=("rank",))
def init_rows(ids, scale, *, rank):
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        jax.random.PRNGKey(0), ids)
    return scale * jax.vmap(
        lambda k: jax.random.uniform(k, (rank,), dtype=jnp.float32))(keys)


@partial(jax.jit, static_argnames=("fault",), donate_argnums=(0, 1))
def sweep(U, V, su, si, sv, sw, omega_u, omega_v, lr, lam, *, fault=None):
    """One sweep over every minibatch in order. ``fault="half_batch"``
    leaves the first half of every minibatch out (the mean over the
    rest)."""
    if fault == "half_batch":
        mb = sw.shape[-1]
        sw = sw * (jnp.arange(mb) >= mb // 2).astype(sw.dtype)[None, :]

    def body(carry, x):
        U, V = carry
        ur, ir, val, w = x
        u, v = U[ur], V[ir]
        e = (val - jnp.sum(u * v, axis=-1)) * w
        ou = jnp.maximum(omega_u[ur], 1.0)
        ov = jnp.maximum(omega_v[ir], 1.0)
        du = -lr * ((lam / ou)[:, None] * u * w[:, None] - e[:, None] * v)
        dv = -lr * ((lam / ov)[:, None] * v * w[:, None] - e[:, None] * u)
        cu = jnp.zeros(U.shape[0], jnp.float32).at[ur].add(w)
        cv = jnp.zeros(V.shape[0], jnp.float32).at[ir].add(w)
        du = du / jnp.maximum(cu[ur], 1.0)[:, None]
        dv = dv / jnp.maximum(cv[ir], 1.0)[:, None]
        return (U.at[ur].add(du), V.at[ir].add(dv)), None

    with jax.default_matmul_precision("highest"):
        (U, V), _ = jax.lax.scan(body, (U, V), (su, si, sv, sw))
    return U, V


def learning_rate(cfg: dict, sweep_index: int) -> np.float32:
    """``sweep_index`` is 1-based."""
    lr = np.float32(cfg["learning_rate"])
    if cfg["lr_schedule"] == "warm_boost":
        return np.float32(2.5) * lr if sweep_index <= 2 else lr
    if cfg["lr_schedule"] == "constant":
        return lr
    raise ValueError(f"reference has no schedule {cfg['lr_schedule']!r}")


@jax.jit
def to_id_space(table, row_of_id):
    return table[row_of_id]


def fit(u, i, r, cfg: dict, sweeps: int, *, fault=None):
    """The reference fit: ``sweeps`` sweeps from its own init. Returns the
    initial tables and the tables after each sweep, all in ID space
    (``[num_users, rank]``, ``[num_items, rank]``), per side the mask of
    ids seen in training, and what it notes for the result line."""
    lay = block_layout(
        u, i, r, num_users=cfg["num_users"], num_items=cfg["num_items"],
        k=cfg["num_blocks"], minibatch=cfg["minibatch_size"],
        solver_seed=cfg["solver_seed"], sort_side=cfg["minibatch_sort"])
    scale = jnp.float32(cfg["init_scale"])
    U = init_rows(lay["id_of_user_row"], scale, rank=cfg["num_factors"])
    V = init_rows(lay["id_of_item_row"], scale, rank=cfg["num_factors"])
    ru, ri = lay["row_of_user"], lay["row_of_item"]
    out = {"init": (to_id_space(U, ru), to_id_space(V, ri)),
           "seen": (lay["omega_u"][ru] > 0, lay["omega_v"][ri] > 0),
           "sweeps": [], "notes": {"bmax": lay["bmax"]}}
    for s in range(1, sweeps + 1):
        U, V = sweep(U, V, lay["su"], lay["si"], lay["sv"], lay["sw"],
                     lay["omega_u"], lay["omega_v"],
                     learning_rate(cfg, s), jnp.float32(cfg["lambda"]),
                     fault=fault)
        out["sweeps"].append((to_id_space(U, ru), to_id_space(V, ri)))
    return out


@jax.jit
def holdout_rmse(U_id, V_id, seen_u, seen_v, hu, hi, hr):
    """Root mean squared error over the holdout ratings whose user and item
    were both seen in training (the others are not predictions)."""
    chunk = 1 << 20
    n = hu.shape[0]
    nc = -(-n // chunk)
    pad = nc * chunk - n
    mask = jnp.pad((seen_u[hu] & seen_v[hi]).astype(jnp.float32), (0, pad))
    hu, hi, hr = (jnp.pad(a, (0, pad)).reshape(nc, chunk)
                  for a in (hu, hi, hr))
    err = jax.lax.map(
        lambda x: x[2] - jnp.sum(U_id[x[0]] * V_id[x[1]], axis=-1),
        (hu, hi, hr)).reshape(-1)
    return jnp.sqrt(jnp.sum(mask * err * err) / jnp.maximum(mask.sum(), 1.0))
