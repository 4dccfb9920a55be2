"""``dsgd_ref``'s fit for a shape whose blocking ``dsgd_ref`` cannot hold on
one chip: the same semantics (rows, strata, the order inside a bucket, init,
sweeps; ``dsgd_ref.py``'s docstring), in float32 ``jax.numpy``, importing
nothing of the program.

``dsgd_ref._bucket`` holds the ratings, the permutation, its argsort and
four gathered columns at once: at Yahoo! Music's 249.7M training ratings
that is well over 12 GB. Here the order is reached one column at a time:
``perm[argsort(flat[perm], stable)]`` is the order by (bucket, place in
the permutation), and the two are unique together, so each column is
sorted by that pair alone and laid out by contiguous copies of its buckets
before the next one is sorted. It all runs on the last local device, which
the program has left empty by the time the runner calls the reference (the
runner's data and the program's tables lie on the first); the sweeps are
``dsgd_ref.sweep`` over whole tables, at ``highest``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import dsgd_ref as plain


@jax.jit
def _rows(row_of_u, row_of_i, u, i):
    return row_of_u[u], row_of_i[i]


@partial(jax.jit, static_argnames=("k", "rpb_u", "rpb_v"))
def _keys(key, urow, irow, *, k, rpb_u, rpb_v):
    """Each rating's bucket ``(stratum, user block)`` and its place in the
    seeded permutation (the permutation's inverse)."""
    ublk, iblk = urow // rpb_u, irow // rpb_v
    flat = (((iblk - ublk) % k) * k + ublk).astype(jnp.int32)
    perm = jax.random.permutation(key, flat.shape[0])
    return flat, jnp.argsort(perm).astype(jnp.int32)


@partial(jax.jit, static_argnames=("kk",))
def _sizes(flat, place, *, kk):
    flat_s = jax.lax.sort((flat, place), num_keys=2)[0]
    ends = jnp.searchsorted(flat_s, jnp.arange(1, kk + 1, dtype=jnp.int32))
    return jnp.diff(ends, prepend=0)


@jax.jit
def _sorted_by(flat, place, col):
    return jax.lax.sort((flat, place, col), num_keys=2)[2]


@partial(jax.jit, static_argnames=("sizes", "bmax"))
def _laid(col_s, *, sizes, bmax):
    """Bucket after bucket, each padded with zeros to ``bmax`` slots."""
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]]).tolist()
    return jnp.concatenate([jnp.pad(col_s[a:a + s], (0, bmax - s))
                            for a, s in zip(starts, sizes)])


@partial(jax.jit, static_argnames=("sizes", "bmax"))
def _weights(*, sizes, bmax):
    slot = jnp.arange(bmax)
    return jnp.concatenate([(slot < s).astype(jnp.float32) for s in sizes])


@partial(jax.jit, static_argnames=("mb", "sort_side"))
def _minibatch_sorted(su, si, sv, sw, *, mb, sort_side):
    """Each minibatch sorted by one side's row, stable (``dsgd_ref``'s
    ``argsort(..., stable=True)`` and ``take_along_axis``)."""
    su, si, sv, sw = (a.reshape(-1, mb) for a in (su, si, sv, sw))
    if sort_side == "user":
        su, si, sv, sw = jax.lax.sort((su, si, sv, sw), dimension=-1,
                                      num_keys=1, is_stable=True)
    elif sort_side == "item":
        si, su, sv, sw = jax.lax.sort((si, su, sv, sw), dimension=-1,
                                      num_keys=1, is_stable=True)
    return su, si, sv, sw


def block_layout(data: list, *, num_users, num_items, k, minibatch,
                 solver_seed, sort_side):
    """``dsgd_ref.block_layout``'s result. ``data`` is ``[u, i, r]``; it is
    emptied as the columns are used, so that what the caller passed is let
    go of as early as it can be."""
    u, i = data[0], data[1]
    base = jax.random.PRNGKey(int(solver_seed))
    rpb_u = plain.rows_per_block(num_users, k)
    rpb_v = plain.rows_per_block(num_items, k)
    counts_u = jnp.zeros(num_users, jnp.int32).at[u].add(1)
    counts_v = jnp.zeros(num_items, jnp.int32).at[i].add(1)
    row_of_u, omega_u, id_of_ur = plain._deal_rows(
        jax.random.fold_in(base, 10), counts_u, k=k, rpb=rpb_u)
    row_of_i, omega_v, id_of_ir = plain._deal_rows(
        jax.random.fold_in(base, 11), counts_v, k=k, rpb=rpb_v)
    urow, irow = _rows(row_of_u, row_of_i, u, i)
    del u, i, data[:2]
    flat, place = _keys(jax.random.fold_in(base, 12), urow, irow, k=k,
                        rpb_u=rpb_u, rpb_v=rpb_v)
    sizes = tuple(int(s) for s in np.asarray(_sizes(flat, place,
                                                    kk=k * k)))
    bmax = max(max(sizes), 1)
    bmax = -(-bmax // minibatch) * minibatch
    cols = [urow, irow, data.pop()]
    del urow, irow
    laid = []
    while cols:
        laid.append(_laid(_sorted_by(flat, place, cols.pop(0)),
                          sizes=sizes, bmax=bmax))
    del flat, place
    laid.append(_weights(sizes=sizes, bmax=bmax))
    su, si, sv, sw = _minibatch_sorted(*laid, mb=minibatch,
                                       sort_side=sort_side)
    return {"su": su, "si": si, "sv": sv, "sw": sw,
            "omega_u": omega_u, "omega_v": omega_v,
            "row_of_user": row_of_u, "row_of_item": row_of_i,
            "id_of_user_row": id_of_ur, "id_of_item_row": id_of_ir,
            "bmax": bmax}


def fit(u, i, r, cfg: dict, sweeps: int, *, fault=None):
    """``dsgd_ref.fit`` on the last local device; what it returns is put
    where ``u`` lies, for the comparison."""
    home = next(iter(u.devices())) if isinstance(u, jax.Array) else None
    dev = jax.local_devices()[-1]
    lay = block_layout(
        [jax.device_put(x, dev) for x in (u, i, r)],
        num_users=cfg["num_users"], num_items=cfg["num_items"],
        k=cfg["num_blocks"], minibatch=cfg["minibatch_size"],
        solver_seed=cfg["solver_seed"], sort_side=cfg["minibatch_sort"])
    scale = jnp.float32(cfg["init_scale"])
    U = plain.init_rows(lay["id_of_user_row"], scale, rank=cfg["num_factors"])
    V = plain.init_rows(lay["id_of_item_row"], scale, rank=cfg["num_factors"])
    ru, ri = lay["row_of_user"], lay["row_of_item"]

    def back(x):
        return x if home is None else jax.device_put(x, home)

    out = {"init": (back(plain.to_id_space(U, ru)),
                    back(plain.to_id_space(V, ri))),
           "seen": (back(lay["omega_u"][ru] > 0),
                    back(lay["omega_v"][ri] > 0)),
           "sweeps": [], "notes": {"bmax": lay["bmax"]}}
    for s in range(1, sweeps + 1):
        U, V = plain.sweep(U, V, lay["su"], lay["si"], lay["sv"], lay["sw"],
                           lay["omega_u"], lay["omega_v"],
                           plain.learning_rate(cfg, s),
                           jnp.float32(cfg["lambda"]), fault=fault)
        out["sweeps"].append((back(plain.to_id_space(U, ru)),
                              back(plain.to_id_space(V, ri))))
    return out
