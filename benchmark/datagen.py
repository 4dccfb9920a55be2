"""Inputs and weights from ``--seed``, made on the device in one jitted call
each. The yardstick's own copies: the program receives only the arrays.

``planted_ratings`` has the semantics of the program's
``synthetic_like_device`` (planted low-rank scores plus noise, ids drawn
from a truncated exponential so low ids are hot, 95/5 split by volume);
``data/device_blocking.py::synthetic_like_device`` is the original and is
listed in PERF.md for a later PR to delete or share."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_CHUNK = 1 << 20


def seed_key(seed: int) -> jax.Array:
    """``--seed`` may pass 2**31: fold it into a key in two 16/31-bit parts."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _skewed_ids(key, lam: float, n_ids: int, size: int):
    u = jax.random.uniform(key, (size,), dtype=jnp.float32)
    u = u * (1.0 - np.exp(-lam))
    v = jnp.floor(-jnp.log1p(-u) / lam * n_ids).astype(jnp.int32)
    return jnp.minimum(v, n_ids - 1)


def _row_dots(Ut, Vt, u, i):
    """<Ut[u], Vt[i]> in chunks, so the gathered rows never take more than
    2 x [chunk, rank]."""
    n = u.shape[0]
    nc = -(-n // _CHUNK)
    pad = nc * _CHUNK - n
    up = jnp.pad(u, (0, pad)).reshape(nc, _CHUNK)
    ip = jnp.pad(i, (0, pad)).reshape(nc, _CHUNK)
    r = jax.lax.map(
        lambda ui: jnp.sum(Ut[ui[0]] * Vt[ui[1]], axis=-1), (up, ip))
    return r.reshape(-1)[:n]


@partial(jax.jit, static_argnames=("num_users", "num_items", "n_train",
                                   "n_hold", "rank", "noise", "skew_lam"))
def _planted(key, *, num_users, num_items, n_train, n_hold, rank, noise,
             skew_lam):
    kf, kt, kh = jax.random.split(key, 3)
    ku, kv = jax.random.split(kf)
    scale = 1.0 / np.sqrt(rank)
    Ut = scale * jax.random.normal(ku, (num_users, rank), jnp.float32)
    Vt = scale * jax.random.normal(kv, (num_items, rank), jnp.float32)

    def batch(k, n):
        k1, k2, k3 = jax.random.split(k, 3)
        u = _skewed_ids(k1, skew_lam, num_users, n)
        i = _skewed_ids(k2, skew_lam, num_items, n)
        r = _row_dots(Ut, Vt, u, i)
        return u, i, r + noise * jax.random.normal(k3, (n,), jnp.float32)

    return batch(kt, n_train), batch(kh, n_hold)


def planted_ratings(seed: int, *, num_users: int, num_items: int, nnz: int,
                    rank: int, noise: float, skew_lam: float,
                    holdout_share: float = 0.05):
    """``((u, i, r), (hu, hi, hr))`` on the device: dense int32 ids,
    float32 values."""
    n_hold = int(round(nnz * holdout_share))
    return _planted(seed_key(seed), num_users=int(num_users),
                    num_items=int(num_items), n_train=int(nnz) - n_hold,
                    n_hold=n_hold, rank=int(rank), noise=float(noise),
                    skew_lam=float(skew_lam))


@partial(jax.jit, static_argnames=("num_users", "num_items", "rank"))
def _factors(key, *, num_users, num_items, rank):
    ku, kv = jax.random.split(key)
    scale = 1.0 / np.sqrt(rank)
    return (scale * jax.random.normal(ku, (num_users, rank), jnp.float32),
            scale * jax.random.normal(kv, (num_items, rank), jnp.float32))


def serving_factors(seed: int, *, num_users: int, num_items: int, rank: int):
    """Random float32 factor tables ``(U, V)``, the type they are served
    in; entries N(0, 1/rank) so a score is about N(0, 1/rank)."""
    return _factors(seed_key(seed), num_users=int(num_users),
                    num_items=int(num_items), rank=int(rank))
