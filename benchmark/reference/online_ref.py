"""Plain float32 reference of the micro-batch online MF the cell
``syn10m1m-r512-online.ingest-replay`` runs. It imports nothing of the
program.

Source: ``OnlineSpark.buildModelWithMap`` (spark-adaptive-recom,
``OnlineSpark.scala:164-232``): every micro-batch of the rating stream is
one pass of SGD over the batch's ratings alone (``iterations = 1``), with
the plain unregularized ``SGDUpdater`` (``FactorUpdater.scala:35-53``),
and the vectors it touched are merged into the model.

One micro-batch, as this rebuild states it (``ops/sgd.py``): the batch's
ratings, in arrival order, are cut into minibatches of ``minibatch_size``;
for each minibatch, with ``u`` / ``i`` the user / item rows of its ratings
``r`` and ``eta`` the learning rate,

    p_j = U[u_j],  q_j = V[i_j]                  (rows as the minibatch found them)
    e_j = r_j - <p_j, q_j>
    du_j = eta * e_j * q_j,   dv_j = eta * e_j * p_j
    collision "mean":  du_j /= #{l : u_l = u_j},  dv_j /= #{l : i_l = i_j}
    U[u_j] += du_j,  V[i_j] += dv_j              (rows hit more than once add up)

so a row that several ratings of one minibatch hit moves by the MEAN of
their steps (``"sum"``: by their sum). Departures from the source, each the
rebuild's own and stated in ``models/online.py``:

- the source passes a micro-batch block by block (a one-iteration DSGD:
  ratings bucketed into user x item blocks, a stratum at a time) and
  applies the ratings of a block one after the other; the rebuild passes it
  minibatch by minibatch in arrival order, every rating of a minibatch
  against the rows as the minibatch found them;
- the source has no rule for colliding rows (its updates are sequential);
  the rebuild averages them;
- float32 throughout (the source computes in doubles).

Everything here is straightforward ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: a Python loop over the
minibatches, one small jitted step a minibatch that takes the tables and
gives them back (donated: a 5 GB table is not copied 8,192 times), no
scan, no kernel, no padding. The row counts come from a comparison of
every rating of the minibatch with every other, not from a scatter.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_CHUNK = 1 << 16  # holdout ratings scored at a time (2 x 128 MB of rows)


@partial(jax.jit, donate_argnums=(0, 1), static_argnames=("collision",))
def _step(U, V, u, i, r, eta, *, collision):
    p, q = U[u], V[i]
    e = r - jnp.sum(p * q, axis=1)
    du = eta * e[:, None] * q
    dv = eta * e[:, None] * p
    if collision == "mean":
        du = du / jnp.sum(u[:, None] == u[None, :], axis=1)[:, None]
        dv = dv / jnp.sum(i[:, None] == i[None, :], axis=1)[:, None]
    elif collision != "sum":
        raise ValueError(f"unknown collision rule {collision!r}")
    return U.at[u].add(du), V.at[i].add(dv)


def follow(U, V, batches, cfg: dict, on_batch=None, fault: str | None = None):
    """Apply ``batches`` (an iterable of ``(u, i, r)`` numpy arrays, the
    micro-batches in log order; ``u`` and ``i`` index the tables' rows) to
    the tables ``U``, ``V``, which are CONSUMED (each step donates them),
    and return them. ``cfg``: ``learning_rate``, ``minibatch_size``,
    ``collision_mode``. ``on_batch(b, U, V)`` is called at the end of
    micro-batch ``b`` (1-based) and must keep nothing of the tables.

    ``fault="half_batch"``: every second minibatch of every micro-batch is
    left out (what the cell's limits are read against)."""
    if fault not in (None, "half_batch"):
        raise ValueError(f"unknown fault {fault!r}")
    mb = int(cfg["minibatch_size"])
    eta = jnp.float32(cfg["learning_rate"])
    collision = cfg["collision_mode"]
    with jax.default_matmul_precision("highest"):
        for b, (u, i, r) in enumerate(batches, start=1):
            for m, a in enumerate(range(0, len(u), mb)):
                if fault == "half_batch" and m % 2:
                    continue
                U, V = _step(U, V, jnp.asarray(u[a:a + mb], jnp.int32),
                             jnp.asarray(i[a:a + mb], jnp.int32),
                             jnp.asarray(r[a:a + mb], jnp.float32), eta,
                             collision=collision)
            if on_batch is not None:
                on_batch(b, U, V)
    return U, V


@jax.jit
def _sse(U, V, hu, hi, hr):
    def chunk(args):
        u, i, r, w = args
        e = r - jnp.sum(U[u] * V[i], axis=1)
        return jnp.sum(w * e * e)

    n = hu.shape[0]
    nc = -(-n // _CHUNK)
    pad = nc * _CHUNK - n

    def cut(x):
        return jnp.pad(x, (0, pad)).reshape(nc, _CHUNK)

    w = jnp.ones(n, jnp.float32)
    return jnp.sum(jax.lax.map(chunk, (cut(hu), cut(hi), cut(hr), cut(w))))


def holdout_sse(U, V, hu, hi, hr):
    """Sum of squared errors of ``<U[hu], V[hi]>`` against ``hr``, a device
    scalar (nothing is waited for), the rows gathered ``_CHUNK`` ratings at
    a time. Both sides of the comparison are scored by this one function."""
    with jax.default_matmul_precision("highest"):
        return _sse(U, V, hu, hi, hr)


def rmse(sse, n: int) -> float:
    return float(np.sqrt(float(sse) / n))
