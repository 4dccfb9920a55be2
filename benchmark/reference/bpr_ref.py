"""Plain reference for the BPR fit cells: Bayesian Personalized Ranking
(Rendle, Freudenthaler, Gantner and Schmidt-Thieme, UAI 2009) by stratified
SGD on minibatches, in float32 ``jax.numpy`` under
``default_matmul_precision("highest")``, and the expected percentile rank
of held-out interactions. It imports nothing of the program and takes
nothing the program made: from the COO interactions and the
configuration's numbers it does its own blocking, its own initial tables
and its own sweeps, and the comparison holds the program's tables against
them.

Semantics (what the configuration file fixes):

- blocking, strata, the order of a sweep and the initial tables are
  ``dsgd_ref``'s (its ``block_layout`` and ``init_rows``); the values of
  the entries are not read: every entry is a positive;
- negatives: for minibatch ``m`` of block ``p`` in stratum ``s`` at sweep
  ``t`` (1-based), with ``q = (p + s) mod k`` the item block it visits,
  ``j = seen_q[randint(key, (minibatch,), 0, n_q)]``, where ``key`` is
  ``fold_in(PRNGKey(solver_seed), 13)`` folded with ``t``, ``s``, ``p``
  and ``m`` in that order, and ``seen_q`` the ``n_q`` rows of block ``q``
  that hold an item seen in training, in row order (this file's own list:
  it holds wherever the blocking puts them in their block);
- a minibatch gathers ``u``, ``v_i``, ``v_j`` from the whole tables, forms
  ``x = <u, v_i - v_j>`` and ``g = sigmoid(-x)``, and the deltas
  ``lr w (g (v_i - v_j) - lambda u)``, ``lr w (g u - lambda v_i)`` and
  ``lr w (-g u - lambda v_j)``; each delta is divided by the weighted
  number of times its row occurs in the minibatch (on the item side as a
  positive or as a negative: collision "mean") and scatter-added, the item
  side's two as one scatter of the positives then the negatives; ``lr`` is
  ``dsgd_ref.learning_rate``'s.

Departures from the paper, as the program has them: negatives are drawn
from the visited item block, not from the whole catalog (a block's items
are a random k-th of the catalog by popularity, and every block is visited
once a sweep); a negative that is a positive of the user is not rejected
(about 3e-5 of the draws at the Million Playlist shape); one lambda for
the three factor kinds where the paper gives each its own; minibatches
where LearnBPR takes one triple at a time.

The rank is the one ``ials_ref`` computes (Hu, Koren and Volinsky, eq. 8;
a tie half a place), over the held-out pairs of the users whose id is a
multiple of ``RANKED_EVERY`` (at 1,000,000 playlists the 10,000 of the
Million Playlist challenge's own test set; all of them cost 1.9e15 FLOP an
evaluation) and against the items seen in training alone: an item the fit
never saw has no row the program would serve.

``fault`` plants the fault the correctness control is read against.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import dsgd_ref

RANKED_EVERY = 100  # users ranked: ids that are multiples of it
_PAIRS = 128  # held-out pairs a block of the rank: [_PAIRS, num_items]
FAULTS = ("no_negative_step",)


def seen_rows(omega, k: int):
    """The rows of each of the ``k`` blocks whose id was seen in training:
    ``int32[k, rows a block]``, block ``q``'s seen rows first in row
    order, then its others, and ``int32[k]`` the count of seen ones."""
    seen = (omega > 0).reshape(k, -1)
    rpb = seen.shape[1]
    order = jnp.argsort(~seen, axis=1, stable=True).astype(jnp.int32)
    return (order + (jnp.arange(k, dtype=jnp.int32) * rpb)[:, None],
            jnp.sum(seen, axis=1, dtype=jnp.int32))


def negatives(seen, n_seen, key, q, size: int):
    """``size`` rows drawn uniformly from the seen rows of block ``q``."""
    pick = jax.random.randint(key, (size,), 0, n_seen[q], dtype=jnp.int32)
    return seen[q][pick]


@partial(jax.jit, static_argnames=("k", "per_bucket", "fault"),
         donate_argnums=(0, 1))
def sweep(U, V, su, si, sw, seen, n_seen, key, t, lr, lam, *, k, per_bucket,
          fault=None):
    """One sweep over every minibatch in order (``su``, ``si``, ``sw``:
    ``[minibatches, minibatch]``, ``per_bucket`` minibatches a bucket;
    ``seen``, ``n_seen``: ``seen_rows``). ``fault="no_negative_step"``
    leaves ``-g u`` out of the negative's delta (its ridge term stays)."""
    n = su.shape[0]
    g = jnp.arange(n, dtype=jnp.int32)
    bucket = g // per_bucket
    s, p, m = bucket // k, bucket % k, g % per_bucket

    def body(carry, x):
        U, V = carry
        ur, ir, w, s, p, m = x
        q = (p + s) % k
        kk = key
        for part in (t, s, p, m):
            kk = jax.random.fold_in(kk, part)
        jr = negatives(seen, n_seen, kk, q, ur.shape[0])
        u, vi, vj = U[ur], V[ir], V[jr]
        d = vi - vj
        sig = jax.nn.sigmoid(-jnp.sum(u * d, axis=-1))[:, None]
        lw = (lr * w)[:, None]
        du = lw * (sig * d - lam * u)
        dvi = lw * (sig * u - lam * vi)
        push = 0.0 if fault == "no_negative_step" else -sig * u
        dvj = lw * (push - lam * vj)
        rows = jnp.concatenate([ir, jr])
        cu = jnp.zeros(U.shape[0], jnp.float32).at[ur].add(w)
        cv = jnp.zeros(V.shape[0], jnp.float32).at[rows].add(
            jnp.concatenate([w, w]))
        du = du / jnp.maximum(cu[ur], 1.0)[:, None]
        dv = (jnp.concatenate([dvi, dvj])
              / jnp.maximum(cv[rows], 1.0)[:, None])
        return (U.at[ur].add(du), V.at[rows].add(dv)), None

    with jax.default_matmul_precision("highest"):
        (U, V), _ = jax.lax.scan(body, (U, V), (su, si, sw, s, p, m))
    return U, V


def fit(u, i, r, cfg: dict, sweeps: int, *, fault=None):
    """The reference fit: ``sweeps`` sweeps from its own init. Returns the
    initial tables and the tables after each sweep, all in ID space, per
    side the mask of ids seen in training, and what it notes for the
    result line."""
    if fault not in (None, *FAULTS):
        raise ValueError(f"reference has no fault {fault!r}")
    del r  # every entry is a positive
    k, mb = cfg["num_blocks"], cfg["minibatch_size"]
    lay = dsgd_ref.block_layout(
        u, i, jnp.ones(np.shape(u)[0], jnp.float32),
        num_users=cfg["num_users"], num_items=cfg["num_items"], k=k,
        minibatch=mb, solver_seed=cfg["solver_seed"],
        sort_side=cfg["minibatch_sort"])
    scale = jnp.float32(cfg["init_scale"])
    U = dsgd_ref.init_rows(lay["id_of_user_row"], scale,
                           rank=cfg["num_factors"])
    V = dsgd_ref.init_rows(lay["id_of_item_row"], scale,
                           rank=cfg["num_factors"])
    ru, ri = lay["row_of_user"], lay["row_of_item"]
    out = {"init": (dsgd_ref.to_id_space(U, ru), dsgd_ref.to_id_space(V, ri)),
           "seen": (lay["omega_u"][ru] > 0, lay["omega_v"][ri] > 0),
           "sweeps": [],
           "notes": {"reference": "bpr_ref", "bmax": lay["bmax"]}}
    seen, n_seen = seen_rows(lay["omega_v"], k)
    key = jax.random.fold_in(jax.random.PRNGKey(int(cfg["solver_seed"])), 13)
    for t in range(1, sweeps + 1):
        U, V = sweep(U, V, lay["su"], lay["si"], lay["sw"], seen, n_seen,
                     key, jnp.int32(t), dsgd_ref.learning_rate(cfg, t),
                     jnp.float32(cfg["lambda"]), k=k,
                     per_bucket=lay["bmax"] // mb, fault=fault)
        out["sweeps"].append((dsgd_ref.to_id_space(U, ru),
                              dsgd_ref.to_id_space(V, ri)))
    return out


@jax.jit
def _rank_block(U_id, V_id, seen_i, bu, bi, bw):
    """The sum of ``w * rank`` and of ``w`` over one block of pairs."""
    with jax.default_matmul_precision("highest"):
        scores = U_id[bu] @ V_id.T
    own = jnp.take_along_axis(scores, bi[:, None], axis=1)
    above = jnp.sum((scores > own) & seen_i, axis=1).astype(jnp.float32)
    ties = jnp.sum((scores == own) & seen_i, axis=1).astype(jnp.float32)
    places = jnp.maximum(jnp.sum(seen_i) - 1, 1).astype(jnp.float32)
    rank = (above + 0.5 * (ties - 1.0)) / places
    return jnp.stack([jnp.sum(bw * rank), jnp.sum(bw)])


def expected_percentile_rank(U_id, V_id, seen_u, seen_i, hu, hi, hr) -> float:
    """``sum r_ui rank_ui / sum r_ui`` over the held-out interactions of
    the users ranked (ids that are multiples of ``RANKED_EVERY``) whose
    user and item were both seen in training, ``rank_ui`` the share of the
    other seen items that user ``u`` scores above item ``i`` (0 the top, 1
    the end, 0.5 chance; lower is better). Block sums in float32 on the
    device, their totals in float64 on the host."""
    hu, hi = np.asarray(hu), np.asarray(hi)
    w = np.asarray(hr, np.float32) * (
        np.asarray(seen_u)[hu] & np.asarray(seen_i)[hi])
    keep = (hu % RANKED_EVERY == 0) & (w > 0)
    hu, hi, w = hu[keep], hi[keep], w[keep]
    pad = -len(hu) % _PAIRS
    hu, hi, w = (np.pad(a, (0, pad)) for a in (hu, hi, w))
    seen_i = jnp.asarray(seen_i)[None, :]
    sums = [_rank_block(U_id, V_id, seen_i, *(jnp.asarray(a[b:b + _PAIRS])
                                              for a in (hu, hi, w)))
            for b in range(0, len(hu), _PAIRS)]
    if not sums:
        return float("nan")
    num, den = np.asarray(jnp.stack(sums), np.float64).sum(axis=0)
    return float(num / max(den, 1e-30))
