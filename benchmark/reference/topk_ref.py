"""Plain reference for the serving cells: exact top-k by full-catalog
inner product, float32 ``jax.numpy`` at ``highest`` matmul precision, over
the benchmark's own factor tables, in blocks of user rows so that the
``[rows, items]`` scores fit. It imports nothing of the program.

``int8_answers`` is the same retrieval computed one precision step down
from what the configuration states (int8 scores with no float32 rescoring):
the control that the comparison has to fail.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@partial(jax.jit, static_argnames=("k",))
def _score_block(U_rows, V, served_ids, *, k):
    with jax.default_matmul_precision("highest"):
        scores = jnp.dot(U_rows, V.T, precision="highest")
    top_v, top_i = jax.lax.top_k(scores, k)
    at_served = jnp.take_along_axis(scores, served_ids, axis=1)
    return top_v, top_i, at_served, jnp.std(scores, axis=1)


def _padded_blocks(arrays, block: int):
    """Equal-size blocks of rows of each array (the last one zero-padded),
    with the number of real rows in each."""
    n = len(arrays[0])
    for b0 in range(0, n, block):
        parts = [np.asarray(a[b0:b0 + block]) for a in arrays]
        real = len(parts[0])
        if real < block:
            parts = [np.concatenate([p, np.zeros((block - real,) + p.shape[1:],
                                                 p.dtype)]) for p in parts]
        yield parts, real


def exact_topk(U, V, user_ids, served_ids, k: int, block: int = 128):
    """For each user: the reference's top-``k`` scores and ids, the
    reference's score of each SERVED id, and the spread (std) of that
    user's scores over the catalog."""
    outs = []
    for (ids, sv), real in _padded_blocks((user_ids, served_ids), block):
        out = _score_block(U[jnp.asarray(ids)], V,
                           jnp.asarray(np.maximum(sv, 0)), k=k)
        outs.append([np.asarray(o)[:real] for o in out])
    return [np.concatenate(c) for c in zip(*outs)]


def _quantize(X):
    amax = jnp.max(jnp.abs(X), axis=1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(X / scale[:, None]), -127, 127).astype(jnp.int8)
    return q, scale


@partial(jax.jit, static_argnames=("k",))
def _int8_block(U_rows, V, *, k):
    qu, su = _quantize(U_rows)
    qv, sv = _quantize(V)
    s = jax.lax.dot_general(qu, qv, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.int32)
    s = s.astype(jnp.float32) * (su[:, None] * sv[None, :])
    return jax.lax.top_k(s, k)


def int8_answers(U, V, user_ids, k: int, block: int = 128):
    """``(ids, scores)`` as an int8-only retrieval would answer."""
    ids_out, sc_out = [], []
    for (ids,), real in _padded_blocks((user_ids,), block):
        v, i = _int8_block(U[jnp.asarray(ids)], V, k=k)
        ids_out.append(np.asarray(i)[:real])
        sc_out.append(np.asarray(v)[:real])
    return np.concatenate(ids_out), np.concatenate(sc_out)
