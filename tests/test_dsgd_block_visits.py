"""``ops.sgd.dsgd_train`` visits a sweep's ``k²`` blocks one at a time and
sweeps each against its own row block of U and item block of V.

The other side of every comparison is built here, from ``sgd_block_sweep``
over the WHOLE tables with global rows (a stratum flattened to one block:
the spelling ``dsgd_train`` had before PR 32), so the two sides share the
minibatch kernel and nothing else. On the CPU the two are the same
additions in the same order: equal bit for bit.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from large_scale_recommendation_tpu.core.generators import (
    SyntheticMFGenerator,
)
from large_scale_recommendation_tpu.core.updaters import (
    RegularizedSGDUpdater,
    inverse_sqrt_lr,
)
from large_scale_recommendation_tpu.data import blocking
from large_scale_recommendation_tpu.ops.sgd import (
    dsgd_train,
    sgd_block_sweep,
)

MB = 64
RANK = 8
UPD = RegularizedSGDUpdater(learning_rate=0.05, lambda_=0.1,
                            schedule=inverse_sqrt_lr)
# updater, minibatch and collision are static, as they are in dsgd_train
SWEEP = jax.jit(sgd_block_sweep, static_argnums=(8, 10, 11))


def blocked(k, n=3000, seed=0):
    """A host-blocked toy problem: (U0, V0, su, si, sv, sw, omega_u,
    omega_v, icu, icv) as numpy, blocks padded to whole minibatches."""
    train = SyntheticMFGenerator(num_users=90, num_items=50, rank=4,
                                 noise=0.1, seed=seed).generate(n)
    problem = blocking.block_problem(train, num_blocks=k, seed=0,
                                     minibatch_multiple=MB,
                                     minibatch_sort="item")
    r = problem.ratings
    icu, icv = blocking.minibatch_inv_counts(r, MB)
    rng = np.random.default_rng(seed)
    U0 = rng.normal(0, 0.2, (problem.users.omega.shape[0], RANK))
    V0 = rng.normal(0, 0.2, (problem.items.omega.shape[0], RANK))
    return (U0.astype(np.float32), V0.astype(np.float32),
            r.u_rows.astype(np.int32), r.i_rows.astype(np.int32),
            r.values.astype(np.float32), r.weights.astype(np.float32),
            problem.users.omega, problem.items.omega, icu, icv)


def flat_sweeps(U, V, su, si, sv, sw, ou, ov, icu, icv, *, k, iterations,
                collision, t0):
    """Each stratum flattened to ``[k·b]`` and swept against the whole
    tables, as ``dsgd_train`` did before it visited blocks."""
    store = U.dtype
    U, V = U.astype(jnp.float32), V.astype(jnp.float32)

    def flat(a, s):
        return None if a is None else a[s].reshape(-1)

    for it in range(iterations):
        for s in range(k):
            U, V = SWEEP(U, V, flat(su, s), flat(si, s), flat(sv, s),
                         flat(sw, s), ou, ov, UPD, it + 1 + t0, MB,
                         collision, flat(icu, s), flat(icv, s))
    return U.astype(store), V.astype(store)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("collision", ["mean", "sum"])
@pytest.mark.parametrize("precomputed", [True, False],
                         ids=["scales", "counted"])
@pytest.mark.parametrize("k", [1, 2, 4])
def test_block_visits_equal_the_flat_stratum_sweep(k, precomputed,
                                                   collision, dtype):
    U0, V0, su, si, sv, sw, ou, ov, icu, icv = map(jnp.asarray, blocked(k))
    if not precomputed:
        icu = icv = None
    U0, V0 = U0.astype(dtype), V0.astype(dtype)
    assert su.shape[:2] == (k, k) and su.shape[-1] > MB
    kw = dict(iterations=2, collision=collision, t0=3)
    U, V = dsgd_train(U0, V0, su, si, sv, sw, ou, ov, icu, icv,
                      updater=UPD, minibatch=MB, num_blocks=k, **kw)
    Uf, Vf = flat_sweeps(U0, V0, su, si, sv, sw, ou, ov, icu, icv, k=k,
                         **kw)
    assert U.dtype == dtype and V.dtype == dtype
    assert not np.array_equal(np.asarray(U, np.float32),
                              np.asarray(U0, np.float32))
    np.testing.assert_array_equal(np.asarray(U, np.float32),
                                  np.asarray(Uf, np.float32))
    np.testing.assert_array_equal(np.asarray(V, np.float32),
                                  np.asarray(Vf, np.float32))


def test_padding_in_later_blocks_moves_no_row():
    """Weight-0 padding carries global row 0, which lies outside every
    block ``p > 0``: it must land on a valid local row and add nothing.
    A layout with a minibatch of padding appended to every block leaves
    every row (global row 0 and each block's local row 0 among them) as
    the layout without it leaves them."""
    k = 4
    U0, V0, su, si, sv, sw, ou, ov, icu, icv = blocked(k)
    assert (sw[1:, 1:] == 0).any(), "blocks p > 0 already hold padding"

    def padded(a, fill):
        pad = np.full(a.shape[:2] + (MB,), fill, a.dtype)
        return np.concatenate([a, pad], axis=-1)

    more = (padded(su, 0), padded(si, 0), padded(sv, 0), padded(sw, 0),
            ou, ov, padded(icu, 1), padded(icv, 1))
    kw = dict(updater=UPD, minibatch=MB, num_blocks=k, iterations=2)
    U, V = dsgd_train(U0, V0, su, si, sv, sw, ou, ov, icu, icv, **kw)
    Up, Vp = dsgd_train(U0, V0, *more, **kw)
    np.testing.assert_array_equal(np.asarray(Up), np.asarray(U))
    np.testing.assert_array_equal(np.asarray(Vp), np.asarray(V))
    # a padding-only run: nothing but padding, nothing moves at all
    zero = np.zeros_like(sw)
    Uz, Vz = dsgd_train(U0, V0, np.zeros_like(su), np.zeros_like(si),
                        sv, zero, ou, ov, icu, icv, **kw)
    np.testing.assert_array_equal(np.asarray(Uz), U0)
    np.testing.assert_array_equal(np.asarray(Vz), V0)


@pytest.mark.parametrize("fault", ["minibatch", "rows_u", "rows_v"])
def test_misaligned_layout_raises(fault):
    k = 2
    U0, V0, su, si, sv, sw, ou, ov, icu, icv = blocked(k)
    mb = MB
    if fault == "minibatch":
        mb = MB - 1
        assert su.shape[-1] % mb
    elif fault == "rows_u":
        U0, ou = U0[:-1], ou[:-1]
    else:
        V0, ov = V0[:-1], ov[:-1]
    with pytest.raises(ValueError, match="minibatch|divisible"):
        dsgd_train(U0, V0, su, si, sv, sw, ou, ov, icu, icv, updater=UPD,
                   minibatch=mb, num_blocks=k, iterations=1)
