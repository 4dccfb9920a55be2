"""MovieLens dataset loaders (the benchmark workloads, BASELINE.md).

The reference repo ships no data loaders at all — its examples hardcode 47
ratings (reference: SparkExample.scala:54-104) and its algorithms consume
engine datasets the caller built. The benchmark configs (BASELINE.md) are
MovieLens-100K/25M and Netflix-scale workloads, so first-class loaders live
here:

- ``load_ml100k``: the ``u.data`` tab-separated format
  (user, item, rating, timestamp).
- ``load_ml25m``: the ``ratings.csv`` format
  (userId,movieId,rating,timestamp with a header row).
- ``train_test_split``: seeded holdout split.
- ``synthetic_like``: a planted-low-rank stand-in with the same shape
  statistics, for environments without the datasets (zero-egress CI).
"""

from __future__ import annotations

import os

import numpy as np

from large_scale_recommendation_tpu.core.generators import SyntheticMFGenerator
from large_scale_recommendation_tpu.core.types import Ratings
from large_scale_recommendation_tpu.data.native import parse_ratings_file


def load_ml100k(path: str) -> Ratings:
    """Load MovieLens-100K ``u.data`` (tab-separated, no header)."""
    if os.path.isdir(path):
        path = os.path.join(path, "u.data")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"ML-100K not found at {path}; pass the directory containing "
            "u.data or use synthetic_like('ml-100k')"
        )
    users, items, vals = parse_ratings_file(path, delimiter="\t")
    return Ratings.from_arrays(users=users, items=items, ratings=vals)


def load_ml25m(path: str) -> Ratings:
    """Load MovieLens-25M ``ratings.csv`` (comma-separated, header row).

    Uses the native single-pass parser when built (seconds instead of the
    minutes numpy text readers take at this size)."""
    if os.path.isdir(path):
        path = os.path.join(path, "ratings.csv")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"ML-25M not found at {path}; pass the directory containing "
            "ratings.csv or use synthetic_like('ml-25m')"
        )
    users, items, vals = parse_ratings_file(path, delimiter=",",
                                            skip_header=1)
    return Ratings.from_arrays(users=users, items=items, ratings=vals)


def load_ratings_file(path: str) -> Ratings:
    """Load a ratings file, sniffing the format: MovieLens-25M
    ``ratings.csv`` (comma-separated, ``userId,movieId,...`` header) or
    MovieLens-100K ``u.data`` (tab-separated, no header): a real-data
    run should accept either format without the caller naming it."""
    if os.path.isdir(path):
        for cand in ("ratings.csv", "u.data"):
            p = os.path.join(path, cand)
            if os.path.exists(p):
                path = p
                break
        else:
            raise FileNotFoundError(
                f"no ratings.csv or u.data in directory {path}")
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    with open(path, "r") as fh:
        first = fh.readline()
    if "," in first:
        if any(c.isalpha() for c in first):
            return load_ml25m(path)
        users, items, vals = parse_ratings_file(path, delimiter=",")
        return Ratings.from_arrays(users=users, items=items, ratings=vals)
    return load_ml100k(path)


def compact_ratings(ratings: Ratings):
    """Dense-id compaction of a real-id ratings set — the parse→compact
    seam in front of the on-device pipeline (``fit_device`` /
    ``device_block_problem`` require ids in [0, num_users) × [0,
    num_items); real MovieLens ids are sparse).

    Returns ``(u, i, vals, num_users, num_items)`` with int32 dense ids
    (row j of the dense space = j-th id in the compaction order — opaque
    to training, which only needs density).
    """
    from large_scale_recommendation_tpu.data.native import compact_ids

    ru, ri, rv, rw = ratings.to_numpy()
    real = rw > 0
    ru, ri, rv = ru[real], ri[real], rv[real]
    _, u_dense, _ = compact_ids(ru)
    _, i_dense, _ = compact_ids(ri)
    return (u_dense.astype(np.int32), i_dense.astype(np.int32),
            rv.astype(np.float32),
            int(u_dense.max()) + 1, int(i_dense.max()) + 1)


_SHAPES = {
    # name: (num_users, num_items, nnz)
    "ml-100k": (943, 1682, 100_000),
    "ml-1m": (6_040, 3_706, 1_000_209),
    "ml-25m": (162_541, 59_047, 25_000_095),
    "netflix": (480_189, 17_770, 100_480_507),
}


def synthetic_like(name: str, nnz: int | None = None, rank: int = 16,
                   noise: float = 0.3, seed: int = 0,
                   skew_lam: float = 2.0,
                   num_users: int | None = None,
                   num_items: int | None = None) -> tuple[Ratings, Ratings]:
    """A planted-low-rank workload with the named dataset's shape statistics
    (skewed id draws — real rating matrices are power-law).

    Returns (train, test) with a 95/5 split by volume. The stand-in for
    benchmark runs where the real files aren't present (zero-egress hosts).
    ``num_users``/``num_items`` override the named shape (reduced runs must
    shrink the vocab with nnz to stay ≥ ~100 obs/row — docs/PERF.md).
    """
    if name not in _SHAPES:
        raise KeyError(f"unknown dataset {name!r}; have {sorted(_SHAPES)}")
    nu, ni, n = _SHAPES[name]
    nu = int(num_users) if num_users is not None else nu
    ni = int(num_items) if num_items is not None else ni
    n = nnz if nnz is not None else n
    gen = SyntheticMFGenerator(num_users=nu, num_items=ni, rank=rank,
                               noise=noise, seed=seed, skew_lam=skew_lam)
    return gen.generate(int(n * 0.95)), gen.generate(n - int(n * 0.95))


def train_test_split(ratings: Ratings, test_fraction: float = 0.1,
                     seed: int = 0) -> tuple[Ratings, Ratings]:
    """Seeded random holdout split."""
    ru, ri, rv, rw = ratings.to_numpy()
    real = rw > 0
    ru, ri, rv = ru[real], ri[real], rv[real]
    rng = np.random.default_rng(seed)
    n = len(ru)
    test_mask = np.zeros(n, dtype=bool)
    test_mask[rng.choice(n, int(n * test_fraction), replace=False)] = True
    return (
        Ratings.from_arrays(ru[~test_mask], ri[~test_mask], rv[~test_mask]),
        Ratings.from_arrays(ru[test_mask], ri[test_mask], rv[test_mask]),
    )
