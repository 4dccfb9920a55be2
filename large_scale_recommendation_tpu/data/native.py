"""ctypes bridge to the native fastblock library, with NumPy fallback.

The compute path of this framework is JAX/XLA on device; the runtime around
it is native where that pays (SURVEY: the reference's equivalent layer is
the engines' JVM/Netty runtime). ``csrc/fastblock.cpp`` accelerates the two
host-side ingest hot spots:

- delimited ratings-file parsing (numpy's text readers are ~100× slower on
  ML-25M-sized files),
- one-pass id compaction with occurrence counts (the omegas).

Build is lazy and cached: first use compiles the .so with g++ into
``csrc/`` next to the source (no pybind11 — plain ``extern "C"`` + ctypes).
The .so is a build output, never committed (``.gitignore``): a checkout
starts without one and builds it with its own toolchain, so a binary
from another machine can never be loaded. Every entry point has a
pure-NumPy fallback, so the framework works unchanged where no compiler
exists — after a loud warning.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "csrc", "fastblock.cpp")
_SO = os.path.join(os.path.dirname(_SRC), "libfastblock.so")

_lib = None
_lib_lock = threading.Lock()
_build_failed = False
_build_error: str | None = None


def _load() -> ctypes.CDLL | None:
    """Load (building if needed) the native library; None if unavailable.

    A build/load failure is NOT silent (round-1 lesson: a broken .cpp
    shipped unnoticed because every caller quietly fell back to NumPy):
    it warns once with the compiler error tail, and the message is kept
    in ``native_build_error()`` for tests/diagnostics.
    """
    global _lib, _build_failed, _build_error
    if _lib is not None:
        return _lib
    if _build_failed:
        return None
    with _lib_lock:
        if _lib is not None or _build_failed:
            return _lib
        try:
            if (not os.path.exists(_SO)
                    or os.path.getmtime(_SO) < os.path.getmtime(_SRC)):
                # build beside the target, then rename: a concurrent
                # process never loads a half-written library
                tmp = f"{_SO}.{os.getpid()}.tmp"
                try:
                    subprocess.run(
                        ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
                         "-o", tmp, _SRC],
                        check=True, capture_output=True, timeout=120,
                    )
                    os.replace(tmp, _SO)
                finally:
                    if os.path.exists(tmp):
                        os.remove(tmp)
            lib = ctypes.CDLL(_SO)
        except (OSError, subprocess.SubprocessError, FileNotFoundError) as e:
            _build_failed = True
            detail = ""
            if isinstance(e, subprocess.CalledProcessError) and e.stderr:
                stderr = e.stderr
                if isinstance(stderr, bytes):
                    stderr = stderr.decode(errors="replace")
                detail = ": " + stderr[-1000:]
            _build_error = f"{type(e).__name__}: {e}{detail}"
            import warnings

            warnings.warn(
                "fastblock native build/load failed; using NumPy fallback "
                f"(~100x slower ingest). {_build_error}",
                RuntimeWarning,
                stacklevel=3,
            )
            return None

        LP64 = ctypes.POINTER(ctypes.c_int64)
        LPF = ctypes.POINTER(ctypes.c_float)
        lib.fb_parse_ratings.restype = ctypes.c_int64
        lib.fb_parse_ratings.argtypes = [
            ctypes.c_char_p, ctypes.c_char, ctypes.c_int,
            ctypes.POINTER(LP64), ctypes.POINTER(LP64), ctypes.POINTER(LPF),
        ]
        lib.fb_compact_ids.restype = ctypes.c_int64
        lib.fb_compact_ids.argtypes = [
            LP64, ctypes.c_int64, LP64,
            ctypes.POINTER(LP64), ctypes.POINTER(LP64),
        ]
        lib.fb_stable_bucket.restype = None
        lib.fb_stable_bucket.argtypes = [
            LP64, LP64, ctypes.c_int64, ctypes.c_int64, LP64,
        ]
        lib.fb_minibatch_inv_counts.restype = None
        lib.fb_minibatch_inv_counts.argtypes = [
            ctypes.POINTER(ctypes.c_int32), LPF, ctypes.c_int64,
            ctypes.c_int64, LPF,
        ]
        lib.fb_free.restype = None
        lib.fb_free.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def native_available() -> bool:
    return _load() is not None


def native_build_error() -> str | None:
    """Compiler/loader error from the last failed build attempt, if any."""
    _load()
    return _build_error


def _take_array(lib, ptr, n, ctype, dtype) -> np.ndarray:
    """Copy a malloc'd C buffer into a NumPy array and free it."""
    if n == 0:
        lib.fb_free(ptr)
        return np.empty(0, dtype=dtype)
    arr = np.ctypeslib.as_array(ptr, shape=(n,)).astype(dtype, copy=True)
    lib.fb_free(ptr)
    return arr


def parse_ratings_file(
    path: str, delimiter: str = ",", skip_header: int = 0
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Parse (user, item, rating[, ...]) text into COO arrays.

    Native single-pass parser when available; NumPy fallback otherwise."""
    lib = _load()
    if lib is not None:
        up = ctypes.POINTER(ctypes.c_int64)()
        ip = ctypes.POINTER(ctypes.c_int64)()
        vp = ctypes.POINTER(ctypes.c_float)()
        n = lib.fb_parse_ratings(
            path.encode(), delimiter.encode(), skip_header,
            ctypes.byref(up), ctypes.byref(ip), ctypes.byref(vp),
        )
        if n < 0:
            raise FileNotFoundError(path)
        return (
            _take_array(lib, up, n, ctypes.c_int64, np.int64),
            _take_array(lib, ip, n, ctypes.c_int64, np.int64),
            _take_array(lib, vp, n, ctypes.c_float, np.float32),
        )
    # fallback
    data = np.genfromtxt(path, delimiter=delimiter, skip_header=skip_header,
                         usecols=(0, 1, 2))
    data = np.atleast_2d(data)
    return (data[:, 0].astype(np.int64), data[:, 1].astype(np.int64),
            data[:, 2].astype(np.float32))


def compact_ids(
    ids: np.ndarray,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Dense first-seen-order compaction.

    Returns (unique_ids, inverse_indices, counts) — counts are the omegas
    (≙ DSGDforMF.scala:537-541). Native O(n) hash pass when available,
    np.unique otherwise (sorted order instead of first-seen; both valid
    layouts for callers that treat the mapping as opaque)."""
    ids = np.ascontiguousarray(ids, dtype=np.int64)
    lib = _load()
    if lib is not None:
        idx = np.empty(len(ids), dtype=np.int64)
        up = ctypes.POINTER(ctypes.c_int64)()
        cp = ctypes.POINTER(ctypes.c_int64)()
        m = lib.fb_compact_ids(
            ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            len(ids),
            idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            ctypes.byref(up), ctypes.byref(cp),
        )
        return (
            _take_array(lib, up, m, ctypes.c_int64, np.int64),
            idx,
            _take_array(lib, cp, m, ctypes.c_int64, np.int64),
        )
    uniq, idx, counts = np.unique(ids, return_inverse=True,
                                  return_counts=True)
    return uniq, idx, counts


def stable_bucket(keys: np.ndarray, perm: np.ndarray,
                  num_keys: int) -> np.ndarray:
    """Order indices: ``perm`` stably grouped by ``keys[perm]``.

    Equivalent to ``perm[np.argsort(keys[perm], kind="stable")]`` — the
    blocking hot path's "seeded shuffle then stable sort by block id"
    (data/blocking.py). Native two-pass counting sort when available
    (keys are block ids, so num_keys is tiny)."""
    keys = np.ascontiguousarray(keys, dtype=np.int64)
    perm = np.ascontiguousarray(perm, dtype=np.int64)
    if len(keys) and (keys.min() < 0 or keys.max() >= num_keys):
        # the native kernel indexes a counter array by key — fail cleanly
        # instead of corrupting the heap on native builds
        raise ValueError(
            f"stable_bucket keys outside [0, {num_keys}): "
            f"min={keys.min()} max={keys.max()}"
        )
    lib = _load()
    if lib is not None:
        out = np.empty(len(perm), dtype=np.int64)
        LP64 = ctypes.POINTER(ctypes.c_int64)
        lib.fb_stable_bucket(
            keys.ctypes.data_as(LP64), perm.ctypes.data_as(LP64),
            len(perm), int(num_keys), out.ctypes.data_as(LP64),
        )
        return out
    return perm[np.argsort(keys[perm], kind="stable")]


def minibatch_inv_counts_flat(rows: np.ndarray, weights: np.ndarray,
                              minibatch: int) -> np.ndarray:
    """Per-entry 1/(occurrences of rows[j] in its minibatch chunk); weight-0
    entries get 1.0 and don't count. One native pass when available; the
    NumPy fallback pays an O(n log n) np.unique."""
    rows = np.ascontiguousarray(rows, dtype=np.int32)
    weights = np.ascontiguousarray(weights, dtype=np.float32)
    lib = _load()
    if lib is not None:
        out = np.empty(len(rows), dtype=np.float32)
        LPF = ctypes.POINTER(ctypes.c_float)
        lib.fb_minibatch_inv_counts(
            rows.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            weights.ctypes.data_as(LPF), len(rows), int(minibatch),
            out.ctypes.data_as(LPF),
        )
        return out
    flat = rows.astype(np.int64)
    chunk = np.arange(flat.size, dtype=np.int64) // minibatch
    w = weights > 0
    key = chunk * (int(flat.max(initial=0)) + 2) + flat
    key = np.where(w, key, -1)
    _, inverse, counts = np.unique(key, return_inverse=True,
                                   return_counts=True)
    inv = (1.0 / counts[inverse]).astype(np.float32)
    return np.where(w, inv, 1.0).astype(np.float32)
