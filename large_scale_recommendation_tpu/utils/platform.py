"""Backend selection and the persistent compile cache, in one place.

Tests, the multichip dry run and every CPU-only tool call ``force_cpu``
before the first backend initialization; every entry point that can run
on the chip calls ``enable_compilation_cache`` so its executables land in
one directory that can be placed from outside.
"""

from __future__ import annotations

import os
import sys


def compilation_cache_dir() -> str:
    """Where the persistent compile cache lives: ``JAX_COMPILATION_CACHE_DIR``
    when set, otherwise ``<checkout>/.jax_cache`` (git-ignored). The path
    is part of the cache key, so it is never derived from a temp name, a
    pid or the clock."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache (idempotent); returns
    the directory in use (``compilation_cache_dir``). Thresholds are
    dropped so every executable is cached: a cold chip run is dominated
    by compiles, and a second process against the same directory pays
    none of them."""
    import jax

    directory = compilation_cache_dir()
    jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return directory


def device_summary() -> dict:
    """The device as JAX reports it. Every benchmark result and the chip
    smoke carry this, so a CPU number is never read as a chip's."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def stamp_device(extra: dict) -> dict:
    """Write ``platform`` / ``device_kind`` / ``device_count`` into a
    benchmark result's ``extra`` and return the summary, for the one
    line each harness prints about where it ran."""
    device = device_summary()
    extra.update(platform=device["platform"], device_kind=device["kind"],
                 device_count=device["count"])
    return device


def force_cpu(n_devices: int | None = None):
    """Restrict JAX to the CPU backend; returns the imported ``jax`` module.

    Must run before the first backend initialization: it sets
    ``JAX_PLATFORMS`` (for a jax not yet imported), the ``jax_platforms``
    config (for one imported but not initialized) and, with
    ``n_devices``, the ``xla_force_host_platform_device_count`` flag that
    gives the CPU client that many virtual devices. Once backends exist
    nothing here can change them.
    """
    if "jax" not in sys.modules:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if n_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags
                + f" --xla_force_host_platform_device_count={n_devices}"
            ).strip()

    import jax
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        jax.config.update("jax_platforms", "cpu")
    return jax
