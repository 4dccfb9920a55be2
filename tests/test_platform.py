"""utils.platform: where the compile cache goes and what the device is.

The cache directory is part of the cache key and is placed from outside:
``JAX_COMPILATION_CACHE_DIR`` first, a fixed in-checkout path otherwise —
never an argument, a temp name, a pid or the clock."""

import inspect
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cache_dir_is_the_environment_variable_first(monkeypatch):
    from large_scale_recommendation_tpu.utils import platform

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/outside")
    assert platform.compilation_cache_dir() == "/somewhere/outside"
    # nothing in code can name another directory: no argument exists
    assert not inspect.signature(
        platform.enable_compilation_cache).parameters


def test_cache_dir_is_a_fixed_in_checkout_path_otherwise(monkeypatch):
    from large_scale_recommendation_tpu.utils import platform

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert platform.compilation_cache_dir() == want
    assert platform.compilation_cache_dir() == want  # and stays put
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")  # empty = unset
    assert platform.compilation_cache_dir() == want


def test_enable_sets_that_directory_and_caches_everything(tmp_path):
    """In a child: the config is process-global, and a test session must
    not start writing a persistent cache as a side effect."""
    code = (
        "import jax\n"
        "from large_scale_recommendation_tpu.utils.platform import (\n"
        "    device_summary, enable_compilation_cache)\n"
        "d = enable_compilation_cache()\n"
        "c = jax.config\n"
        "print(d == c.jax_compilation_cache_dir,\n"
        "      c.jax_persistent_cache_min_entry_size_bytes,\n"
        "      c.jax_persistent_cache_min_compile_time_secs, d)\n"
        "print(device_summary())\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    first, second = proc.stdout.strip().splitlines()
    assert first == f"True -1 0.0 {tmp_path / 'cache'}"
    assert second.startswith("{'platform': 'cpu', 'kind': 'cpu', 'count': ")
