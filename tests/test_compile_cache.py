"""The persistent compilation cache helper (repro.compile_cache): an
externally set JAX_COMPILATION_CACHE_DIR stands; otherwise the cache
goes to one fixed, gitignored directory inside the checkout."""
import os
from pathlib import Path

import jax

from repro.compile_cache import enable_compile_cache

REPO = Path(__file__).resolve().parents[1]


def test_external_dir_is_left_alone(isolated_compile_cache):
    want = str(isolated_compile_cache)
    assert enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == want
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_default_dir_is_fixed_inside_the_checkout(isolated_compile_cache,
                                                  monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    first = enable_compile_cache()
    assert first == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    # the same path on every call: never temporary, pid- or time-derived
    assert enable_compile_cache() == first
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()


def test_cost_columns_compile_is_never_a_cache_read(isolated_compile_cache):
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.trend import cost_columns

    enable_compile_cache()
    cols = cost_columns(lambda x: jnp.sin(x) * 2.0,
                        np.ones(8, np.float32))
    assert cols["compile_ms"] > 0.0
    # the compile neither wrote nor could read a persistent entry, and
    # the cache is back on afterwards
    assert not isolated_compile_cache.exists() or not any(
        isolated_compile_cache.iterdir())
    assert jax.config.jax_enable_compilation_cache
