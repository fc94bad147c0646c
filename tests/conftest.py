import os

# Keep tests on the single real CPU device (the dry-run sets its own
# device-count flag in its subprocess). Cap compilation parallelism for
# the 1-core container.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def isolated_compile_cache(monkeypatch, tmp_path):
    """For a test that calls `repro.compile_cache.enable_compile_cache`
    (directly or through an entry point): JAX_COMPILATION_CACHE_DIR
    points at a fresh directory, and the process's cache settings are
    restored afterwards so later tests compile as before."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    path = tmp_path / "jax_cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(path))
    yield path
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()
