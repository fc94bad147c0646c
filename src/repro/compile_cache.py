"""JAX's persistent compilation cache, set up in one place.

Entry points (`chip_smoke.py`, `python -m repro.serve`,
`python -m benchmarks.run`) call `enable_compile_cache()` before their
first compile; nothing calls it at import time.

Where the cache lives:

* `JAX_COMPILATION_CACHE_DIR` set -- that directory, JAX's own setting;
* else `<checkout>/.jax_cache` (gitignored). The path is fixed, never
  temporary or derived from a pid or a clock, so a second process in the
  same checkout finds what the first one compiled.
"""
from __future__ import annotations

import contextlib
import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Points JAX's persistent cache at its directory (see module doc)
    and caches every program, however fast it compiled, so the serving
    step's programs are kept even below JAX's default 1 s threshold.
    Returns the directory. Idempotent; call before the first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


@contextlib.contextmanager
def compile_cache_off():
    """Keeps the persistent cache out of the compiles inside the block:
    a compile that is timed as a compile, or one for a described chip
    that is not attached (its entry could never be read back). The
    setting in force before is restored on exit."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
