"""Small shared helpers."""
from __future__ import annotations

import functools
import os
from pathlib import Path

#: where the persistent compile cache lives when JAX_COMPILATION_CACHE_DIR
#: is unset: a fixed directory inside the checkout (listed in .gitignore)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


@functools.cache
def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache, once per process, and
    return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    that directory is the cache; no code names another.  Otherwise the
    cache is :data:`CHECKOUT_CACHE_DIR`, a fixed path, so later processes
    in the same checkout find what earlier ones compiled."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)
    return jax.config.jax_compilation_cache_dir


def pow2_bucket(n: int, minimum: int) -> int:
    """Smallest power of two >= max(n, minimum) — the shape-bucketing rule
    used so varying lengths fall into a handful of XLA compile shapes."""
    return 1 << max(int(max(n, 1) - 1).bit_length(), minimum.bit_length() - 1)
