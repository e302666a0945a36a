"""Where the on-disk caches live, and JAX's persistent compilation cache.

A cold compile of the flagship train step takes minutes; the driver and
users re-run the same shapes constantly, and the persistent cache makes
every process after the first start hot. The cache directory is part of
the cache key, so it must never move: `JAX_COMPILATION_CACHE_DIR`, when
set, places it (JAX reads the variable itself — nothing here overrides
it); otherwise it is one fixed directory inside the checkout. Called by
chip_smoke.py, denoise.py and the scripts; users can call it
once at program start.
"""
from __future__ import annotations

import os

# <checkout>/.jax_cache (git-ignored): the default home of the jit cache,
# the Q_J / canonical-kernel constants (basis.CACHE_PATH) and the kernel
# block table (kernels.tuning.cache_dir) — a chip call keeps nothing
# outside the checkout
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), '.jax_cache')


def enable_compilation_cache(path: str | None = None) -> str:
    """Turn the persistent compilation cache on; returns its directory.

    `path` (a FIXED directory — tests keep their own) is only used when
    `JAX_COMPILATION_CACHE_DIR` is not set."""
    import jax

    # the compile log starts with the cache: a process that turns the
    # cache on knows, per function, what it traced, compiled and loaded
    from ..observability.runtime import install_compile_listener
    install_compile_listener()
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 1.0)
    env = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if env:
        return env
    path = path or os.path.join(CHECKOUT_CACHE_DIR, 'jit')
    os.makedirs(path, exist_ok=True)
    jax.config.update('jax_compilation_cache_dir', path)
    return path
