"""The one place JAX's persistent compilation cache is placed.

Every entry point (the CLI's ``main``, ``bench.py``, ``chip_smoke.py``)
and the artifact store's fallback call ``configure_compile_cache``:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it at import;
  nothing here sets another directory.
- otherwise: the fixed ``<checkout>/.jax_cache`` (gitignored). The path
  is part of the cache key, so it never derives from a temp name, a
  pid or the time — a directory that moves never hits.
- a directory some earlier caller already configured stays as it is.
"""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: the checkout root: the directory that holds the package
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory
    and return the directory in effect."""
    import jax

    current = jax.config.jax_compilation_cache_dir
    if current:
        # the env var (read by JAX itself) or an earlier caller
        return current
    os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
