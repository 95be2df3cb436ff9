"""JAX's persistent compilation cache, kept at one fixed place.

The command-line entry points (``launch.train``, ``launch.serve``,
``benchmarks.run``, ``chip_smoke.py``) call ``enable_compile_cache()``
before they compile anything.  Importing the package does not, so tests
compile without a cache unless they ask for one.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
there and nothing else is set.  Otherwise the cache lives in
``<checkout>/.jax_cache``: a fixed path, so that a later run finds what an
earlier one compiled.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# src/repro/runtime/compile_cache.py -> the checkout root
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def compile_cache_dir() -> str:
    """Where compiled programs are cached: ``$JAX_COMPILATION_CACHE_DIR``
    if set, else ``<checkout>/.jax_cache``."""
    return os.environ.get(ENV_VAR) or os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at ``compile_cache_dir()``; returns it."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):  # else JAX reads the variable itself
        jax.config.update("jax_compilation_cache_dir", path)
    return path
