"""JAX's persistent compilation cache, switched on by the entry points.

A cold compile of a full-width serving step costs tens of seconds; the
persistent cache lets a later process on the same machine skip it. The
entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks.run``) call ``enable_compile_cache`` once at start-up;
nothing calls it at import, so importing the library never changes JAX's
configuration.
"""
from __future__ import annotations

import os

import jax

#: The checkout root: src/repro/launch/ -> three levels up.
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

#: Where the cache lives when ``JAX_COMPILATION_CACHE_DIR`` is unset — a
#: fixed, git-ignored path inside the checkout. Fixed on purpose: a
#: directory named from a temp dir, a pid or the time would never hit.
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory:
    ``JAX_COMPILATION_CACHE_DIR`` when that is set (and no other),
    ``DEFAULT_CACHE_DIR`` otherwise."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
