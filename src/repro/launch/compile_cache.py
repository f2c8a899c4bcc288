"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points call `enable()` before their first compile, so a process reuses
what an earlier process on the same checkout compiled.  The directory is part
of the cache's key: a path that moved between runs would never hit, so it is
never a temp-, pid- or time-based one.
"""

from __future__ import annotations

import os

import jax

# src/repro/launch/compile_cache.py -> the checkout root, three levels up.
CHECKOUT_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)
DEFAULT_DIR = os.path.join(CHECKOUT_ROOT, ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use.

    With JAX_COMPILATION_CACHE_DIR set, JAX reads it itself and nothing is
    set here.  Otherwise the cache lives at <checkout>/.jax_cache."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
