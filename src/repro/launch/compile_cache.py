"""JAX's persistent compilation cache for the serving entry points.

A 32-layer prefill per bucket plus the decode step take minutes to
compile from cold; the persistent cache lets a second process on the same
machine load them instead.  The cache's directory is part of where JAX
looks entries up, so it must not move between runs: it is either the
directory the environment names or one fixed directory in the checkout.
Tests leave the cache off.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: The cache directory when the environment names none (gitignored).
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    nothing is changed.  Otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`.  Call before the first compilation.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
