"""JAX's persistent compilation cache for the launchers and the chip smoke.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
there and nothing is set here.  Otherwise the cache lives at one fixed
directory of the checkout, ``<checkout>/.jax_cache``: the path is part of
what JAX keys an entry by, so a directory that moved (a temporary name, a
pid, a time) would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout-local cache directory (listed in ``.gitignore``)
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call before the first compile.  Respects ``JAX_COMPILATION_CACHE_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
