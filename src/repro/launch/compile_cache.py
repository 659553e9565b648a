"""Where JAX keeps its persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and this
module sets nothing.  Otherwise the cache lives at ``<checkout>/.jax_cache``
(git-ignored).  The directory is fixed on purpose: a later run from the same
checkout finds what an earlier one compiled only if the directory does not
move, so it is never derived from a temporary name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
