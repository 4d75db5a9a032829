"""JAX persistent compilation cache for the entry points.

Entry points call :func:`enable` once, before their first compile; no
module sets the cache as a side effect of being imported.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is set
here.  Otherwise the cache lives at ``<checkout>/.jax_cache`` (listed in
``.gitignore``): a fixed path, because the path is part of what a later
run must find again.
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
