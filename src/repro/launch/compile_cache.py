"""JAX's persistent compilation cache, placed from outside the program.

``enable()`` is called from each launcher's ``main`` (never on import):

  * ``JAX_COMPILATION_CACHE_DIR`` set -> JAX already reads it; nothing
    else is set here;
  * otherwise -> ``<checkout>/.jax_cache``, one fixed path (the path is
    part of the cache key, so it never moves between runs);
  * the cache switched off (``jax_enable_compilation_cache`` False, as the
    test suite does) -> left off.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> Optional[str]:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use, or None when the cache is switched off."""
    import jax

    if not jax.config.jax_enable_compilation_cache:
        return None
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
