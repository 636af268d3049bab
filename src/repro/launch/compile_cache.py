"""Where JAX keeps its persistent compilation cache.

A cold call on the chip recompiles everything; at the sizes ``chip_smoke.py``
runs, the Cholesky executor alone takes over a minute. The cache's path is
part of its key, so it must not move between runs: when
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing here
overrides it; otherwise the cache lives at a fixed directory inside the
checkout (``.jax_cache``, listed in ``.gitignore``).
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.
    Call before the first compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
