"""JAX's persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``launch/*``, ``bench/run.py``) call
:func:`use_compile_cache` before they compile anything; importing a module
of this package never touches it.
"""

from __future__ import annotations

import os
from pathlib import Path

# src/repro/launch/cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Keep compiled programs where ``JAX_COMPILATION_CACHE_DIR`` says when
    it is set (jax reads that variable itself, so nothing is set here), and
    otherwise in ``.jax_cache/`` of the checkout: a fixed path, because the
    path is part of the cache key.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
