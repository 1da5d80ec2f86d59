"""Where JAX's persistent compilation cache lives.

One rule for every entry point that enables the cache (``chip_smoke.py``,
``tests/conftest.py``): when ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and nothing is set in code, so whoever
launches the process places the cache; otherwise the cache goes to a
fixed directory inside the checkout. The path is part of the cache's key,
so a directory that moves (tempfile, pid, time) never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

#: fixed in-checkout default (git-ignored)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache(min_compile_secs: float = 1.0) -> str:
    """Turn the persistent compilation cache on; returns its directory.
    Call before the first compile. ``min_compile_secs`` keeps programs
    that compile faster than that out of the cache (it stays small)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", min_compile_secs
    )
    return path
