"""JAX's persistent compilation cache, set up the same way by every
entry point that compiles at deployment size (``chip_smoke.py`` and the
benchmark scripts)."""
from __future__ import annotations

import os
from pathlib import Path

#: the checkout root (src/repro/utils/ -> the repository)
CHECKOUT = Path(__file__).resolve().parents[3]

#: cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is not set: a
#: fixed path (the path is part of the cache key, so it never moves)
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here; otherwise the cache lives in the fixed
    ``<checkout>/.jax_cache``."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    DEFAULT_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
