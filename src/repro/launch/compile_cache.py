"""JAX's persistent compilation cache, at a path that can be set from outside.

The cache key includes its directory, so the directory must not move
between runs: it is either what ``JAX_COMPILATION_CACHE_DIR`` names (JAX
reads that variable itself, and nothing here overrides it) or one fixed
directory inside the checkout, ``<repo>/.jax_cache`` (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

#: the checkout this package was imported from (src/repro/launch -> root)
REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Call before the first compilation of the process.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
