"""JAX's persistent compilation cache, placed from outside or in the checkout.

Entry points call ``enable_compile_cache()`` once, before their first
compile; nothing calls it at import time or from tests.  With
``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads that directory and
this sets no other.  Without it, the cache goes to ``<checkout>/.jax_cache``
(git-ignored): a fixed path, because the path is part of what makes a
cached executable found again by the next run of the same tree.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
