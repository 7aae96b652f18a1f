"""Where JAX keeps its persistent compile cache for a run of this checkout.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; when that is set, nothing
here overrides it. Otherwise the cache lives at the fixed path
``<checkout>/.jax_cache`` (gitignored). The path is part of the cache key,
so it must not move between runs: no temporary name, pid or timestamp.
"""
from __future__ import annotations

import os
from typing import Optional

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

__all__ = ["use_compile_cache"]


def use_compile_cache() -> Optional[str]:
    """Point JAX's persistent compile cache at ``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` is set; returns the path it set,
    or None when the environment decides."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    import jax
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
