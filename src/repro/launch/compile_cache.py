"""JAX's persistent compilation cache for the entry points.

``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
wins.  Otherwise the cache lives at ``<checkout>/.jax_cache`` (listed in
``.gitignore``): a fixed path, because a directory named after a
temporary name, a pid or the time would never find what an earlier run
wrote.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
