"""JAX's persistent compilation cache at a fixed place.

Entry scripts call ``enable()`` before their first compile.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache there and
nothing else is set; otherwise the cache lives at ``<repo>/.jax_cache``.
The path never depends on a temporary name, a pid or a time: it is part
of what makes a cached program found again.
"""

from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
