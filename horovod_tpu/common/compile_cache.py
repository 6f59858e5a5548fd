"""Where JAX's persistent compilation cache lives.

The cache key includes the directory, so a directory that moves never hits:
the path is either the one the environment names or one fixed place in the
checkout, never a temporary name, a process id or a timestamp.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache (git-ignored), beside the package directory.
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses it and this
    sets no other; where it is not, the cache goes to :data:`DEFAULT_DIR`.
    Call before the first compile of the process."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
