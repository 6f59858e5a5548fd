"""Where JAX's persistent compilation cache lives.

The cache key includes the directory, so a directory that moves never hits:
the path is either the one the environment names or one fixed place in the
checkout, never a temporary name, a process id or a timestamp.

The key also includes the programs' metadata. By default JAX strips it
(``strip-debuginfo``) before hashing, and ``op_name`` — the ``phase_*`` and
``hvd_*`` named scopes a device trace shows and the benchmark's readers key
on — lives there: two versions of the step that differ only in their scopes
then share one entry, and whichever compiled first lends the other its names
(my chip run, PR 24: the ResNet-50 step loaded the executable of the commit
before the scopes and its trace showed none). With the metadata in the key
an edit that moves traced lines compiles anew. A checkout at another path
does not: the metadata names source files, and the checkout's own prefix is
cut from those names (``jax_hlo_source_file_canonicalization_regex``), so
the same tree gives the same keys wherever it lies.
"""

from __future__ import annotations

import os
import re
from pathlib import Path

import jax

# <repo>/.jax_cache (git-ignored), beside the package directory.
ROOT = Path(__file__).resolve().parents[2]
DEFAULT_DIR = ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses it and this
    sets no other; where it is not, the cache goes to :data:`DEFAULT_DIR`.
    Call before the first compile of the process."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if jax.config.jax_hlo_source_file_canonicalization_regex is None:
        jax.config.update("jax_hlo_source_file_canonicalization_regex",
                          "^" + re.escape(str(ROOT)) + "/")
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
