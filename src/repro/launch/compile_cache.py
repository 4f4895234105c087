"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points call ``use_compile_cache()`` before they compile anything, so
a second run of the same program on the same chip reads its compiled steps
back instead of compiling them again.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

# <checkout>/.jax_cache: fixed, because the directory is part of the key
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> Optional[str]:
    """Turn on the persistent compilation cache; returns the directory set.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX has already read it
    and nothing is changed (returns None).  Otherwise the cache goes to
    ``CACHE_DIR``.  Call before the first compile: JAX opens the cache then.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
