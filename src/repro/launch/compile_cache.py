"""JAX's persistent compilation cache, in one fixed place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other directory.  Otherwise the cache lives at
``<repo>/.jax_cache`` (listed in ``.gitignore``): a path that never
changes between runs, because the path is part of what the cache keys on.

A process that sees more than one device keeps no persistent cache.  On a
TPU v5e 2x2, 2-chip programs read back from the cache halted the chip in
their first run, while the same programs compiled in the process ran;
one-chip programs read back run (PERF.md).
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax
from jax.experimental.compilation_cache import compilation_cache

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent cache on and return the directory it uses; with
    more than one device, turn it off and return None."""
    if len(jax.devices()) > 1:
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        return None
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(REPO_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
