"""JAX persistent compilation cache at a fixed, repo-relative path.

Entry points (`chip_smoke.py`, `repro.launch.serve`, `benchmarks.run`)
call `enable_compile_cache()` at the top of `main()`; nothing calls it at
import time, so tests keep JAX's defaults. The cache key includes the
directory, so the path must never move between runs: it is either what
`JAX_COMPILATION_CACHE_DIR` names (JAX reads that variable itself and the
helper leaves it alone) or `<repo>/.jax_cache` (git-ignored).
"""

from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; returns the directory used."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
