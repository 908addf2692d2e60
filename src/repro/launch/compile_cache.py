"""JAX's persistent compilation cache for the entry points.

`enable()` is called once at process start by `chip_smoke.py`,
`repro.launch.train`, `repro.launch.serve` and `benchmarks/common.py`,
so a second run in the same checkout reuses the first run's compiled
programs.  Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself
and this module sets nothing.  Otherwise the cache lives at the fixed
`<checkout>/.jax_cache`, never a temporary or per-process name, so a
run in the same checkout always finds it again.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
