"""JAX's persistent compilation cache, placed from outside.

Entry points (scripts, benchmark mains, `chip_smoke.py`) call
`enable_compile_cache()` once before they compile; no library module
calls it on import. The fused engine compiles a whole run as one scan,
so a cold start pays that compile in full; the cache lets the next
process with the same program skip it.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this
module sets no directory. Otherwise the cache lives at the fixed path
`<checkout>/.jax_cache`: the path is part of what a later process must
find again, so it is never built from a temporary name, a process id or
the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory:
    `$JAX_COMPILATION_CACHE_DIR` when set, else `<checkout>/.jax_cache`."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
