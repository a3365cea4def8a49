"""Where JAX keeps its persistent compilation cache.

Called by entry points (`chip_smoke.py`, `benchmarks/run.py`,
`benchmarks/bench_dataplane.py`) before their first compile, never at
import: a library that picked a cache directory on import would decide
for every program that imports it.
"""
from __future__ import annotations

import os
from pathlib import Path


def use_compile_cache(checkout: Path) -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    `$JAX_COMPILATION_CACHE_DIR` wins when set (JAX reads it itself, so
    no other directory is configured). Otherwise the cache lives at
    `<checkout>/.jax_cache`, a fixed path: a directory named after a
    temp dir, a pid or the time would never be found again. Every
    program is cached, however quickly it compiled: many of the data
    plane's small programs compile in under JAX's default 1 s threshold,
    and a repeated run should find all of them.
    """
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(Path(checkout).resolve() / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
