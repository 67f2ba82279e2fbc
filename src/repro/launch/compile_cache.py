"""JAX's persistent compilation cache for the command-line entry points.

`enable_compile_cache()` is called first thing by `chip_smoke.py`,
`python -m repro.launch.cluster_serve` and the `benchmarks/*_bench.py`
scripts, so their processes share compiled programs across runs. The path is
part of each entry's key, so it is fixed: a directory that moved would never
hit. Tests do not call it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"  # <checkout>/.jax_cache


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    `JAX_COMPILATION_CACHE_DIR`, when set, is the cache: JAX reads it itself
    and no other directory is set here. Otherwise the cache lives at
    `<checkout>/.jax_cache` (listed in `.gitignore`).
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
