"""Persistent JAX compilation cache for the entry points.

`enable_compile_cache` is called once, before the first compile, by
the programs a user starts: `chip_smoke.py`, `benchmarks/run.py` and
`python -m repro.launch.monitor`. Importing the library or running the
tests never turns the cache on.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
this sets no other directory. Otherwise the cache lives at the fixed
``<repo>/.jax_cache`` (listed in `.gitignore`): the directory is part
of what makes an entry findable again, so it never depends on a
temporary directory, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

#: The default cache directory: ``.jax_cache`` at the repository root.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    ``$JAX_COMPILATION_CACHE_DIR`` when it is set, else at
    `DEFAULT_DIR`. Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
