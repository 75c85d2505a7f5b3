"""JAX's persistent compilation cache for this repository's entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
other directory is set here. Otherwise the cache lives at a fixed
``<repo>/.jax_cache`` (gitignored): the path is part of what a later run
must find again, so it never holds a temp dir, a pid or a time.

The serving engine compiles many small per-layer programs, each in well
under a second; JAX caches only programs that took a second or more by
default, so the threshold is lowered to zero. On the CPU backend (tests,
reduced-width runs) the cache stays off: XLA:CPU entries reload with
warnings about the machine they were compiled for, and save little.

Library modules never call this: only entry points do, first thing in
``main`` (``chip_smoke.py``, ``repro.launch.serve``/``train``, the
benchmarks).
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping, Optional

REPO_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def cache_dir_to_set(environ: Mapping[str, str] = os.environ
                     ) -> Optional[str]:
    """The directory an entry point sets, or None where JAX already has
    one from the environment."""
    return None if environ.get(ENV_VAR) else REPO_CACHE_DIR


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compilation cache on for an accelerator
    backend; returns its directory (None on the CPU backend)."""
    import jax

    if jax.default_backend() == "cpu":
        return None
    path = cache_dir_to_set()
    if path is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path or os.environ[ENV_VAR]
