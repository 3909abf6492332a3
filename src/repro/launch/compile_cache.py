"""Where JAX keeps compiled programs between runs of this repo's programs.

Every entry point (`chip_smoke.py`, `repro.launch.serve_alloc`,
`repro.launch.fedsem_e2e`, `benchmarks/run.py`, `benchmarks/bench_*.py`)
calls `setup_compile_cache` before its first compile. A cold serving run
compiles one solve program per bucket (plus the warm-start refine programs),
each taking seconds on a TPU, so a second run that finds them on disk starts
far sooner.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here. Otherwise the cache is a fixed directory inside the checkout,
``<repo>/.jax_cache`` (listed in ``.gitignore``): the path is part of what a
later run must find again, so it never depends on a temporary name, a pid
or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the checkout's root (src/repro/launch/ -> repo)
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
