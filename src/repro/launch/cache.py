"""JAX's persistent compilation cache for the entry points.

`use_compile_cache()` is called by every entry point (`repro.launch.train`,
`repro.launch.serve`, `benchmarks/run.py`, `chip_smoke.py`) before it
compiles — never at import.  Where `JAX_COMPILATION_CACHE_DIR` (or the
config option it sets) names a directory, JAX uses it and nothing is set
here.  Otherwise, on an accelerator, the cache goes to `.jax_cache` at the
root of the checkout: a fixed path, because the path is part of what a
later run in the same checkout must find again.  The CPU backend gets no
default cache: its compiles are cheap, and its cached executables are
tied to the host's instruction set.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str | None:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use (None: no cache)."""
    configured = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                  or jax.config.jax_compilation_cache_dir)
    if configured:
        return configured
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
