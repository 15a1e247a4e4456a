"""Where JAX's persistent compilation cache lives.

Entry points call :func:`enable_compile_cache` once at start-up:
``chip_smoke.py``, the fleet worker (``python -m repro.fleet.worker``),
``examples/*`` and ``benchmarks/*``.  Library code never does, so
importing ``repro`` changes no JAX setting.

``JAX_COMPILATION_CACHE_DIR``, when set, names the directory, and no other
directory is set in code.  Otherwise the cache lives at the fixed
``<checkout>/.jax_cache`` (git-ignored).  The path is part of a cache
entry's key, so it never depends on a temporary name, a pid or the time.
Fleet workers inherit the parent's environment and resolve the same
directory, so every process of a run shares one cache.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping, Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: the in-checkout default: <checkout>/src/repro/compile_cache.py -> <checkout>
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def compile_cache_dir(environ: Optional[Mapping[str, str]] = None) -> str:
    """The cache directory ``environ`` (default: ``os.environ``) selects."""
    environ = os.environ if environ is None else environ
    return environ.get(ENV_VAR) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return that directory."""
    import jax
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
