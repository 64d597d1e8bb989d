"""Where JAX keeps its persistent compilation cache.

Called by the entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``python -m repro.service``), never on import of the library.  If
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing is
set here.  Otherwise the cache lives at ``<checkout>/.jax_cache``: a
fixed path, because the path is part of what a later run must find
again.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
