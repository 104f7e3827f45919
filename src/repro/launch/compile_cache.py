"""JAX's persistent compilation cache for the repository's entry points.

``enable_compile_cache()`` is called by the scripts a user runs
(``chip_smoke.py``, ``benchmarks/run.py``, ``tools/serve_traffic.py``,
``tools/sweep_resume.py``), never at import.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this sets no
other directory; otherwise the cache lives at one fixed, gitignored path in
the checkout, so a later run of the same checkout finds it again.  The
minimum compile time worth caching drops to zero: the packer's kernels
compile in well under a second each, and JAX's default threshold would
skip every one of them.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache: fixed, never built from a temporary name, a PID or
# the time (a directory that moves between runs never hits)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
