"""Where compiled programs persist across processes.

Compiling the fused super-step for a chip takes seconds to minutes, and
every entry point (``chip_smoke.py``, ``examples/``, ``perfbench/``)
compiles the same programs again on each start.  JAX's persistent
compilation cache removes that cost, provided the cache directory does not
move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the checkout's own cache directory (listed in ``.gitignore``)
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory:
    ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself, and
    nothing else is set here), else the fixed ``<checkout>/.jax_cache``.
    Entry points call this; library code and tests never do."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
