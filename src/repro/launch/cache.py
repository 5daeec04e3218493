"""Where JAX keeps compiled programs across processes.

One call at each entry point's start-up (``chip_smoke.py``,
``launch/train.py``, ``launch/serve_lda.py``).  A cache directory is part of
each entry's key, so it must not move between runs: when
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing is set
here; otherwise the cache lives at one fixed directory of the checkout
(``.jax_cache/``, git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
