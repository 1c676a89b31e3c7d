"""JAX's persistent compilation cache, kept at one fixed place per checkout.

Entry points (``chip_smoke.py``, the ``repro.launch`` CLIs, ``benchmarks/run.py``)
call :func:`enable` before their first compile; importing ``repro`` does not.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has read it already and it
stands. Otherwise the cache goes to ``<repo root>/.jax_cache`` (gitignored):
a fixed path, so a later process of the same checkout finds what an earlier
one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
