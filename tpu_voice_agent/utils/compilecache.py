"""Where the persistent XLA compile cache lives — decided in ONE place.

Whisper-large-v3 plus the decoder is minutes of compilation on a cold
start, and every entry point (the service mains, ``python -m
tpu_voice_agent.services.stack``, ``bench.py``, the benches,
``chip_smoke.py``, the test harness) pays it again unless they share a
cache. The cache's path is part of its key, so it must never move: no temp
names, pids or timestamps.

- ``JAX_COMPILATION_CACHE_DIR`` set: the operator (or the machine image)
  placed the cache; JAX reads the variable itself and this module sets
  nothing.
- unset: ``<checkout>/.jax_cache`` (git-ignored).
"""

from __future__ import annotations

import os
from pathlib import Path

_CHECKOUT = Path(__file__).resolve().parents[2]


def place_compile_cache() -> str:
    """Call before the first compilation. Returns the cache directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
