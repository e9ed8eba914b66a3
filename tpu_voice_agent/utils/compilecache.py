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

The names a program carries onto the device's trace (``jax.named_scope``
paths, Pallas kernel names: docs/OBSERVABILITY.md "Names on the device")
are METADATA, and JAX leaves metadata out of the cache key unless told
otherwise: an executable written by another commit is then loaded with
that commit's names, or with none, and a trace read by scope reads
nothing (seen on the chip, PR 24). So the key includes metadata. Metadata
is also every op's Python traceback — file paths and line numbers, which
would make the cache miss in another checkout or after any edit — so the
tracebacks are left out of the locations: the key then depends on the
computation and its names alone, and the price is HLO without source
lines. ``JAX_TRACEBACK_IN_LOCATIONS_LIMIT=10`` (JAX's own variable) brings
them back for a debugging session, with a cache per path and line.
"""

from __future__ import annotations

import os
from pathlib import Path

_CHECKOUT = Path(__file__).resolve().parents[2]


def place_compile_cache() -> str:
    """Call before the first compilation. Returns the cache directory."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if "JAX_TRACEBACK_IN_LOCATIONS_LIMIT" not in os.environ:
        jax.config.update("jax_traceback_in_locations_limit", 0)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
