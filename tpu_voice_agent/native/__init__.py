"""Native (C++) host-side runtime pieces, ctypes-bound.

The reference has zero native code (SURVEY.md §2: all TS/JS; its heavy
lifting is cloud APIs). This package holds the host-side hot paths that
should not run in Python: audio decode/resample/RMS and the energy
endpointer. The TPU compute path stays JAX/Pallas; this is the IO layer
around it.

On a machine with no compiler ``native_available()`` is False and the
pure-numpy twins in ``audio/`` are used instead — same seam style as the
reference's null-key STT fake (SURVEY.md §4). With g++ present a failed
build raises.
"""

from . import frontend
from .frontend import (
    NativeEndpointer,
    pcm16_to_float,
    resample,
    rms,
)


def native_available() -> bool:
    """True once the C++ frontend .so has been built+loaded (lazy, so a
    module-level by-value snapshot would always read False)."""
    return frontend.NATIVE_AVAILABLE


__all__ = ["native_available", "NativeEndpointer", "pcm16_to_float", "resample", "rms"]
