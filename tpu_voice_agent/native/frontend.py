"""ctypes binding for the C++ audio frontend, with numpy fallback.

The shared library is built on first use with g++, next to the source and
named by the source's content hash — a build of other source is never
picked up, whatever a checkout or a copy did to the mtimes. No pybind11 in
this image, so the ABI is a small extern-C surface bound via ctypes.

The numpy fallback is for a machine WITHOUT a compiler. With g++ present a
failed build raises: a broken frontend must not pass as a slow one.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import shutil
import subprocess
import threading

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "audio_frontend.cpp")

_lock = threading.Lock()
_lib = None
NATIVE_AVAILABLE = False


def _build() -> str | None:
    """Path of the built library, or None when there is no g++ at all."""
    with open(_SRC, "rb") as f:
        key = hashlib.sha256(f.read()).hexdigest()[:12]
    so = os.path.join(_DIR, f"_audio_frontend.{key}.so")
    if os.path.exists(so):
        return so
    if shutil.which("g++") is None:
        return None
    tmp = f"{so}.{os.getpid()}.tmp"  # concurrent first uses race safely
    try:
        proc = subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-o", tmp, _SRC],
            capture_output=True, text=True, timeout=120,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"g++ failed to build {_SRC}:\n{proc.stderr[-2000:]}")
        os.replace(tmp, so)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return so


def _load():
    global _lib, NATIVE_AVAILABLE
    with _lock:
        if _lib is not None:
            return _lib
        so = _build()
        if so is None:
            return None
        lib = ctypes.CDLL(so)
        i64, i32, f32p, i16p = (
            ctypes.c_int64, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int16),
        )
        lib.vg_pcm16_to_float.argtypes = [i16p, i64, f32p]
        lib.vg_rms.argtypes = [f32p, i64]
        lib.vg_rms.restype = ctypes.c_double
        lib.vg_resample_len.argtypes = [i64, i32, i32]
        lib.vg_resample_len.restype = i64
        lib.vg_resample.argtypes = [f32p, i64, i32, i32, f32p]
        lib.vg_resample.restype = i64
        lib.vg_endpointer_new.argtypes = [i32, i32, i32, i32, ctypes.c_double]
        lib.vg_endpointer_new.restype = ctypes.c_void_p
        lib.vg_endpointer_free.argtypes = [ctypes.c_void_p]
        lib.vg_endpointer_reset.argtypes = [ctypes.c_void_p]
        lib.vg_endpointer_in_speech.argtypes = [ctypes.c_void_p]
        lib.vg_endpointer_in_speech.restype = i32
        lib.vg_endpointer_noise_floor.argtypes = [ctypes.c_void_p]
        lib.vg_endpointer_noise_floor.restype = ctypes.c_double
        lib.vg_endpointer_feed.argtypes = [ctypes.c_void_p, f32p, i64]
        lib.vg_endpointer_feed.restype = i32
        _lib = lib
        NATIVE_AVAILABLE = True
        return lib


def _f32(a: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(a, dtype=np.float32)


def pcm16_to_float(data: bytes) -> np.ndarray:
    """PCM16LE bytes -> float32 [-1, 1]; C++ path when available."""
    lib = _load()
    n = len(data) // 2
    if lib is None:
        return np.frombuffer(data, dtype="<i2").astype(np.float32) / 32768.0
    src = np.frombuffer(data, dtype="<i2")
    out = np.empty(n, dtype=np.float32)
    lib.vg_pcm16_to_float(
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_int16)), n,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
    )
    return out


def rms(samples: np.ndarray) -> float:
    lib = _load()
    x = _f32(samples)
    if lib is None:
        return float(np.sqrt(np.mean(x * x))) if len(x) else 0.0
    return float(lib.vg_rms(x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), len(x)))


def resample(samples: np.ndarray, sr_in: int, sr_out: int) -> np.ndarray:
    """Windowed-sinc resample (anti-aliased — unlike the reference's
    nearest-neighbor decimation, App.tsx:18-32). Falls back to linear
    interpolation without the native lib."""
    x = _f32(samples)
    if sr_in == sr_out or len(x) == 0:
        return x
    lib = _load()
    n_out = len(x) * sr_out // sr_in
    if lib is None:
        pos = np.arange(n_out) * (sr_in / sr_out)
        return np.interp(pos, np.arange(len(x)), x).astype(np.float32)
    out = np.empty(n_out, dtype=np.float32)
    got = lib.vg_resample(
        x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), len(x), sr_in, sr_out,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
    )
    return out[:got]


class NativeEndpointer:
    """C++ twin of audio.endpoint.EnergyEndpointer (same constructor/feed
    semantics; parity-tested). Falls back to the Python implementation."""

    def __init__(
        self,
        sample_rate: int = 16_000,
        frame_ms: int = 20,
        trailing_silence_ms: int = 350,
        min_speech_ms: int = 200,
        threshold_mult: float = 3.0,
    ):
        lib = _load()
        self._lib = lib
        if lib is None:
            from ..audio.endpoint import EnergyEndpointer

            self._py = EnergyEndpointer(
                sample_rate, frame_ms, trailing_silence_ms, min_speech_ms, threshold_mult
            )
            self._h = None
        else:
            self._py = None
            self._h = lib.vg_endpointer_new(
                sample_rate, frame_ms, trailing_silence_ms, min_speech_ms,
                ctypes.c_double(threshold_mult),
            )

    @property
    def in_speech(self) -> bool:
        if self._py is not None:
            return self._py.in_speech
        return bool(self._lib.vg_endpointer_in_speech(self._h))

    @property
    def noise_floor(self) -> float:
        if self._py is not None:
            return self._py.noise_floor
        return float(self._lib.vg_endpointer_noise_floor(self._h))

    def feed(self, samples: np.ndarray) -> bool:
        if self._py is not None:
            return self._py.feed(samples)
        x = _f32(samples)
        return bool(
            self._lib.vg_endpointer_feed(
                self._h, x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), len(x)
            )
        )

    def reset(self) -> None:
        if self._py is not None:
            self._py.reset()
        else:
            self._lib.vg_endpointer_reset(self._h)

    def __del__(self):
        h = getattr(self, "_h", None)
        if h and self._lib is not None:
            try:
                self._lib.vg_endpointer_free(h)
            except Exception:
                pass
