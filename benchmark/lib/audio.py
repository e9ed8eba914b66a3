"""Synthetic speech and framing — copies of ``bench.py synth_utterance`` and
``chip_smoke.py pcm16_frames`` (the web client's framing)."""

from __future__ import annotations

import numpy as np

FRAME_MS = 60
SAMPLE_RATE = 16_000


def synth_utterance(seconds: float, sr: int = SAMPLE_RATE) -> np.ndarray:
    """Speech-like audio: modulated tone bursts over a noise floor."""
    rng = np.random.default_rng(0)
    t = np.arange(int(sr * seconds)) / sr
    return (
        0.2 * np.sin(2 * np.pi * 220 * t) * (np.sin(2 * np.pi * 2.5 * t) > -0.3)
        + 0.002 * rng.standard_normal(len(t))
    ).astype(np.float32)


def pcm16_frames(audio: np.ndarray, frame_ms: int = FRAME_MS) -> list[bytes]:
    """Float audio -> 60 ms PCM16 frames, exactly like the web client."""
    pcm = (np.clip(audio, -1, 1) * 32767).astype("<i2").tobytes()
    step = SAMPLE_RATE * frame_ms // 1000 * 2
    return [pcm[i:i + step] for i in range(0, len(pcm), step)]


def silence_frame(frame_ms: int = FRAME_MS) -> bytes:
    return b"\x00" * (SAMPLE_RATE * frame_ms // 1000 * 2)
