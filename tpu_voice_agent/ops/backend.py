"""Which backend the kernels run on — asked in ONE place.

Pallas TPU kernels only execute on a TPU; on the CPU they run under the
Pallas interpreter, which is how the test suite exercises kernel code paths
without a chip. JAX falls back to the CPU on its own when no accelerator
initialises, so "the default backend is the CPU" alone does not say anybody
asked for that: the engines accept interpret-mode kernels only when the CPU
was requested (``JAX_PLATFORMS=cpu`` / ``jax_platforms`` config).
"""

from __future__ import annotations

import jax


def on_cpu() -> bool:
    """Default for every kernel's ``interpret`` argument."""
    return jax.default_backend() == "cpu"


def cpu_requested() -> bool:
    """The operator pinned JAX to the CPU (the test harness, a rehearsal)."""
    return jax.config.jax_platforms == "cpu"


def measurement_devices() -> list:
    """``jax.devices()`` for a bench: a TPU, or the CPU when the CPU was
    asked for (the shape the test suite drives). A bench that found no
    accelerator and was not told to use the CPU exits instead of printing a
    CPU number under a device metric's name."""
    devices = jax.devices()
    if devices[0].platform != "tpu" and not cpu_requested():
        raise SystemExit(
            f"no TPU found (devices: {devices}) and JAX_PLATFORMS=cpu was "
            "not set: refusing to measure on a fallback backend")
    return devices


def resolve_kernels(kernels: str) -> str:
    """An engine's ``kernels`` option ("auto" | "xla" | "pallas") resolved
    against the live backend. "auto" is pallas on a TPU and xla elsewhere;
    an explicit "pallas" that would run interpreted is refused unless the
    CPU was asked for — a TPU that failed to initialise must not turn into
    a silent interpret-mode run."""
    if kernels == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if kernels not in ("xla", "pallas"):
        raise ValueError(f"unknown kernels {kernels!r}")
    if kernels == "pallas" and on_cpu() and not cpu_requested():
        raise RuntimeError(
            'kernels="pallas" would run in interpret mode: JAX\'s default '
            "backend is the CPU but JAX_PLATFORMS=cpu was not set (did the "
            "TPU fail to initialise?)")
    return kernels
