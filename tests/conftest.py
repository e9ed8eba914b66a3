"""Test harness config.

All model/mesh tests run on CPU with 8 virtual XLA devices
(SURVEY.md §4: mirror the reference's seam strategy; multi-chip behavior is
validated via xla_force_host_platform_device_count). JAX_PLATFORMS=cpu is
the explicit CPU run ``ops.backend.cpu_requested`` recognises: Pallas
kernels run interpreted here, and ``tests/test_kernels_compile_tpu.py``
AOT-compiles them for the TPU without a chip.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_want_cache = os.environ.get("JAX_TEST_CACHE") != "0"
if _want_cache:
    # the CPU AOT cache loader logs TWO ERROR-level lines PER CACHE HIT
    # about XLA's prefer-no-scatter/gather pseudo-features (benign: they
    # are compiler preferences, not ISA features; verified level 2 does
    # not silence them). The cost of "3" is that other C++ ERROR logs are
    # also hidden during tests — export TF_CPP_MIN_LOG_LEVEL yourself (or
    # JAX_TEST_CACHE=0) when debugging a suspected XLA runtime failure.
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

import jax  # noqa: E402

# Persistent compilation cache: compiles dominate the suite and repeat
# identically across runs. Placed by the same helper as every entry point
# (JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache);
# JAX_TEST_CACHE=0 opts out.
if _want_cache:
    from tpu_voice_agent.utils.compilecache import place_compile_cache  # noqa: E402

    place_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

import pytest  # noqa: E402

# Two test tiers (round-2 VERDICT weak #7: the full suite is too slow to be
# a habit). Fast tier = the service/contract/unit tests plus the shared
# session-scoped engines: `pytest -m "not slow"` (< ~3 min on CPU). Slow
# tier = compile-heavy mesh/parity/model tests, auto-marked per module here
# (one central list instead of scattered pytestmark lines). The plain
# `pytest tests/` still runs EVERYTHING — the driver's green bar covers
# both tiers.
SLOW_MODULES = {
    "test_brain_planner",
    "test_ckpt",
    "test_colocate",
    "test_expert",
    "test_hf_real",
    "test_longctx",
    "test_multihost",
    "test_ops_sharded",
    "test_pipeline",
    "test_qwen2vl",
    "test_races",
    "test_ring",
    "test_stt",
    "test_whisper",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.fspath.purebasename in SLOW_MODULES:
            item.add_marker(pytest.mark.slow)


@pytest.fixture(scope="session")
def tiny_engine():
    """Shared tiny random-weight engine (compile once per test session)."""
    from tpu_voice_agent.serve import DecodeEngine

    return DecodeEngine(preset="test-tiny", max_len=2048, prefill_buckets=(64, 128, 256, 512, 1024))


@pytest.fixture(scope="session")
def tiny_batch_engine():
    from tpu_voice_agent.serve import DecodeEngine

    return DecodeEngine(
        preset="test-tiny", max_len=1024, batch_slots=3, prefill_buckets=(64, 128, 256, 512)
    )


@pytest.fixture(scope="session")
def distilled_intent():
    """(cfg, params) of the in-tree DISTILLED intent checkpoint, for tests that
    need a parse to be an ANSWER: ``_result_to_response`` refuses a decode
    that does not reach EOS, and random weights never reach it."""
    from tpu_voice_agent.models.llama import LlamaConfig
    from tpu_voice_agent.train import distill

    loaded = distill.load_ckpt("checkpoints", distill.INTENT_CKPT, LlamaConfig)
    if loaded is None:
        pytest.skip("trained checkpoints not present (run python -m tpu_voice_agent.train.make_tiny_ckpts)")
    return loaded
