"""Quantized paged KV (KV_QUANT=int8|int4) + fused grammar-mask→sample
decode tail (ISSUE 12) — FAST tier.

The storage contract (ops/kvquant.py): the paged pool stores per-(position,
kv_head) scaled int8 (or packed int4) values, quantized ONCE at write time
(deterministic rowwise math shared by the in-forward scatter and the host
prefix/tail scatter), with the bf16 scale planes pool-indexed by block id —
so radix sharing and the warm-restart reserve path carry scales with the
block for free. ``KV_QUANT`` unset keeps the bf16 pool byte-identical,
differentially tested like ``RADIX_ENABLE`` before it.

The accuracy contract is the golden differential (evals/golden.py
``kv_quant_differential``): int8 token-identical on the golden set with the
distilled checkpoint, int4 held to a pinned intent-type-agreement floor,
both grammar-valid always.

The fused decode tail (ops/grammar_mask.py): grammar mask + argmax + FSM
advance in ONE Pallas call (``masked_argmax_advance``) — parity-tested
against the XLA reference path it replaces.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_voice_agent.grammar.fsm import fsm_advance
from tpu_voice_agent.serve import DecodeEngine, PagedDecodeEngine
from tpu_voice_agent.serve.scheduler import ContinuousBatcher
from tpu_voice_agent.services.brain import (
    SessionTranscripts,
    install_prompt_prefix,
)
from tpu_voice_agent.services.prompts import render_prompt
from tpu_voice_agent.utils import chaos, get_metrics
from tpu_voice_agent.utils.costmodel import decode_step_bytes
from tpu_voice_agent.utils.hbmledger import (
    engine_hbm_plan,
    measure_hbm,
)

BUCKETS = (128, 256, 512, 1024, 2048)
PROMPT_TEXTS = ["search for usb hubs", "scroll down"]
MAXTOK = 48


def _paged(kv_quant, radix=False, **kw):
    eng = PagedDecodeEngine(
        preset="test-tiny", max_len=2048, batch_slots=2,
        prefill_buckets=BUCKETS, radix_enable=radix,
        kv_quant=kv_quant, **kw)
    install_prompt_prefix(eng)
    return eng


def _run(eng, prompts, max_new=MAXTOK):
    return ContinuousBatcher(eng, chunk_steps=8,
                             max_new_tokens=max_new).generate_many(prompts)


@pytest.fixture(scope="module")
def prompts():
    return [render_prompt(t, {}) for t in PROMPT_TEXTS]


@pytest.fixture(scope="module")
def eng_int8():
    return _paged("int8")


@pytest.fixture(scope="module")
def int8_baseline(eng_int8, prompts):
    res = _run(eng_int8, prompts)
    assert all(r.error is None for r in res)
    return res


# ------------------------------------------------------------ value layout


def test_kvquant_roundtrip_and_pack():
    from tpu_voice_agent.ops.kvquant import (
        dequantize_kv,
        pack_int4,
        quantize_kv,
        unpack_int4,
    )

    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 2, 32))
    for tier, tol in (("int8", 2.5e-2), ("int4", 3.5e-1)):
        q, s = quantize_kv(x, tier)
        assert q.dtype == jnp.int8 and s.dtype == jnp.bfloat16
        assert s.shape == x.shape[:-1]
        xd = dequantize_kv(q, s, tier)
        assert float(jnp.max(jnp.abs(xd.astype(jnp.float32) - x))) < tol
        # determinism: the same fp rows always produce the same stored
        # bytes (what makes prefill-written and decode-written KV bitwise
        # comparable at the differential suites' level)
        q2, s2 = quantize_kv(x, tier)
        assert bool((q2 == q).all()) and bool((s2 == s).all())
    # int4 packing: low nibble dims [0, hd/2), high nibble [hd/2, hd),
    # arithmetic-shift decode sign-extends exactly
    q8 = jnp.clip(jax.random.randint(jax.random.PRNGKey(1), (4, 8), -7, 8),
                  -7, 7).astype(jnp.int8)
    assert (unpack_int4(pack_int4(q8)) == q8).all()
    # all-zero rows quantize through the guarded scale, not a NaN
    q, s = quantize_kv(jnp.zeros((2, 4)), "int8")
    assert bool((q == 0).all()) and bool(jnp.isfinite(s.astype(jnp.float32)).all())


def test_kv_block_bytes_capacity_ratios():
    """The tentpole's capacity claim as pure accounting: at serving head
    dims a fixed HBM budget holds >= 1.9x the blocks under int8 and
    >= 3.5x under int4 (scale overhead included — the ratio is NOT a clean
    2x/4x and the ledger must use the honest number)."""
    from tpu_voice_agent.ops.kvquant import kv_block_bytes, kv_quant_bits

    assert (kv_quant_bits(None), kv_quant_bits("int8"),
            kv_quant_bits("int4")) == (16, 8, 4)
    for hd in (64, 128):
        off = kv_block_bytes(22, 128, 4, hd, None)
        i8 = kv_block_bytes(22, 128, 4, hd, "int8")
        i4 = kv_block_bytes(22, 128, 4, hd, "int4")
        assert off == 2 * 22 * 128 * 4 * hd * 2
        budget = 512 * off  # a 512-block bf16 budget
        assert (budget // i8) / (budget // off) >= 1.9
        assert (budget // i4) / (budget // off) >= 3.5


def test_decode_step_bytes_cpu_harness_proxy():
    """The decode-stage wall proxy (decode is HBM-bound, wall ∝ bytes
    moved): at the swarm shape — batched decode, ~2k context — int8 KV
    moves >= 1.5x fewer total bytes per step, int4 >= 2x. This is the
    acceptance scoreboard's CPU-harness stand-in for `engine.step.*`."""
    cfg = DecodeEngine(preset="test-tiny", max_len=128, prefill_buckets=(64,),
                       init_weights=False).cfg
    # the bench config's serving dims (docs/PERF.md "What the floor is")
    cfg = cfg.__class__(**{**cfg.__dict__, "dim": 2048, "ffn_dim": 5632,
                           "n_layers": 22, "n_heads": 32, "n_kv_heads": 4})
    off = decode_step_bytes(cfg, batch=64, context_tokens=2048)
    i8 = decode_step_bytes(cfg, batch=64, context_tokens=2048,
                           kv_quant="int8")
    i4 = decode_step_bytes(cfg, batch=64, context_tokens=2048,
                           kv_quant="int4")
    assert off["weights_bytes"] == i8["weights_bytes"]  # weights untouched
    assert off["total_bytes"] / i8["total_bytes"] >= 1.5
    assert off["total_bytes"] / i4["total_bytes"] >= 2.0
    # KV-only ratio matches the block-bytes accounting (~1.94x / ~3.8x)
    assert off["kv_read_bytes"] / i8["kv_read_bytes"] == pytest.approx(
        128 / 66, rel=1e-6)


# ------------------------------------------------------------ fused kernels


@pytest.mark.parametrize("bits", [8, 4])
def test_paged_attention_quant_kernel_parity(bits):
    """The fused-dequant decode kernel == dequantize-then-reference, int8
    and packed int4, ragged kv_len, both layers."""
    from tpu_voice_agent.ops import paged_attention_quant
    from tpu_voice_agent.ops.kvquant import quantize_kv
    from tpu_voice_agent.ops.paged_attention import (
        paged_attention_quant_reference,
    )

    tier = "int8" if bits == 8 else "int4"
    L, N, bs, B, nq, nkv, hd = 2, 8, 16, 3, 8, 4, 32
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, nq, hd), jnp.float32)
    kf = jax.random.normal(ks[1], (L, N, bs, nkv, hd), jnp.float32)
    vf = jax.random.normal(ks[2], (L, N, bs, nkv, hd), jnp.float32)
    k_pool, k_scale = quantize_kv(kf, tier)
    v_pool, v_scale = quantize_kv(vf, tier)
    tables = jnp.asarray([[3, 7, 1], [5, 2, 6], [4, 0, 2]], jnp.int32)
    kv_len = jnp.asarray([5, 33, 48], jnp.int32)
    for layer in (0, 1):
        ref = paged_attention_quant_reference(
            q, k_pool, v_pool, k_scale, v_scale, tables, kv_len, layer,
            bits=bits)
        out = paged_attention_quant(
            q, k_pool, v_pool, k_scale, v_scale, tables, kv_len,
            jnp.int32(layer), bits=bits)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("bits", [8, 4])
def test_paged_block_attention_quant_kernel_parity(bits):
    from tpu_voice_agent.ops import paged_block_attention_quant
    from tpu_voice_agent.ops.kvquant import quantize_kv
    from tpu_voice_agent.ops.paged_attention import (
        paged_block_attention_quant_reference,
    )

    tier = "int8" if bits == 8 else "int4"
    L, N, bs, B, T, nq, nkv, hd = 1, 6, 16, 2, 3, 8, 4, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, T, nq, hd), jnp.float32)
    kf = jax.random.normal(ks[1], (L, N, bs, nkv, hd), jnp.float32)
    vf = jax.random.normal(ks[2], (L, N, bs, nkv, hd), jnp.float32)
    k_pool, k_scale = quantize_kv(kf, tier)
    v_pool, v_scale = quantize_kv(vf, tier)
    tables = jnp.asarray([[3, 1, 5], [2, 4, 0]], jnp.int32)
    positions = jnp.asarray([[17, 18, 19], [30, 31, 32]], jnp.int32)
    ref = paged_block_attention_quant_reference(
        q, k_pool, v_pool, k_scale, v_scale, tables, positions,
        jnp.int32(0), bits=bits)
    out = paged_block_attention_quant(
        q, k_pool, v_pool, k_scale, v_scale, tables, positions,
        jnp.int32(0), bits=bits)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("bits", [8, 4])
def test_decode_attention_quant_kernel_parity(bits):
    """The dense-cache fused-dequant twin (same _qk_dot/_pv_dot packed
    arithmetic as the paged kernels — one copy, both proven here)."""
    from tpu_voice_agent.ops import decode_attention_quant
    from tpu_voice_agent.ops.decode_attention import (
        decode_attention_quant_reference,
    )
    from tpu_voice_agent.ops.kvquant import quantize_kv

    tier = "int8" if bits == 8 else "int4"
    B, S, nq, nkv, hd = 3, 256, 8, 4, 32
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (B, nq, hd), jnp.float32)
    kf = jax.random.normal(ks[1], (B, S, nkv, hd), jnp.float32)
    vf = jax.random.normal(ks[2], (B, S, nkv, hd), jnp.float32)
    k_cache, k_scale = quantize_kv(kf, tier)
    v_cache, v_scale = quantize_kv(vf, tier)
    kv_len = jnp.asarray([5, 133, 256], jnp.int32)
    ref = decode_attention_quant_reference(
        q, k_cache, v_cache, k_scale, v_scale, kv_len, bits=bits)
    out = decode_attention_quant(
        q, k_cache, v_cache, k_scale, v_scale, kv_len, bits=bits,
        block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.fixture(scope="module")
def tiny_tables():
    eng = DecodeEngine(preset="test-tiny", max_len=128, prefill_buckets=(64,),
                       init_weights=False)
    return eng.tables, eng.cfg.vocab_size


def test_masked_argmax_advance_fuses_mask_argmax_and_fsm(tiny_tables):
    """ONE kernel == the three-op chain it replaces (mask -> argmax ->
    fsm_advance) on the engine's real grammar tables, including the
    clamped dead-state contract the poison gate relies on."""
    from tpu_voice_agent.ops import (
        masked_argmax,
        masked_argmax_advance,
        masked_argmax_advance_reference,
    )

    tables, V = tiny_tables
    assert tables.dense_mask is not None
    S = tables.dense_mask.shape[0]
    B = 8
    logits = jax.random.normal(jax.random.PRNGKey(11), (B, V), jnp.float32)
    states = jnp.asarray([0, 1, S - 1, 2, 0, 5 % S, -1, 3 % S], jnp.int32)
    tok, nxt = masked_argmax_advance(
        logits, states, tables.dense_mask, tables.table, tables.col_id)
    rtok, rnxt = masked_argmax_advance_reference(
        logits, states, tables.dense_mask, tables.table, tables.col_id)
    assert (np.asarray(tok) == np.asarray(rtok)).all()
    assert (np.asarray(nxt) == np.asarray(rnxt)).all()
    # live rows: exactly the unfused chain
    live = np.asarray(states) >= 0
    chain_tok = masked_argmax(logits, jnp.maximum(states, 0),
                              tables.dense_mask)
    chain_nxt = fsm_advance(tables, jnp.maximum(states, 0), chain_tok)
    assert (np.asarray(tok)[live] == np.asarray(chain_tok)[live]).all()
    assert (np.asarray(nxt)[live] == np.asarray(chain_nxt)[live]).all()


# ------------------------------------------------------------ engine gating


def test_kv_quant_unset_keeps_bf16_pool(monkeypatch):
    """KV_QUANT unset: bf16 pool, no scale planes, no quant branches —
    the byte-identical contract's structural half (the behavioral half is
    every pre-existing paged test running on this default path)."""
    monkeypatch.delenv("KV_QUANT", raising=False)
    eng = PagedDecodeEngine(preset="test-tiny", max_len=512,
                            prefill_buckets=(64,), init_weights=False)
    assert eng.kv_quant is None and eng.kv_quant_bits == 16
    assert eng.k_pool.dtype == jnp.bfloat16
    assert eng.k_scale is None and eng.v_scale is None


def test_kv_quant_env_knob_and_validation(monkeypatch):
    monkeypatch.setenv("KV_QUANT", "int8")
    eng = PagedDecodeEngine(preset="test-tiny", max_len=512,
                            prefill_buckets=(64,), init_weights=False)
    assert eng.kv_quant == "int8" and eng.k_pool.dtype == jnp.int8
    assert eng.k_scale is not None and eng.k_scale.dtype == jnp.bfloat16
    # stored last axis: full head_dim int8, half packed int4
    hd = eng.cfg.head_dim
    assert eng.k_pool.shape[-1] == hd
    monkeypatch.setenv("KV_QUANT", "int4")
    eng4 = PagedDecodeEngine(preset="test-tiny", max_len=512,
                             prefill_buckets=(64,), init_weights=False)
    assert eng4.k_pool.shape[-1] == hd // 2
    monkeypatch.setenv("KV_QUANT", "fp8")
    with pytest.raises(ValueError, match="KV_QUANT"):
        PagedDecodeEngine(preset="test-tiny", max_len=512,
                          prefill_buckets=(64,), init_weights=False)


# ------------------------------------------------------ int8 differentials

TURNS = [
    ("search for wireless headphones", {}),
    ("open the second result", {"last_query": "wireless headphones"}),
    ("sort these by price from low to high", {"last_query": "wireless headphones"}),
]


def _play_session(eng, turns=TURNS, max_new=MAXTOK):
    tok = eng.tokenizer
    st = SessionTranscripts(tok)
    results = []
    for text, ctx in turns:
        prompt = st.prompt_for("sess", text, ctx)
        ids = (tok.encode(prompt, bos=True) if isinstance(prompt, str)
               else list(prompt))
        r = _run(eng, [ids], max_new=max_new)[0]
        assert r.error is None, r.error
        results.append(r)
        st.record("sess", ids, r.token_ids)
    return results


def test_int8_radix_warm_cold_identity(eng_int8):
    """Radix chains share QUANTIZED blocks (scales travel with the block):
    warm admissions served from int8 cached chains are token-identical to
    int8 cold admissions — decode-written and prefill-written quantized KV
    are bitwise equal, same contract as the bf16 pool."""
    warm_eng = _paged("int8", radix=True)
    cold = _play_session(eng_int8)
    warm = _play_session(warm_eng)
    P = len(warm_eng.prefix_ids)
    for c, w in zip(cold, warm):
        assert c.token_ids == w.token_ids
        assert warm_eng.fsm.walk(w.token_ids) >= 0
    assert warm[0].cached_tokens == P       # turn 1: static prefix only
    assert warm[1].cached_tokens > P        # turn 2+: quantized chain hit
    # full replay FROM the cached quantized chains: still identical
    warm2 = _play_session(warm_eng)
    for c, w in zip(cold, warm2):
        assert c.token_ids == w.token_ids


def test_int8_chaos_nan_quarantines_alone(eng_int8, prompts, int8_baseline):
    """The chaos quarantine drill on the quantized plane: a NaN-poisoned
    row evicts alone, its batch-mate token-identical, zero leaked blocks."""
    counters = get_metrics().snapshot()["counters"]
    before = counters.get("scheduler.slots_quarantined", 0)
    eng = _paged("int8")
    b = ContinuousBatcher(eng, chunk_steps=8, max_new_tokens=MAXTOK)
    chaos.configure("nan_logits@2")
    try:
        res = b.generate_many(prompts)
    finally:
        chaos.reset()
    assert res[1].error is not None and \
        res[1].error.startswith("poisoned: non-finite"), res[1].error
    assert res[0].error is None
    assert res[0].token_ids == int8_baseline[0].token_ids
    after = get_metrics().snapshot()["counters"]["scheduler.slots_quarantined"]
    assert after == before + 1
    assert eng.allocator.blocks_in_use == len(eng._prefix_blocks[0])


def test_int8_warm_restart_readopts_quantized_prefix(eng_int8, prompts,
                                                     int8_baseline):
    """warm_restart keeps the quantized pool arrays AND scale planes;
    reserve() re-adopts the static-prefix blocks whose scales are pool-
    indexed — post-restart output identical, prefix still served from
    cache, sentinel quiet contract covered by test_steplog elsewhere."""
    from tpu_voice_agent.utils.compilewatch import get_compile_watcher

    eng = _paged("int8")
    first = _run(eng, prompts)
    for ref, r in zip(int8_baseline, first):
        assert r.error is None and r.token_ids == ref.token_ids
    eng.warm_restart()  # arms the recompile-sentinel fence
    fence_before = get_compile_watcher().state()["post_fence_compiles"]
    again = _run(eng, prompts)
    for ref, r in zip(int8_baseline, again):
        assert r.error is None and r.token_ids == ref.token_ids
        assert r.cached_tokens == len(eng.prefix_ids)
    # the acceptance bar's sentinel half: the quantized plane's jitted
    # entry points (scatter twin, quant forward, fused tail) all come back
    # at their warmed shapes — zero compiles past the fence
    assert get_compile_watcher().state()["post_fence_compiles"] == \
        fence_before


# ------------------------------------------------------------ accounting


@pytest.mark.parametrize("tier", [None, "int8", "int4"])
def test_hbm_plan_matches_measured_kv(tier):
    """hbm.plan_drift ~ 0 under every tier: the static plan's KV bytes
    equal the measured pool + scale planes exactly (the satellite that
    kills the phantom 2-4x drift a bf16-assumed plan would flag)."""
    eng = PagedDecodeEngine(preset="test-tiny", max_len=512, batch_slots=2,
                            prefill_buckets=(64,), kv_quant=tier,
                            init_weights=False)
    plan = engine_hbm_plan(eng)
    measured = measure_hbm(eng)
    assert plan["kv_pool_bytes"] == measured["kv_pool_bytes"]
    assert eng.kv_bytes_per_block * eng.allocator.n_blocks == \
        plan["kv_pool_bytes"]


def test_pool_gauges_bytes_view(eng_int8):
    """record_pool_gauges with the engine exports the bytes-denominated
    view (satellite: block counts stopped being a unit of HBM); the one
    host-dispatched instance of the fused tail is the
    ``.first_token_call`` part of every admission the batcher runs above
    recorded (it replaced the gauge that timed the same dispatch)."""
    from tpu_voice_agent.serve.paged import record_pool_gauges

    record_pool_gauges(eng_int8.allocator, engine=eng_int8)
    g = get_metrics().snapshot()["gauges"]
    assert g["paged.kv_quant_bits"] == 8.0
    assert g["paged.kv_bytes_per_block"] == float(eng_int8.kv_bytes_per_block)
    assert g["paged.kv_bytes_total"] == pytest.approx(
        g["paged.kv_blocks_total"] * eng_int8.kv_bytes_per_block)
    assert g["paged.kv_bytes_used"] == pytest.approx(
        g["paged.kv_blocks_used"] * eng_int8.kv_bytes_per_block)
    # paged.kv_utilization stays a FRACTION of one uniform-block pool —
    # invariant under bytes-per-block, so the degradation ladder's
    # measured-thrash trigger (PoolExhausted -> RADIX_PRESSURE_S window)
    # needs no re-expression; the bytes gauges are the dashboard unit
    assert 0.0 <= g["paged.kv_utilization"] <= 1.0
    from tpu_voice_agent.utils import get_steplog

    # the steplog is the process's ring: read the admissions of a run made
    # HERE (whatever is left in it from another file's engine — a grouped
    # admission has no tail of its own — is none of this test's)
    import time

    t0 = time.time()
    assert all(r.error is None for r in _run(eng_int8, [render_prompt("go back", {})], max_new=4))
    adm = [a for s in get_steplog().steps() if s["t_s"] >= t0 for a in s.get("admissions", [])]
    assert adm and all(a["first_token_call_ms"] > 0 for a in adm)
    assert get_metrics().collisions() == []


# ------------------------------------------------------------ golden floors


def test_golden_kv_quant_differential_distilled_floors():
    """The pinned lossy-tier accuracy budget on the TRAINED tiny
    checkpoint (random-weight margins are razor-thin and would pin noise):
    int8 token-identical AND intent-type-identical on the golden subset;
    int4 holds the type-agreement floor with every output grammar-valid."""
    from tpu_voice_agent.evals.golden import (
        GOLDEN_INTENT_CASES,
        kv_quant_differential,
    )
    from tpu_voice_agent.models.llama import LlamaConfig
    from tpu_voice_agent.train import distill

    cfg, params = distill.load_ckpt("checkpoints", distill.INTENT_CKPT,
                                    LlamaConfig)
    device_params = jax.device_put(params)

    def make_engine(tier):
        eng = PagedDecodeEngine(cfg=cfg, max_len=2048, batch_slots=2,
                                prefill_buckets=(256, 512, 1024),
                                kv_quant=tier, init_weights=False)
        eng.load_params(device_params)
        install_prompt_prefix(eng)
        return eng

    out = kv_quant_differential(make_engine, GOLDEN_INTENT_CASES[:6])
    assert out["cases"] == 6
    i8, i4 = out["tiers"]["int8"], out["tiers"]["int4"]
    assert i8["token_identical"] == 1.0
    assert i8["type_agreement"] == 1.0
    assert i8["grammar_valid"] == 1.0
    assert i4["grammar_valid"] == 1.0
    assert i4["type_agreement"] >= 0.5
