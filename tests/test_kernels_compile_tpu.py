"""AOT-compile every public ``ops.*`` kernel for the TPU — without a TPU.

libtpu is installed next to jax, and ``jax.experimental.topologies`` hands
out abstract ``TPU v5 lite`` devices under ``JAX_PLATFORMS=cpu``; lowering a
function whose arguments carry a sharding on one of them and calling
``.compile()`` runs the real XLA:TPU and Mosaic compilers. Interpret mode
(what every other kernel test here uses) accepts programs Mosaic refuses —
``masked_argmax_advance`` shipped that way for nine PRs — so a kernel that
has only ever been interpreted cannot land again.

Compiling is not running: numerics on the chip are ``chip_smoke.py``'s job.

Shapes are the published head geometries of the three model families the
repo serves: TinyLlama-1.1B (32 q / 4 kv heads of 64, 22 layers), Llama-3-8B
(32 q / 8 kv heads of 128, 32 layers) and Whisper-large-v3 (20 heads of 64,
1500 encoder positions, 448 text positions). The kernels the voice->intent
main path reaches run in the fast tier; the rest are ``slow``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from tpu_voice_agent import ops

BF16, I8, I32, F32 = jnp.bfloat16, jnp.int8, jnp.int32, jnp.float32

# (n_q_heads, n_kv_heads, head_dim, n_layers)
TINYLLAMA = (32, 4, 64, 22)
LLAMA3_8B = (32, 8, 128, 32)
WHISPER_V3 = (20, 20, 64, 32)
# the in-tree intent grammar over the in-tree tokenizer (what every random-
# weight engine decodes under): states x classes, vocab
FSM_STATES, FSM_CLASSES, VOCAB = 35449, 310, 619
BLOCK, MAX_BLOCKS = 128, 16  # serve.paged defaults: 128-token blocks, max_len 2048
FF_T = 9  # a grammar fast-forward step: current token + BRAIN_FF=8 chain tokens


@pytest.fixture(scope="module")
def tpu_devices():
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo.devices


def _compile(devices, fn, *args, mesh_specs=None, mesh=None, **static):
    """Lower + compile ``fn(*args)`` for the abstract TPU. ``args`` are
    (shape, dtype) pairs; with ``mesh`` each takes the matching
    PartitionSpec from ``mesh_specs``, otherwise all sit on device 0."""
    if mesh is None:
        shardings = [SingleDeviceSharding(devices[0])] * len(args)
    else:
        shardings = [NamedSharding(mesh, s) for s in mesh_specs]
    sds = [jax.ShapeDtypeStruct(shape, dt, sharding=sh)
           for (shape, dt), sh in zip(args, shardings)]
    return jax.jit(functools.partial(fn, **static)).lower(*sds).compile()


def _dense_cache(B, S, geom, stacked):
    _, nkv, hd, L = geom
    shape = (L, B, S, nkv, hd) if stacked else (B, S, nkv, hd)
    return (shape, BF16), (shape, BF16)


def _pool(N, geom, hdp=None, dtype=BF16):
    _, nkv, hd, L = geom
    shape = (L, N, BLOCK, nkv, hdp or hd)
    return (shape, dtype), (shape, dtype)


def _scales(N, geom):
    _, nkv, _, L = geom
    shape = (L, N, BLOCK, nkv)
    return (shape, BF16), (shape, BF16)


# ------------------------------------------------------------- main path


@pytest.mark.parametrize("T,causal,geom", [
    # Whisper-large-v3 encoder: the full 30 s window, a 10 s final bucket,
    # and the two incremental block widths (0.5 s anchor, 0.7 s with lookback)
    (1500, False, WHISPER_V3), (500, False, WHISPER_V3),
    (25, False, WHISPER_V3), (35, False, WHISPER_V3),
    # TinyLlama prompt-prefix prefill at the 1024 bucket
    (1024, True, TINYLLAMA),
])
def test_flash_attention_compiles(tpu_devices, T, causal, geom):
    nq, nkv, hd, _ = geom
    _compile(tpu_devices, ops.flash_attention,
             ((1, T, nq, hd), BF16), ((1, T, nkv, hd), BF16),
             ((1, T, nkv, hd), BF16), causal=causal, interpret=False)


@pytest.mark.parametrize("S", [448, 1500, 500, 150])
def test_decode_attention_compiles_whisper(tpu_devices, S):
    """The Whisper decoder's T=1 step: self-attention over the 448-slot text
    cache and cross-attention over an utterance's encoder frames."""
    nq, _, hd, _ = WHISPER_V3
    _compile(tpu_devices, ops.decode_attention, ((1, nq, hd), BF16),
             *_dense_cache(1, S, WHISPER_V3, stacked=False), ((1,), I32),
             interpret=False)


def test_decode_attention_layer_compiles(tpu_devices):
    nq, _, hd, _ = TINYLLAMA
    _compile(tpu_devices, ops.decode_attention_layer, ((4, nq, hd), BF16),
             *_dense_cache(4, 1024, TINYLLAMA, stacked=True), ((4,), I32),
             ((), I32), interpret=False)


def test_decode_block_attention_layer_compiles(tpu_devices):
    nq, _, hd, _ = TINYLLAMA
    _compile(tpu_devices, ops.decode_block_attention_layer,
             ((4, FF_T, nq, hd), BF16),
             *_dense_cache(4, 1024, TINYLLAMA, stacked=True),
             ((4, FF_T), I32), ((), I32), interpret=False)


def test_paged_attention_compiles(tpu_devices):
    nq, _, hd, _ = TINYLLAMA
    _compile(tpu_devices, ops.paged_attention, ((4, nq, hd), BF16),
             *_pool(4 * MAX_BLOCKS + 1, TINYLLAMA), ((4, MAX_BLOCKS), I32),
             ((4,), I32), ((), I32), interpret=False)


def test_paged_block_attention_compiles(tpu_devices):
    nq, _, hd, _ = TINYLLAMA
    _compile(tpu_devices, ops.paged_block_attention, ((4, FF_T, nq, hd), BF16),
             *_pool(4 * MAX_BLOCKS + 1, TINYLLAMA), ((4, MAX_BLOCKS), I32),
             ((4, FF_T), I32), ((), I32), interpret=False)


def test_masked_argmax_compiles(tpu_devices):
    _compile(tpu_devices, ops.masked_argmax, ((4, VOCAB), F32), ((4,), I32),
             ((FSM_STATES, VOCAB), jnp.bool_), interpret=False)


def test_masked_argmax_advance_compiles(tpu_devices):
    _compile(tpu_devices, ops.masked_argmax_advance, ((4, VOCAB), F32),
             ((4,), I32), ((FSM_STATES, VOCAB), jnp.bool_),
             ((FSM_STATES, FSM_CLASSES), I32), ((VOCAB,), I32), interpret=False)


# ------------------------------------------------------- the rest (slow)


@pytest.mark.slow
@pytest.mark.parametrize("geom", [TINYLLAMA, LLAMA3_8B])
def test_llama_dense_kernels_compile(tpu_devices, geom):
    nq, nkv, hd, _ = geom
    B, S = 4, 2048
    flat = _dense_cache(B, S, geom, stacked=False)
    stacked = _dense_cache(B, S, geom, stacked=True)
    _compile(tpu_devices, ops.flash_attention, ((1, 2048, nq, hd), BF16),
             ((1, 2048, nkv, hd), BF16), ((1, 2048, nkv, hd), BF16),
             causal=True, interpret=False)
    _compile(tpu_devices, ops.decode_attention, ((B, nq, hd), BF16), *flat,
             ((B,), I32), interpret=False)
    _compile(tpu_devices, ops.decode_attention_layer, ((B, nq, hd), BF16),
             *stacked, ((B,), I32), ((), I32), interpret=False)
    _compile(tpu_devices, ops.decode_block_attention, ((B, FF_T, nq, hd), BF16),
             *flat, ((B, FF_T), I32), interpret=False)
    _compile(tpu_devices, ops.decode_block_attention_layer,
             ((B, FF_T, nq, hd), BF16), *stacked, ((B, FF_T), I32), ((), I32),
             interpret=False)


@pytest.mark.slow
@pytest.mark.parametrize("geom", [TINYLLAMA, LLAMA3_8B])
def test_llama_paged_kernels_compile(tpu_devices, geom):
    nq, _, hd, _ = geom
    B, N = 4, 4 * MAX_BLOCKS + 1
    tables, lens, layer = ((B, MAX_BLOCKS), I32), ((B,), I32), ((), I32)
    _compile(tpu_devices, ops.paged_attention, ((B, nq, hd), BF16),
             *_pool(N, geom), tables, lens, layer, interpret=False)
    _compile(tpu_devices, ops.paged_block_attention, ((B, FF_T, nq, hd), BF16),
             *_pool(N, geom), tables, ((B, FF_T), I32), layer, interpret=False)


@pytest.mark.slow
@pytest.mark.parametrize("geom", [TINYLLAMA, LLAMA3_8B])
@pytest.mark.parametrize("bits", [8, 4])
def test_quantized_kv_kernels_compile(tpu_devices, geom, bits):
    nq, nkv, hd, _ = geom
    B, N, S = 4, 4 * MAX_BLOCKS + 1, 2048
    hdp = hd if bits == 8 else hd // 2
    tables, lens, layer = ((B, MAX_BLOCKS), I32), ((B,), I32), ((), I32)
    _compile(tpu_devices, ops.paged_attention_quant, ((B, nq, hd), BF16),
             *_pool(N, geom, hdp, I8), *_scales(N, geom), tables, lens, layer,
             bits=bits, interpret=False)
    _compile(tpu_devices, ops.paged_block_attention_quant,
             ((B, FF_T, nq, hd), BF16), *_pool(N, geom, hdp, I8),
             *_scales(N, geom), tables, ((B, FF_T), I32), layer,
             bits=bits, interpret=False)
    _compile(tpu_devices, ops.decode_attention_quant, ((B, nq, hd), BF16),
             ((B, S, nkv, hdp), I8), ((B, S, nkv, hdp), I8),
             ((B, S, nkv), BF16), ((B, S, nkv), BF16), lens,
             bits=bits, interpret=False)


@pytest.mark.slow
@pytest.mark.parametrize("d,f", [(2048, 5632), (4096, 14336)])
def test_grouped_matmul_compiles(tpu_devices, d, f):
    """MoE expert dispatch at TinyLlama / Llama-3-8B (Mixtral) FFN widths,
    8 experts, 1024 expert-sorted rows."""
    M, E, tm = 1024, 8, 128
    _compile(tpu_devices, ops.grouped_matmul, ((M, d), BF16), ((E, d, f), BF16),
             ((M // tm,), I32), tm=tm, interpret=False)


# OLMoE-1B-7B-0125 (the benchmark's olmoe_flood cell): 16 q / 16 kv heads of
# 128 — group 1, so a fast-forward block is 9 query rows a head where
# Mistral's is 36 — 16 layers, 64 experts of 2048 x 1024 in int8
OLMOE = (16, 16, 128, 16)


@pytest.mark.parametrize("rows,tm", [(288, 64), (32, 16), (1024, 128)])
def test_grouped_matmul_int8_stacked_compiles_at_olmoe_widths(tpu_devices, rows, tm):
    """The served expert dispatch: the STACKED int8 leaf and its scales go to
    the kernel whole, with the layer and the tiles' experts in the scalar
    prefetch, at the decode forward's, a suffix prefill's and the prefix
    prefill's token counts and the row tile the program picks for each."""
    from tpu_voice_agent.models.llama import moe_row_tile

    L, E, d, f, K = 16, 64, 2048, 1024, 8
    assert moe_row_tile(rows * K, E) == tm
    n = -(-(rows * K + min(E, rows * K) * (tm - 1)) // tm)
    sh = SingleDeviceSharding(tpu_devices[0])
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)
    for din, dout in ((d, f), (f, d)):  # gate / up, then down
        jax.jit(functools.partial(ops.grouped_matmul, tm=tm, interpret=False)).lower(
            S((n * tm, din), BF16), {"q": S((L, E, din, dout), I8), "s": S((L, E, 1, dout), F32)},
            S((n,), I32), S((), I32), S((), I32)).compile()


def test_paged_attention_compiles_at_group_one(tpu_devices):
    """Full multi-head attention (no grouping): 9 query rows a head in the
    fast-forward block, 1 in the T = 1 step, over the benchmark's pool."""
    nq, nkv, hd, L = OLMOE
    B, N, blocks = 32, 200, 12
    tables, layer = ((B, blocks), I32), ((), I32)
    _compile(tpu_devices, ops.paged_block_attention, ((B, FF_T, nq, hd), BF16),
             *_pool(N, OLMOE), tables, ((B, FF_T), I32), layer, interpret=False)
    _compile(tpu_devices, ops.paged_attention, ((B, nq, hd), BF16),
             *_pool(N, OLMOE), tables, ((B,), I32), layer, interpret=False)


@pytest.mark.parametrize("B,geom", [(32, LLAMA3_8B), (32, OLMOE), (8, LLAMA3_8B)],
                         ids=["parse_flood", "olmoe_flood", "parse_solo"])
def test_paged_block_attention_common_pass_compiles_at_the_cells_shapes(tpu_devices, B, geom):
    """The block kernel with its common pass (ISSUE 31) on the packed real
    positions (ISSUE 48) as the three cells run it: (rows, 9 queries, q / kv
    heads of 128) = (32, 9, 32 / 8) Mistral's full width, (32, 9, 16 / 16)
    OLMoE's, (8, 9, 32 / 8) the compacted width, over the benchmark's 200-block
    pool and 12-column tables, with the write mask and ``n_real`` handed down.
    Its dynamic grid, the VMEM it asks for beyond the default (the queries,
    their packed copy and its statistics stay resident), the sub-chunks'
    dynamic sublane slices, a rider's queries written to and its state read
    from ANY sublane offset are what interpret mode cannot refuse and Mosaic
    can."""
    nq, nkv, hd, L = geom
    N, blocks = 200, 12
    attend = lambda q, kp, vp, tables, pos, layer, live, n_real: ops.paged_block_attention(
        q, kp, vp, tables, pos, layer, live, None, None, n_real, interpret=False)
    _compile(tpu_devices, attend, ((B, FF_T, nq, hd), BF16),
             *_pool(N, geom), ((B, blocks), I32), ((B, FF_T), I32), ((), I32),
             ((B,), jnp.bool_), ((B,), I32))


@pytest.mark.parametrize("B,T", [(32, FF_T), (8, FF_T), (32, 1)],
                         ids=["moonlight_flood", "compacted", "a step"])
def test_paged_latent_attention_packed_passes_compile_at_the_cells_shapes(tpu_devices, B, T):
    """The latent kernel on the packed real positions (ISSUE 49) as
    ``moonlight_flood`` runs it: (rows, 1 + 8 positions, 16 heads) queries of
    512 + 64 over the cell's two planes and 12-column tables, with the write
    mask and ``n_real`` handed down. The packed copy of both query halves and
    its statistics beside the resident operands (42 MB at 32 rows, in ONE
    group), a position's rows copied to a dynamic tile offset, the sub-chunks'
    dynamic trip count and a rider's state read back from its packed place are
    what interpret mode cannot refuse and Mosaic can."""
    H, C, R, N, L = 16, 512, 64, 200, 17
    from tpu_voice_agent.ops import latent_attention

    assert latent_attention._rows_that_fit(B, T, H, C, R, 2) == B
    attend = lambda qc, qr, cp, rp, tables, pos, layer, live, n_real: ops.paged_latent_attention(
        qc, qr, cp, rp, tables, pos, layer, live, None, n_real, scale=192 ** -0.5, interpret=False)
    _compile(tpu_devices, attend, ((B, T, H, C), BF16), ((B, T, H, R), BF16),
             ((L, N, BLOCK, C), BF16), ((L, N, BLOCK, R), BF16), ((B, 12), I32), ((B, T), I32),
             ((), I32), ((B,), jnp.bool_), ((B,), I32))


def _conditionals(hlo: str) -> int:
    """``conditional`` instructions of a compiled program's text."""
    return hlo.count(" conditional(")


def _chunk_program_at_published_widths(tpu_devices, monkeypatch, config, compacted: bool):
    """The whole decode chunk at its COMPACTED width (ISSUE 29: 8 of 32 slots'
    rows, gathered and scattered back inside the program) or at its full one
    with the packed MLP (ISSUE 37), as the benchmark's
    configuration serves it — published widths, int8 weights, the 200-block
    pool, fast-forward 8, chunk 16, the real grammar tables — lowered on
    shapes and compiled by XLA:TPU and Mosaic. The dense model reaches
    ``paged_block_attention`` at 8 rows; the routed one also the grouped
    matmul at 72 tokens = 576 assignments and its row tile. No chip has run
    the routed variant at this width (PERF.md section 7, ``olmoe_solo``)."""
    import json
    import sys
    from pathlib import Path

    from benchmark.builders import olmoe_stack, parse_stack
    from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
    from tpu_voice_agent.serve import PagedDecodeEngine, paged

    for mod in ("paged_attention", "grouped_matmul"):  # the kernels ask where they run: not interpreted here
        monkeypatch.setattr(sys.modules[f"tpu_voice_agent.ops.{mod}"], "on_cpu", lambda: False)
    conf = json.loads((Path(__file__).parents[1] / "benchmark" / "configs" / f"{config}.json").read_text())
    dims = parse_stack.model_dims(conf, False)
    m, s = dims["model"], dims["serving"]
    routed = "num_experts" in m
    llama_config, make_params = ((olmoe_stack.llama_config, olmoe_stack.make_params) if routed else
                                 (parse_stack.dense_llama_config, parse_stack.make_decoder_params))
    eng = PagedDecodeEngine(  # the builder's engine, but for a two-block pool: the real one is a shape below
        cfg=llama_config(m, s), tokenizer=default_tokenizer(), quant=s["quant"], batch_slots=s["batch_slots"],
        block_size=s["block_size"], pool_blocks=2, max_len=s["max_len"],
        prefill_buckets=tuple(s["prefill_buckets"]), fast_forward=s["fast_forward"], init_weights=False)
    B, R, cfg = eng.batch_slots, eng.compact_rows, eng.cfg
    assert (B, R) == (32, 8) and (cfg.moe_impl == "grouped") == routed

    chip = SingleDeviceSharding(tpu_devices[0])
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    shapes = lambda tree: jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), tree)
    pool = S((cfg.n_layers, s["pool_blocks"], eng.block_size, cfg.n_kv_heads, cfg.head_dim), BF16)
    compiled = paged.paged_chunk_decode_loop.__wrapped__.lower(
        shapes(jax.eval_shape(lambda: make_params(cfg, s["weights_seed"]))), cfg, pool, pool,
        S((B, eng.max_blocks), I32), S((B,), I32), S((B,), I32), S((B,), I32), S((B,), jnp.bool_),
        S((B,), I32), S((B,), I32), shapes(eng.tables_ff), shapes(eng.byte_len_table),
        shapes(jax.random.PRNGKey(0)), S((), F32), S((), I32), trash_idx=S((B,), I32), rules=None,
        logit_mask=None if eng.logit_mask is None else shapes(eng.logit_mask),
        **({"rows_idx": S((R,), I32)} if compacted else {"ffn_pack": eng.ffn_pack_rows}),
        chunk_steps=16, greedy=True, constrained=True, kernels="pallas", eos_id=eng.eos_id,
        pad_id=eng.pad_id, max_len=eng.max_len, kv_quant=None, quality_lanes=eng.quality_lanes).compile()
    return compiled.as_text(), eng, routed


def _the_kv_write_alone_holds_a_planes_shape(text: str, eng, blocks: int = 200):
    """Of the compiled chunk program's ops, only the K/V write's in-place scatters (and the
    fusions they are the roots of) produce a K/V plane's or the pool's shape: the walk over
    tiles of the real rows (ISSUE 60) carries the pools through its ``while`` in place — no
    ``copy``, no slice of a plane (PRs 34, 58)."""
    from tools.kv_write_check import shaped_ops

    cfg = eng.cfg
    pool = (cfg.n_layers, blocks, eng.block_size, cfg.n_kv_heads, cfg.head_dim)
    found = shaped_ops(text, pool)
    assert found and set(found) <= {"fusion", "scatter"}, found


@pytest.mark.parametrize("config", ["mistral-7b-v0.1-int8", "olmoe-1b-7b-0125-int8"])
def test_the_compacted_chunk_program_compiles_at_published_widths(tpu_devices, monkeypatch, config):
    text, eng, routed = _chunk_program_at_published_widths(tpu_devices, monkeypatch, config, True)
    B, R = eng.batch_slots, eng.compact_rows
    assert text.count("tpu_custom_call") == (4 if routed else 1)  # block attention (+ gate, up, down)
    assert f"bf16[{R},9," in text and f"bf16[{B},9," not in text  # the forwards run at R rows
    _the_kv_write_alone_holds_a_planes_shape(text, eng)
    assert R * 9 <= eng.ffn_pack_rows and "conditional" not in text  # nothing to pack at this width


@pytest.mark.parametrize("config", ["mistral-7b-v0.1-int8",
                                    pytest.param("olmoe-1b-7b-0125-int8", marks=pytest.mark.slow)])
def test_the_packed_chunk_program_compiles_at_published_widths(tpu_devices, monkeypatch, config):
    """The FULL-width chunk program with its position-wise regions on the
    real positions (ISSUES 37, 41): 32 x 9 positions packed into
    ``ffn_pack_rows`` = 96 rows, two conditionals in the layer scan's body —
    q/k/v, and the output projection with the MLP — for the routed model the
    grouped kernel in BOTH branches of the second, at both row tiles."""
    text, eng, routed = _chunk_program_at_published_widths(tpu_devices, monkeypatch, config, False)
    B, P = eng.batch_slots, eng.ffn_pack_rows
    assert (B, P) == (32, 96) and _conditionals(text) == 2
    assert text.count("tpu_custom_call") == (7 if routed else 1)  # block attention (+ 3 a branch)
    assert f"bf16[{B},9," in text and f"bf16[{P}," in text  # both branches
    _the_kv_write_alone_holds_a_planes_shape(text, eng)


def _hybrid_engine(monkeypatch):
    """The ``phi4flash_flood`` cell's engine (published widths, a two-block
    pool: the real one is a shape below), its configuration file's sizes and
    abstract weights, with the kernels told they are not interpreted."""
    import json
    import sys
    from pathlib import Path

    from benchmark.builders import sambay_stack
    from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
    from tpu_voice_agent.serve import PagedDecodeEngine

    for mod in ("paged_attention", "selective_scan"):
        monkeypatch.setattr(sys.modules[f"tpu_voice_agent.ops.{mod}"], "on_cpu", lambda: False)
    conf = json.loads((Path(__file__).parents[1] / "benchmark" / "configs"
                       / "phi-4-mini-flash-reasoning-int8.json").read_text())
    dims = sambay_stack.model_dims(conf, False)
    m, s = dims["model"], dims["serving"]
    eng = PagedDecodeEngine(
        cfg=sambay_stack.sambay_config(m, s), tokenizer=default_tokenizer(), quant=s["quant"],
        batch_slots=s["batch_slots"], block_size=s["block_size"], pool_blocks=2, max_len=s["max_len"],
        prefill_buckets=tuple(s["prefill_buckets"]), fast_forward=s["fast_forward"], init_weights=False)
    params = jax.eval_shape(lambda: sambay_stack.make_params(eng.cfg, s["weights_seed"]))
    return eng, s, params


def _hybrid_pools(eng, s, S):
    from tpu_voice_agent.serve.paged import build_pools

    return build_pools(eng._cache_spec, s["pool_blocks"], eng.block_size, eng.batch_slots, zeros=S)


@pytest.mark.parametrize("width", [pytest.param("full", marks=pytest.mark.slow), "compact"])  # the chip runs "full" in every check
def test_the_hybrid_chunk_program_compiles_at_published_widths(tpu_devices, monkeypatch, width):
    """Phi-4-mini-flash-reasoning's decode chunk as ``phi4flash_flood`` serves
    it — 32 layers at published widths, int8 weights, 9 K/V planes of 10 packed
    heads of 128 over the 200-block pool, the per-slot convolution tails and
    float32 states riding the pools, the 200 064-wide head on one position a
    row — at the full width and at the compacted one (8 rows; no chip has run
    that one: PERF.md section 7, ``phi4flash_solo``). Mosaic compiles the
    block kernel with and without the window and the selective scan."""
    from tpu_voice_agent.serve import paged

    eng, s, params = _hybrid_engine(monkeypatch)
    B, R, cfg = eng.batch_slots, eng.compact_rows, eng.cfg
    assert (B, R) == (32, 8) and eng.hybrid
    chip = SingleDeviceSharding(tpu_devices[0])
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    shapes = lambda tree: jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), tree)
    k_pool, v_pool = _hybrid_pools(eng, s, S)
    rows = {"rows_idx": S((R,), I32)} if width == "compact" else {}
    compiled = paged.paged_chunk_decode_loop.__wrapped__.lower(
        shapes(params), cfg, k_pool, v_pool,
        S((B, eng.max_blocks + 1), I32), S((B,), I32), S((B,), I32), S((B,), I32), S((B,), jnp.bool_),
        S((B,), I32), S((B,), I32), shapes(eng.tables_ff), shapes(eng.byte_len_table),
        shapes(jax.random.PRNGKey(0)), S((), F32), S((), I32), trash_idx=S((B,), I32), rules=None,
        logit_mask=None if eng.logit_mask is None else shapes(eng.logit_mask), **rows,
        chunk_steps=16, greedy=True, constrained=True, kernels="pallas", eos_id=eng.eos_id,
        pad_id=eng.pad_id, max_len=eng.max_len, kv_quant=None, quality_lanes=eng.quality_lanes).compile()
    text = compiled.as_text()
    # windowed and full attention and a scan in the front scan's body and again in
    # the full pair's, the cross-attention in the back scan's
    assert text.count("tpu_custom_call") == 5
    n = R if width == "compact" else B
    # the head runs on one position a row: no (rows, 9, vocabulary) or (rows * 9, vocabulary) logits
    assert f"f32[{n},200064]" in text and f"{n},9,200064]" not in text and f"[{9 * n},200064]" not in text


@pytest.mark.parametrize("bucket", [64])
def test_the_hybrid_suffix_prefill_compiles_at_published_widths(tpu_devices, monkeypatch, bucket):
    """An admission's forward: one row, a suffix bucket behind the cached
    prefix, the covered blocks gathered, the scan masked to the real tokens."""
    from tpu_voice_agent.models import llama

    eng, s, params = _hybrid_engine(monkeypatch)
    chip = SingleDeviceSharding(tpu_devices[0])
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    shapes = lambda tree: jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), tree)
    k_pool, v_pool = _hybrid_pools(eng, s, S)
    llama.forward_paged.__wrapped__.lower(
        shapes(params), eng.cfg, S((1, bucket), I32), S((1, bucket), I32), k_pool, v_pool,
        S((1, eng.max_blocks + 1), I32), attn_impl="pallas", gather_blocks=8,
        n_real=S((1,), I32)).compile()


# Command A+ (the benchmark's cmdaplus_flood cell): 128 q heads of 128 over 8 kv
# heads on a 4096-wide residual (group 16: 144 query rows a K/V head in a
# fast-forward block), 8 layers, 16 held experts of 4096 x 4096 in int8
CMDAPLUS = (128, 8, 128, 8)


@pytest.mark.parametrize("rows", [288, 32])
def test_grouped_matmul_tiled_int8_stacked_compiles_at_command_a_plus_widths(tpu_devices, rows):
    """A (4096, 4096) int8 plane is 16 MiB, over ``_PLANE_BYTES``: the kernel's
    (tk, tn)-tiled path with the float32 accumulator, the STACKED leaf of the
    16 experts HELD with the layer in the scalar prefetch, at the decode
    forward's and a suffix prefill's token counts; the row tile follows the
    ROUTER's width (18 rows an expert of 128 at 288 tokens: 32)."""
    from tpu_voice_agent.models.llama import moe_row_tile
    from tpu_voice_agent.ops.grouped_matmul import _PLANE_BYTES, plane_tiles

    L, H, E, d, K = 8, 16, 128, 4096, 8
    tm = moe_row_tile(rows * K, E)
    assert tm == (32 if rows == 288 else 16) and plane_tiles(d, d, 1) == (4096, 512)
    assert d * d > _PLANE_BYTES
    n = -(-(rows * K + min(H, rows * K) * (tm - 1)) // tm)
    sh = SingleDeviceSharding(tpu_devices[0])
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)
    jax.jit(functools.partial(ops.grouped_matmul, tm=tm, interpret=False)).lower(
        S((n * tm, d), BF16), {"q": S((L, H, d, d), I8), "s": S((L, H, 1, d), F32)},
        S((n,), I32), S((), I32), S((), I32)).compile()


@pytest.mark.parametrize("window", [None, 4096], ids=["unbound", "window"])
def test_paged_block_attention_compiles_at_144_query_rows_a_head(tpu_devices, window):
    """(32 rows, 9 queries, 128 / 8 heads of 128): the queries of 32 rows,
    their packed copy and its state are 94 MB (4608 query rows a head), so the kernel
    walks two groups of 16, each with the split — and the packing of its rows'
    real positions — its caller made (``row_group_splits``); also behind a
    window, where a lone split made the wrapper refuse."""
    nq, nkv, hd, L = CMDAPLUS
    B, N, blocks, bs = 32, 200, 12, 128

    def attend(q, kp, vp, tables, pos, layer, live, n_real):
        splits = ops.row_group_splits((B, FF_T, nq, nkv, hd), tables, pos, live, bs, window=window,
                                      n_real=n_real)
        assert len(splits) == 2
        return ops.paged_block_attention(q, kp, vp, tables, pos, layer, live, splits,
                                         None if window is None else jnp.int32(window), n_real,
                                         interpret=False)

    _compile(tpu_devices, attend, ((B, FF_T, nq, hd), BF16), *_pool(N, CMDAPLUS),
             ((B, blocks), I32), ((B, FF_T), I32), ((), I32), ((B,), jnp.bool_), ((B,), I32))


def _cmdaplus_engine(monkeypatch, **serving):
    """The ``cmdaplus_flood`` cell's engine (published widths, a two-block
    pool: the real one is a shape below) and abstract weights, with the
    kernels told they are not interpreted; ``serving`` overrides the file's."""
    import json
    import sys
    from pathlib import Path

    from benchmark.builders import cohere2moe_stack, parse_stack
    from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
    from tpu_voice_agent.serve import PagedDecodeEngine

    for mod in ("paged_attention", "grouped_matmul", "flash_attention"):
        monkeypatch.setattr(sys.modules[f"tpu_voice_agent.ops.{mod}"], "on_cpu", lambda: False)
    conf = json.loads((Path(__file__).parents[1] / "benchmark" / "configs"
                       / "command-a-plus-05-2026-int8.json").read_text())
    m, s = parse_stack.as_run(conf, False)
    s = {**s, **serving}
    eng = PagedDecodeEngine(
        cfg=cohere2moe_stack.llama_config(m, s), tokenizer=default_tokenizer(), quant=s["quant"],
        batch_slots=s["batch_slots"], block_size=s["block_size"], pool_blocks=2, max_len=s["max_len"],
        prefill_buckets=tuple(s["prefill_buckets"]), fast_forward=s["fast_forward"], init_weights=False)
    params = jax.eval_shape(lambda: cohere2moe_stack.make_params(eng.cfg, s["weights_seed"]))
    return eng, s, params


@pytest.mark.parametrize("width", [pytest.param("full", marks=pytest.mark.slow), "compact", "window",  # the chip runs "full" in every check
                                   "packed"])  # ... since ISSUE 37 "packed"; since ISSUE 41 it holds the count of conditionals
def test_the_command_a_plus_chunk_program_compiles_at_published_widths(tpu_devices, monkeypatch, width):
    """Command A+'s decode chunk as ``cmdaplus_flood`` serves it — 8 parallel
    blocks at published widths, int8 weights, 16 held experts through the
    grouped kernel's tiled path, 128 query heads over 8 K/V planes in the
    200-block pool, the 32768-wide tied head on one position a row — at the
    full width, at the compacted one, and ("window") with ``max_len`` 5120 so
    that the 4096 window BINDS: two slots, as the published-window check on
    the chip runs it, the sliding layers through the block kernel's windowed
    variant with splits of their own."""
    from tpu_voice_agent.models import llama
    from tpu_voice_agent.serve import paged

    bound = width == "window"
    eng, s, params = _cmdaplus_engine(monkeypatch, **({"max_len": 5120, "batch_slots": 2} if bound else {}))
    B, R, cfg = eng.batch_slots, eng.compact_rows, eng.cfg
    assert cfg.moe_impl == "grouped" and llama.bound_window(cfg) == (4096 if bound else None)
    chip = SingleDeviceSharding(tpu_devices[0])
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    shapes = lambda tree: jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), tree)
    pool = S((cfg.n_layers, s["pool_blocks"], eng.block_size, cfg.n_kv_heads, cfg.head_dim), BF16)
    rows = {"rows_idx": S((R,), I32)} if width == "compact" else {}
    if width == "packed":  # both regions of a layer on the real positions, 96 rows (ISSUES 37, 41)
        rows = {"ffn_pack": eng.ffn_pack_rows}
    compiled = paged.paged_chunk_decode_loop.__wrapped__.lower(
        shapes(params), cfg, pool, pool,
        S((B, eng.max_blocks), I32), S((B,), I32), S((B,), I32), S((B,), I32), S((B,), jnp.bool_),
        S((B,), I32), S((B,), I32), shapes(eng.tables_ff), shapes(eng.byte_len_table),
        shapes(jax.random.PRNGKey(0)), S((), F32), S((), I32), trash_idx=S((B,), I32), rules=None,
        logit_mask=None if eng.logit_mask is None else shapes(eng.logit_mask), **rows,
        chunk_steps=16, greedy=True, constrained=True, kernels="pallas", eos_id=eng.eos_id,
        pad_id=eng.pad_id, max_len=eng.max_len, kv_quant=None, quality_lanes=eng.quality_lanes).compile()
    text = compiled.as_text()
    n = R if width == "compact" else B
    # the eight layers unrolled: attention (two groups of
    # 16 rows at the full width) and gate, up, down for each
    # ("packed": the three expert calls in each branch of a layer's conditional)
    assert text.count("tpu_custom_call") == 8 * ({"packed": 6}.get(width, 3) + (1 if n == R or bound else 2))
    # the layers run UNROLLED: a conditional a layer is 8 x its text in every
    # executable that holds it, and a warm start loads them all (ROADMAP S11)
    assert _conditionals(text) == (8 * 2 if width == "packed" else 0)
    # the head runs on one position a row
    assert f"f32[{n},32768]" in text and f"{n},9,32768]" not in text and f"[{9 * n},32768]" not in text
    _the_kv_write_alone_holds_a_planes_shape(text, eng, s["pool_blocks"])


@pytest.mark.parametrize("bucket,fresh", [(64, False), (1024, True), (1, False), (9, False)],
                         ids=["suffix", "prefix", "one-step", "one-block"])
def test_the_command_a_plus_prefills_compile_at_published_widths(tpu_devices, monkeypatch, bucket, fresh):
    """An admission's forward (one row, a suffix bucket behind the cached
    prefix, the covered blocks gathered), the prefix's own prefill through
    the scratch pool (a fresh 1024-token block: the flash kernel at 128 / 8
    heads, the grouped kernel at 8192 assignments), and the comparison's
    one-row T = 1 step and 1 + 8 block over the whole pool — whose K/V write
    through the flat view of the pool made XLA pad a copy of it sixteenfold
    (6.25 GB; my chip run, PR 34): the temporaries stay under 3 GB."""
    from tpu_voice_agent.models import llama

    eng, s, params = _cmdaplus_engine(monkeypatch)
    cfg = eng.cfg
    chip = SingleDeviceSharding(tpu_devices[0])
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    shapes = lambda tree: jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), tree)
    blocks = 9 if fresh else s["pool_blocks"]
    pool = S((cfg.n_layers, blocks, eng.block_size, cfg.n_kv_heads, cfg.head_dim), BF16)
    prefill = bucket > 9
    compiled = llama.forward_paged.__wrapped__.lower(
        shapes(params), cfg, S((1, bucket), I32), S((1, bucket), I32), pool, pool,
        S((1, 8 if fresh else eng.max_blocks), I32),
        attn_impl="pallas" if fresh or not prefill else "xla",
        fresh_block=fresh, gather_blocks=8 if prefill and not fresh else None).compile()
    # the pools are not donated through ``__wrapped__``: two copies of 0.84 GB are in it
    assert compiled.memory_analysis().temp_size_in_bytes < 3 << 30


@pytest.mark.parametrize("config", ["mistral-7b-v0.1-int8", "olmoe-1b-7b-0125-int8",
                                    "phi-4-mini-flash-reasoning-int8", "command-a-plus-05-2026-int8"])
def test_the_grouped_admission_compiles_at_published_widths(tpu_devices, monkeypatch, config):
    """ISSUE 35's one new program: a group's table rows, prefix tails and
    state snapshots, ``forward_paged`` over the waiting suffixes of a step —
    (``admit_rows``, 64) rows behind the cached prefix, a table row, a write
    mask and a head position PER ROW, the covered blocks gathered — and the
    batcher's first tokens, as each of the benchmark's four configurations serves it
    (published widths, int8 weights, the 200-block pool). The head runs on one
    position a row, and the program's temporaries stay far under the chip's
    memory beside the weights."""
    import json
    import sys
    from pathlib import Path

    from benchmark.builders import olmoe_stack, parse_stack
    from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
    from tpu_voice_agent.models import llama
    from tpu_voice_agent.serve import PagedDecodeEngine

    if config.startswith("phi-4"):
        eng, s, params = _hybrid_engine(monkeypatch)
    elif config.startswith("command-a"):
        eng, s, params = _cmdaplus_engine(monkeypatch)
    else:
        monkeypatch.setattr(sys.modules["tpu_voice_agent.ops.grouped_matmul"], "on_cpu", lambda: False)
        conf = json.loads((Path(__file__).parents[1] / "benchmark" / "configs" / f"{config}.json").read_text())
        dims = parse_stack.model_dims(conf, False)
        m, s = dims["model"], dims["serving"]
        llama_config, make_params = ((olmoe_stack.llama_config, olmoe_stack.make_params) if "num_experts" in m
                                     else (parse_stack.dense_llama_config, parse_stack.make_decoder_params))
        eng = PagedDecodeEngine(
            cfg=llama_config(m, s), tokenizer=default_tokenizer(), quant=s["quant"], batch_slots=s["batch_slots"],
            block_size=s["block_size"], pool_blocks=2, max_len=s["max_len"],
            prefill_buckets=tuple(s["prefill_buckets"]), fast_forward=s["fast_forward"], init_weights=False)
        params = jax.eval_shape(lambda: make_params(eng.cfg, s["weights_seed"]))
    eng.prefix_ids = [0] * 879  # behind a prefix: what turns the grouped path on
    A, cfg = eng.admit_rows, eng.cfg
    assert A == 4 and eng.GROUP_BUCKET == 64
    chip = SingleDeviceSharding(tpu_devices[0])
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    shapes = lambda tree: jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), tree)
    if eng.hybrid:
        k_pool, v_pool = _hybrid_pools(eng, s, S)
    else:
        k_pool = v_pool = S((cfg.n_layers, s["pool_blocks"], eng.block_size, cfg.n_kv_heads, cfg.head_dim), BF16)
    from tpu_voice_agent.serve import paged, scheduler

    tail = S((k_pool["kv"] if eng.hybrid else k_pool).shape[:1] + (879 % eng.block_size,)
             + (k_pool["kv"] if eng.hybrid else k_pool).shape[3:], BF16)
    snapshot = ({"conv": S(k_pool["conv"].shape[:1] + k_pool["conv"].shape[2:], k_pool["conv"].dtype),
                 "ssm": S(v_pool["ssm"].shape[:1] + v_pool["ssm"].shape[2:], v_pool["ssm"].dtype)}
                if eng.hybrid else None)
    B = eng.batch_slots
    state = (S((B,), I32), S((B,), I32), S((B,), I32), S((B,), I32), S((B,), I32), S((B,), jnp.bool_))
    pick_args = (shapes(jax.random.PRNGKey(0)), S((1,), I32), S((), F32), S((), I32), shapes(eng.tables),
                 None if eng.logit_mask is None else shapes(eng.logit_mask))
    compiled = paged.forward_paged_first_tokens.__wrapped__.lower(
        shapes(params), cfg, S((A, 64), I32), S((A, 64), I32), k_pool, v_pool,
        S((B, eng.max_blocks + eng.hybrid), I32), S((A, eng.max_blocks + eng.hybrid), I32),
        S((A,), I32), S((A,), I32), S((A,), jnp.bool_), S((A,), I32),
        S((A,), I32) if eng.hybrid else None, {"k": tail, "v": tail}, S((A * (879 % eng.block_size),), I32),
        snapshot, S((A,), I32), state, pick_args, rules=None,
        attn_impl="pallas" if eng.hybrid else "xla", gather_blocks=eng._gather_bucket(879, 64),
        pick=scheduler._first_tokens_into_slots,
        pick_kw=(("greedy", True), ("constrained", True), ("kernels", "pallas"), ("rules", None))).compile()
    text = compiled.as_text()  # one program, the head on one position a row
    assert "jit_forward_paged_first_tokens" in text and f"f32[{A},{cfg.vocab_size}]" in text
    assert f"[{A},64,{cfg.vocab_size}]" not in text and f"[{A * 64},{cfg.vocab_size}]" not in text
    # the pools are not donated through ``__wrapped__``: two copies of them are in it
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30


def _moonlight_engine(monkeypatch):
    """The ``moonlight_flood`` cell's engine (published widths, a two-block
    pool: the real one is a shape below) and abstract weights, with the
    kernels told they are not interpreted."""
    import json
    import sys
    from pathlib import Path

    from benchmark.builders import moonlight_stack, parse_stack
    from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
    from tpu_voice_agent.serve import PagedDecodeEngine

    for mod in ("latent_attention", "grouped_matmul"):
        monkeypatch.setattr(sys.modules[f"tpu_voice_agent.ops.{mod}"], "on_cpu", lambda: False)
    conf = json.loads((Path(__file__).parents[1] / "benchmark" / "configs"
                       / "moonlight-16b-a3b-int8.json").read_text())
    m, s = parse_stack.as_run(conf, False)
    eng = PagedDecodeEngine(
        cfg=moonlight_stack.llama_config(m, s), tokenizer=default_tokenizer(), quant=s["quant"],
        batch_slots=s["batch_slots"], block_size=s["block_size"], pool_blocks=2, max_len=s["max_len"],
        prefill_buckets=tuple(s["prefill_buckets"]), fast_forward=s["fast_forward"], init_weights=False)
    params = jax.eval_shape(lambda: moonlight_stack.make_params(eng.cfg, s["weights_seed"]))
    return eng, s, params


def _latent_pools(eng, s, S):
    cfg = eng.cfg
    planes = lambda width, blocks=s["pool_blocks"]: S((cfg.n_layers, blocks, eng.block_size, width), BF16)
    return planes, planes(cfg.kv_lora_rank), planes(cfg.qk_rope_dim)


@pytest.mark.parametrize("width", [pytest.param("full", marks=pytest.mark.slow), "compact", "packed"])  # the chip runs "full" in every check
def test_the_moonlight_chunk_program_compiles_at_published_widths(tpu_devices, monkeypatch, width):
    """Moonlight's decode chunk as ``moonlight_flood`` serves it — layer 0
    dense at 11264, 16 routed layers in a scan (64 experts of 1408 through
    the grouped kernel, the layer in its scalar prefetch, the shared SwiGLU
    beside them), int8 weights, the LATENT pool of 512 + 64 values a token a
    layer behind the latent kernel at 144 query rows a batch row, the
    163840-wide head on one position a row — at the compacted width, with the
    MLPs packed into 96 rows (what the cell runs under load) and whole. Every
    width is told its rows' real positions (``Family.block_real``), so each
    lowers the latent kernel's packed passes (ISSUE 49)."""
    from tpu_voice_agent.serve import paged

    eng, s, params = _moonlight_engine(monkeypatch)
    B, R, cfg = eng.batch_slots, eng.compact_rows, eng.cfg
    assert cfg.moe_impl == "grouped" and eng.latent and eng.ffn_pack_rows == 96
    chip = SingleDeviceSharding(tpu_devices[0])
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    shapes = lambda tree: jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), tree)
    _, c_pool, r_pool = _latent_pools(eng, s, S)
    rows = {"rows_idx": S((R,), I32)} if width == "compact" else {}
    if width == "packed":
        rows = {"ffn_pack": eng.ffn_pack_rows}
    compiled = paged.paged_chunk_decode_loop.__wrapped__.lower(
        shapes(params), cfg, c_pool, r_pool,
        S((B, eng.max_blocks), I32), S((B,), I32), S((B,), I32), S((B,), I32), S((B,), jnp.bool_),
        S((B,), I32), S((B,), I32), shapes(eng.tables_ff), shapes(eng.byte_len_table),
        shapes(jax.random.PRNGKey(0)), S((), F32), S((), I32), trash_idx=S((B,), I32), rules=None,
        logit_mask=None if eng.logit_mask is None else shapes(eng.logit_mask), **rows,
        chunk_steps=16, greedy=True, constrained=True, kernels="pallas", eos_id=eng.eos_id,
        pad_id=eng.pad_id, max_len=eng.max_len, kv_quant=None, quality_lanes=eng.quality_lanes).compile()
    text = compiled.as_text()
    n = R if width == "compact" else B
    # layer 0's latent kernel, and in the scan's body one more beside the three
    # expert calls ("packed": those in each branch of the layer's conditional)
    assert text.count("tpu_custom_call") == 1 + 1 + (6 if width == "packed" else 3)
    assert ("conditional" in text) == (width == "packed")
    # the head runs on one position a row, and no K or V of a cached position
    # is ever decompressed: nothing of (pool positions) x (heads x 128) exists
    assert f"f32[{n},163840]" in text and f"{n},9,163840]" not in text
    assert not any(f"[{blocks},128,16,{w}]" in text for blocks in (s["pool_blocks"], eng.max_blocks)
                   for w in (128, 192, 256))
    # the pools are not donated through ``__wrapped__``: two copies of 0.50 GB are in it
    assert compiled.memory_analysis().temp_size_in_bytes < 3 << 30


@pytest.mark.parametrize("rows,bucket,fresh", [(4, 64, False), (1, 64, False), (1, 1024, True), (1, 9, False)],
                         ids=["group", "suffix", "prefix", "one-block"])
def test_the_moonlight_prefills_compile_at_published_widths(tpu_devices, monkeypatch, rows, bucket, fresh):
    """A group's admission forward ((4, 64) suffixes behind the cached prefix,
    the covered blocks of BOTH planes gathered, absorbed attention in XLA), the
    per-slot one, the prefix's own prefill through the scratch pool (a fresh
    1024-token block) and the comparison's one-row 1 + 8 block over the whole
    pool through the latent kernel."""
    from tpu_voice_agent.models import llama

    eng, s, params = _moonlight_engine(monkeypatch)
    chip = SingleDeviceSharding(tpu_devices[0])
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    shapes = lambda tree: jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), tree)
    planes, _, _ = _latent_pools(eng, s, S)
    blocks = 9 if fresh else s["pool_blocks"]
    prefill = bucket > 9
    compiled = llama.forward_paged.__wrapped__.lower(
        shapes(params), eng.cfg, S((rows, bucket), I32), S((rows, bucket), I32),
        planes(eng.cfg.kv_lora_rank, blocks), planes(eng.cfg.qk_rope_dim, blocks),
        S((rows, 8 if fresh else eng.max_blocks), I32),
        attn_impl="pallas" if fresh or not prefill else "xla",
        fresh_block=fresh, gather_blocks=8 if prefill and not fresh else None).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 3 << 30


@pytest.mark.parametrize("P,blocks", [(16, 264), (12, 264), (9, 264), (16, 65)],
                         ids=["block", "compact", "one-block", "scratch"])
def test_the_indexer_kernel_compiles_at_published_widths(tpu_devices, P, blocks):
    """dots3-note-prev's indexer (64 index heads of 128 a position) over the
    WHOLE index-key plane of a full layer — the cell's 264-block pool and the
    prefix's 65-block scratch pool — for a tile of 16 position slots (a
    fast-forward block, a suffix group, a chunk of the head), the compacted
    width's 12 and the comparison's 1 + 8 block (both padded to 16 inside)."""
    from tpu_voice_agent.ops import sparse_latent as sl

    compiled = _compile(tpu_devices, sl.indexer_scores, ((P, 64, 128), BF16), ((P, 64), F32),
                        ((3, blocks, 128, 128), BF16), ((), I32), interpret=False)
    assert "indexer_scores" in compiled.as_text()


@pytest.mark.parametrize("name,G,Q,K,C", [("sparse_latent_attention", 16, 128, 2048, 512),
                                          ("sparse_latent_attention", 9, 128, 2048, 512),
                                          ("window_latent_attention", 32, 576, 768, 1024),
                                          ("window_latent_attention", 128, 512, 768, 1024),
                                          ("window_latent_attention", 32, 512, 640, 1024)],
                         ids=["selected", "selected-one-block", "window-block", "window-prefix", "window-suffix"])
def test_the_gathered_latent_kernel_compiles_at_published_widths(tpu_devices, name, G, Q, K, C):
    """ONE kernel under two names: a position's 128 heads over its 2048 selected
    rows [c | r] of 512 + 64 as the full layers' plane holds them (a tile of 16
    slots; the comparison's 9), and a sliding layer's row — 9 x 64 queries of a
    fast-forward block, 8 x 64 of a prefill's rows of eight — over the 5-6
    blocks of 1024 + 64, two planes' rows, that hold its window; the whole key
    set one tile."""
    from tpu_voice_agent.ops import sparse_latent as sl

    keys = [((G, K, C + 64), BF16)] if name == "sparse_latent_attention" else [((G, K, C), BF16), ((G, K, 64), BF16)]
    compiled = _compile(tpu_devices, getattr(sl, name), ((G, Q, C), BF16), ((G, Q, 64), BF16),
                        *keys, ((G, K), I32), ((G, Q), I32), ((G, Q), I32), scale=0.07, interpret=False)
    assert name in compiled.as_text()


@pytest.mark.parametrize("H,tile", [(64, 16), (128, 16), (64, 12)], ids=["glm-5.2", "dots3-note", "compact"])
def test_the_walked_latent_kernel_compiles_at_published_widths(tpu_devices, H, tile):
    """ISSUE 62: a tile's 16 (the compacted width's 12) slots x 64 | 128 heads
    as query rows [q_c | q_r] of 512 + 64, walking the 69 columns of their
    tables block by block out of the cell's 264-block plane stack under the
    members' mask — the layer and the block ids (read out of the tile's table by
    the items' keys) in the index maps, a dynamic grid over the tile's items,
    four common columns an item."""
    from tpu_voice_agent.ops import sparse_latent as sl

    nb = 69

    def walk(q_c, q_r, plane, layer, chosen, tables, *split):
        return sl.walked_latent_attention(q_c, q_r, plane, layer, chosen, tables, sl.WalkSplit(*split),
                                          scale=0.07, interpret=False)

    compiled = _compile(tpu_devices, walk, ((tile, H, 512), BF16), ((tile, H, 64), BF16),
                        ((8, 264, 128, 576), BF16), ((), I32), ((tile, nb * 128), jnp.bool_), ((tile, nb), I32),
                        ((), I32), ((), I32), ((), I32), (((1 + tile) * nb,), I32))
    assert "walked_latent_attention" in compiled.as_text()


@pytest.mark.parametrize("tile,keys,k", [(16, 8832, 2048), (12, 8832, 2048), (16, 1152, 1024)],
                         ids=["block", "compact", "head-chunk"])
def test_the_threshold_select_compiles_at_published_widths(tpu_devices, tile, keys, k):
    """ISSUE 63: a walked tile's selection — top-2048 of the 8832 positions its rows' tables span
    (16 slots; the compacted width's 12, padded to 16 inside), top-1024 of a head chunk's 1152 — as
    ONE kernel over the tile's keys whole in VMEM, and no sort in the program around it."""
    from tpu_voice_agent.ops import sparse_latent as sl

    compiled = _compile(tpu_devices, sl.threshold_members, ((tile, keys), F32), k=k, interpret=False)
    text = compiled.as_text()
    assert "threshold_members" in text and " sort(" not in text


@pytest.mark.slow
def test_sharded_kernels_compile_on_2x2(tpu_devices):
    """The shard_map variants the dp x tp serving mesh traces (batch over
    dp, heads over tp), compiled for all four abstract chips."""
    mesh = Mesh(np.array(tpu_devices).reshape(2, 2), ("dp", "tp"))
    nq, nkv, hd, L = TINYLLAMA
    B, S, N = 4, 1024, 4 * MAX_BLOCKS + 2
    heads = P("dp", None, "tp", None)
    cache = P(None, "dp", None, "tp", None)
    pool = P(None, "dp", None, "tp", None)
    rep = P()

    def go(fn, args, specs, **static):
        _compile(tpu_devices, functools.partial(fn, mesh), *args, mesh=mesh,
                 mesh_specs=specs, **static)

    go(ops.sharded_flash_attention,
       [((B, 1024, nq, hd), BF16), ((B, 1024, nkv, hd), BF16),
        ((B, 1024, nkv, hd), BF16)], [heads] * 3, causal=True, interpret=False)
    go(ops.sharded_decode_attention_layer,
       [((B, nq, hd), BF16), *_dense_cache(B, S, TINYLLAMA, True), ((B,), I32),
        ((), I32)], [P("dp", "tp", None), cache, cache, P("dp"), rep],
       interpret=False)
    go(ops.sharded_decode_block_attention_layer,
       [((B, FF_T, nq, hd), BF16), *_dense_cache(B, S, TINYLLAMA, True),
        ((B, FF_T), I32), ((), I32)],
       [heads, cache, cache, P("dp", None), rep], interpret=False)
    go(ops.sharded_paged_attention,
       [((B, nq, hd), BF16), *_pool(N, TINYLLAMA), ((B, MAX_BLOCKS), I32),
        ((B,), I32), ((), I32)],
       [P("dp", "tp", None), pool, pool, P("dp", None), P("dp"), rep],
       interpret=False)
    go(ops.sharded_paged_block_attention,
       [((B, FF_T, nq, hd), BF16), *_pool(N, TINYLLAMA),
        ((B, MAX_BLOCKS), I32), ((B, FF_T), I32), ((), I32)],
       [heads, pool, pool, P("dp", None), P("dp", None), rep], interpret=False)
    go(ops.sharded_masked_argmax_advance,
       [((B, VOCAB), F32), ((B,), I32), ((FSM_STATES, VOCAB), jnp.bool_),
        ((FSM_STATES, FSM_CLASSES), I32), ((VOCAB,), I32)],
       [P("dp", None), P("dp"), rep, rep, rep], interpret=False)
