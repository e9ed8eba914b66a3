"""AOT-compile, for the TPU and without one (``tests/test_kernels_compile_tpu.py``
has the method), what ``smallthinker_pagemap_flood`` runs at published widths:
the block kernel's WINDOWED walk at a GQA group of 7 (T = 9: 63 query rows a
K/V head, a group no other cell has; T = 1: 7), the chunk program and the
grouped admission's forward of the cell's own engine, and a chunk of the
8192-token head's prefill. Compiling is not running."""

import functools
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tpu_voice_agent import ops

BF16, I32, F32 = jnp.bfloat16, jnp.int32, jnp.float32
NQ, NKV, HD, LAYERS, SLOTS, BLOCKS = 28, 4, 128, 24, 32, 69  # published heads; the cut; a row's table


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("B,T,window", [(32, 9, 4096), (32, 1, 4096), (8, 9, 4096), (32, 9, None)])
def test_the_block_kernel_compiles_at_a_group_of_seven(chip, B, T, window):
    """28 query heads on 4 K/V heads: 63 query rows a K/V head padded to 64 (7
    to 16 at T = 1), a position's 7 rows no sublane tile — behind the window
    (a sliding layer, no common pass) and without one (a full layer)."""
    S = lambda shape, dt=BF16: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    fn = functools.partial(ops.paged_block_attention, interpret=False)
    args = [S((B, T, NQ, HD)), S((LAYERS, 8, 128, NKV, HD)), S((LAYERS, 8, 128, NKV, HD)),
            S((B, BLOCKS), I32), S((B, T), I32), S((), I32), S((B,), jnp.bool_)]

    def call(q, kp, vp, bt, qp, layer, live, n_real):
        split = ops.row_group_splits((B, T, NQ, NKV, HD), bt, qp, live, 128, window=window,
                                     n_real=n_real)
        return fn(q, kp, vp, bt, qp, layer, live, split,
                  None if window is None else jnp.int32(window), n_real)

    compiled = jax.jit(call).lower(*args, S((B,), I32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


def _engine(monkeypatch):
    from benchmark.builders import parse_stack, smallthinker_stack
    from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
    from tpu_voice_agent.serve import PagedDecodeEngine

    for mod in ("paged_attention", "grouped_matmul"):  # not interpreted here
        monkeypatch.setattr(sys.modules[f"tpu_voice_agent.ops.{mod}"], "on_cpu", lambda: False)
    conf = json.loads((Path(__file__).parents[1] / "benchmark" / "configs"
                       / "smallthinker-21b-a3b-int8.json").read_text())
    m, s = parse_stack.as_run(conf, False)
    s = {**s, "site_context_tokens": 0}  # the head's text is the cell's; a shape needs none
    eng = PagedDecodeEngine(
        cfg=smallthinker_stack.llama_config(m, s), tokenizer=default_tokenizer(), quant=s["quant"],
        batch_slots=s["batch_slots"], block_size=s["block_size"], pool_blocks=2, max_len=s["max_len"],
        prefill_buckets=tuple(s["prefill_buckets"]), fast_forward=s["fast_forward"], init_weights=False)
    return eng, s, jax.eval_shape(lambda: smallthinker_stack.make_params(eng.cfg, s["weights_seed"]))


@pytest.mark.parametrize("width", [pytest.param("full", marks=pytest.mark.slow), "compact"])  # the chip runs "full" in every check
def test_the_chunk_program_compiles_at_published_widths(chip, monkeypatch, width):
    """The cell's decode chunk — 24 unrolled layers at published widths, int8
    weights, the router ahead of attention, the head on one position a row — at
    the full width (both regions of a layer packed into 96 rows) and at the
    compacted one (8 rows, 72 positions: nothing packs). A constrained program
    with forced chains compiles the (rows, 9) body alone: one block-kernel call
    a layer (18 behind the window, 6 with the common pass) and three
    ``grouped_matmul`` calls a layer and branch."""
    from tpu_voice_agent.serve import paged

    eng, s, params = _engine(monkeypatch)
    B, R, cfg = eng.batch_slots, eng.compact_rows, eng.cfg
    assert (B, R) == (32, 8) and eng.family.name == "plain" and eng.ffn_pack_rows == 96
    assert [c.name for c in eng.family.counts] == ["moe", "attn", "window", "kv"]
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    shapes = lambda tree: jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), tree)
    k_pool, v_pool = paged.build_pools(eng._cache_spec, s["pool_blocks"], eng.block_size, B, zeros=S)
    rows = {"rows_idx": S((R,), I32)} if width == "compact" else {}
    compiled = paged.paged_chunk_decode_loop.__wrapped__.lower(
        shapes(params), cfg, k_pool, v_pool,
        S((B, eng.max_blocks), I32), S((B,), I32), S((B,), I32), S((B,), I32), S((B,), jnp.bool_),
        S((B,), I32), S((B,), I32), shapes(eng.tables_ff), shapes(eng.byte_len_table),
        shapes(jax.random.PRNGKey(0)), S((), F32), S((), I32), trash_idx=S((B,), I32), rules=None,
        logit_mask=None if eng.logit_mask is None else shapes(eng.logit_mask), **rows,
        chunk_steps=16, greedy=True, constrained=True, kernels="pallas", eos_id=eng.eos_id,
        pad_id=eng.pad_id, max_len=eng.max_len, kv_quant=None, quality_lanes=eng.quality_lanes,
        **({"ffn_pack": eng.ffn_pack_rows} if width == "full" else {})).compile()
    text = compiled.as_text()
    branches = 2 if width == "full" else 1  # the packed region and the whole one
    assert text.count("tpu_custom_call") == 24 + 24 * 3 * branches
    n = R if width == "compact" else B
    assert f"f32[{n},151936]" in text and f"{n},9,151936]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("rows,bucket,blocks", [(4, 64, 69), (1, 64, 69), (1, 512, 65)])
def test_an_admissions_forward_compiles_at_published_widths(chip, monkeypatch, rows, bucket, blocks):
    """A group's suffix forward (4 rows, bucket 64) and a lone one's behind the
    8192-token head — the covered blocks gathered, every layer under its own
    mask — and one chunk of the head's own prefill through the scratch pool
    (512 positions attending the 65-block table, the head on one of them: the
    chunk that keeps a layer's float32 scores inside ``paged.PREFIX_SCORE_BYTES``)."""
    from tpu_voice_agent.models import llama
    from tpu_voice_agent.serve import paged

    eng, s, params = _engine(monkeypatch)
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    shapes = lambda tree: jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), tree)
    k_pool, v_pool = paged.build_pools(eng._cache_spec, blocks + 1, eng.block_size, eng.batch_slots, zeros=S)
    compiled = llama.forward_paged.__wrapped__.lower(
        shapes(params), eng.cfg, S((rows, bucket), I32), S((rows, bucket), I32), k_pool, v_pool,
        S((rows, blocks), I32), attn_impl="xla", logit_pos=S((rows,), I32),
        **({"gather_blocks": blocks, "write_mask": S((rows,), jnp.bool_)} if bucket == 64 else {})).compile()
    assert eng.cfg.n_heads * 512 * 8192 * 4 <= paged.PREFIX_SCORE_BYTES < eng.cfg.n_heads * 1024 * 8192 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
