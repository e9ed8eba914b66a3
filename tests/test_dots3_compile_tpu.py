"""AOT-compile, for the TPU and without one, the WHOLE programs the cell
``dots3note_sitemap_flood`` runs at the published widths of dots3-note-prev
(``benchmark/configs/dots3-note-prev-int8.json``): the decode chunk at the
compacted and the packed width (the full one is ``slow``: the chip runs it in
every check), a group's admission behind the 8192-token head, a 1024-token
chunk of that head through the scratch pool and the comparison's one-row
block. ``tests/test_kernels_compile_tpu.py`` (its header has how this works)
compiles the model's kernels alone; these nine-layer programs take a minute
or two each and sit in a file of their own so that the test run spreads them
over another worker."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

BF16, I32, F32 = jnp.bfloat16, jnp.int32, jnp.float32


@pytest.fixture(scope="module")
def tpu_devices():
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo.devices


def _dots3_engine(monkeypatch):
    """The ``dots3note_sitemap_flood`` cell's engine (published widths, a
    two-block pool: the real one is a shape below) and abstract weights, with
    the kernels told they are not interpreted."""
    import json
    import sys
    from pathlib import Path

    import tpu_voice_agent.ops.sparse_latent  # noqa: F401  (not in ``ops``' namespace)
    from benchmark.builders import dots3_stack, parse_stack
    from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
    from tpu_voice_agent.serve import PagedDecodeEngine

    for mod in ("sparse_latent", "grouped_matmul"):
        monkeypatch.setattr(sys.modules[f"tpu_voice_agent.ops.{mod}"], "on_cpu", lambda: False)
    conf = json.loads((Path(__file__).parents[1] / "benchmark" / "configs"
                       / "dots3-note-prev-int8.json").read_text())
    m, s = parse_stack.as_run(conf, False)
    eng = PagedDecodeEngine(
        cfg=dots3_stack.llama_config(m, {**s, "site_context_tokens": 0}), tokenizer=default_tokenizer(),
        quant=s["quant"], batch_slots=s["batch_slots"], block_size=s["block_size"], pool_blocks=2,
        max_len=s["max_len"], prefill_buckets=tuple(s["prefill_buckets"]), fast_forward=s["fast_forward"],
        init_weights=False)
    params = jax.eval_shape(lambda: dots3_stack.make_params(eng.cfg, s["weights_seed"]))
    return eng, s, params


def _dots3_pools(eng, S, blocks):
    planes = eng._cache_spec["planes"]
    return tuple({n: S((L, blocks, eng.block_size, w), BF16) for n, (L, w) in planes[p].items()} for p in "kv")


@pytest.mark.parametrize("width", [pytest.param("full", marks=pytest.mark.slow), "compact", "packed"])  # the chip runs "full" in every check
def test_the_dots3_chunk_program_compiles_at_published_widths(tpu_devices, monkeypatch, width):
    """dots3-note-prev's decode chunk as ``dots3note_sitemap_flood`` serves
    it — nine layers unrolled, three full (the indexer over the 264-block
    plane, ``top_k`` of 2048 over the row's 69 blocks, the gather, the
    selected kernel; tile by tile under a ``while``) and six sliding (the
    window's gather and kernel), a compressed query and a gate a head in
    each, layer 0 dense at 13824, 32 held experts of 1536 through the grouped
    kernel behind a 256-wide router, int8 weights, planes by layer kind, the
    19008-row head on one position a row — 8.4 GB of arguments and under
    1.5 GB of temporaries: it fits the chip's 16."""
    from tpu_voice_agent.serve import paged

    eng, s, params = _dots3_engine(monkeypatch)
    B, R, cfg = eng.batch_slots, eng.compact_rows, eng.cfg
    assert cfg.moe_impl == "grouped" and eng.sparse and eng.ffn_pack_rows == 96 and eng.max_blocks == 69
    chip = SingleDeviceSharding(tpu_devices[0])
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    shapes = lambda tree: jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), tree)
    k_pool, v_pool = _dots3_pools(eng, S, s["pool_blocks"])
    rows = {"rows_idx": S((R,), I32)} if width == "compact" else {}
    if width == "packed":
        rows = {"ffn_pack": eng.ffn_pack_rows}
    compiled = paged.paged_chunk_decode_loop.__wrapped__.lower(
        shapes(params), cfg, k_pool, v_pool,
        S((B, eng.max_blocks), I32), S((B,), I32), S((B,), I32), S((B,), I32), S((B,), jnp.bool_),
        S((B,), I32), S((B,), I32), shapes(eng.tables_ff), shapes(eng.byte_len_table),
        shapes(jax.random.PRNGKey(0)), S((), F32), S((), I32), trash_idx=S((B,), I32), rules=None,
        logit_mask=None if eng.logit_mask is None else shapes(eng.logit_mask), **rows,
        chunk_steps=16, greedy=True, constrained=True, kernels="pallas", eos_id=eng.eos_id,
        pad_id=eng.pad_id, max_len=eng.max_len, kv_quant=None, quality_lanes=eng.quality_lanes).compile()
    text = compiled.as_text()
    n = R if width == "compact" else B
    # (the cell's 8832 keys of table behind top-2048 WALK at 128 heads too: the walked kernel under the scope's name)
    for kernel in ("indexer_scores", "threshold_members", "sparse_latent_attention", "walked_latent_attention",
                   "window_latent_attention", "grouped_matmul"):
        assert kernel in text, kernel
    # ISSUE 44: the packed width walks ONE copy of a layer's position-wise code in tiles of 96 packed
    # rows (two ``while`` a layer) — no predicate, no whole-width twin: the parent of ISSUE 44 (ee06ed6)
    # compiled to 9 ``conditional`` ops here, one a layer around its two MLPs, and an executable of 84 MB
    # serialized for this tree's 54
    assert "conditional" not in text
    if width == "packed":
        # and no projection fused with its opening into heads: XLA:TPU then wants the stacked plane
        # transposed and copies it whole (W_qb, W_qI: ``latent_qkv``'s ``hold``); the planes that ARE
        # relaid out once a chunk are W_kvb's (the parent's too) and W_kva's
        import math
        import re

        copied = {dims for dims in re.findall(r"= s8\[(\d+,\d+,\d+)\]\S* copy\(", text)
                  if math.prod(map(int, dims.split(","))) > 8 << 20}  # the planes of 8 MB and more
        assert copied <= {"6,1024,20480", "3,512,32768", "6,5120,1088", "3,5120,576"}, copied
    # the head on one position a row; no key or value of a cached position is ever decompressed
    assert f"f32[{n},19008]" in text and f"{n},9,19008]" not in text
    assert not any(f"[{blocks},128,{h},{w}]" in text for blocks in (s["pool_blocks"], eng.max_blocks)
                   for h in (64, 128) for w in (128, 192, 256, 320))
    mem = compiled.memory_analysis()
    # the pools are not donated through ``__wrapped__``: two copies of 0.58 GB are in it
    assert mem.temp_size_in_bytes < 1.5 * 2 ** 30 and mem.argument_size_in_bytes + mem.temp_size_in_bytes < 11 << 30


@pytest.mark.parametrize("rows,bucket,blocks,cols", [(4, 64, 264, 69), (1, 1024, 65, 64), (1, 9, 264, 69)],
                         ids=["group", "prefix-chunk", "one-block"])
def test_the_dots3_prefills_compile_at_published_widths(tpu_devices, monkeypatch, rows, bucket, blocks, cols):
    """A group's admission forward ((4, 64) suffixes behind the 8192-token
    head: every block of a row's table scored, 2048 keys selected a
    position; a lone request's (1, 64) is the same path at one row), a 1024-token chunk of the head through the
    65-block scratch pool and the comparison's one-row 1 + 8 block — each
    through the model's ONE attention path, with its kernels."""
    from tpu_voice_agent.models import llama

    eng, s, params = _dots3_engine(monkeypatch)
    chip = SingleDeviceSharding(tpu_devices[0])
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    shapes = lambda tree: jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), tree)
    k_pool, v_pool = _dots3_pools(eng, S, blocks)
    n_real = S((rows,), I32) if bucket == 64 else None
    compiled = llama.forward_paged.__wrapped__.lower(
        shapes(params), eng.cfg, S((rows, bucket), I32), S((rows, bucket), I32), k_pool, v_pool,
        S((rows, cols), I32), attn_impl="pallas", n_real=n_real).compile()
    text = compiled.as_text()
    assert "indexer_scores" in text and "window_latent_attention" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30
