"""AOT-compile, for the TPU and without one, the WHOLE programs the cell
``glm52_sitemap_flood`` runs at the published widths of GLM-5.2
(``benchmark/configs/glm-5.2-int8.json``): the decode chunk at the compacted
and the packed width (the full one is ``slow``: the chip runs it in every
check), a group's admission behind the 8192-token head, a 1024-token chunk of
that head through the scratch pool and the comparison's one-row block.
``tests/test_dots3_compile_tpu.py``'s pattern, in a file of its own so that the
test run spreads these eight-layer programs over another worker."""

import re

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


def _engine(monkeypatch):
    """The ``glm52_sitemap_flood`` cell's engine (published widths, a two-block
    pool: the real one is a shape below) and abstract weights, with the kernels
    told they are not interpreted."""
    import json
    import sys
    from pathlib import Path

    import tpu_voice_agent.ops.sparse_latent  # noqa: F401  (not in ``ops``' namespace)
    from benchmark.builders import glm_dsa_stack, parse_stack
    from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
    from tpu_voice_agent.serve import PagedDecodeEngine

    for mod in ("sparse_latent", "grouped_matmul"):
        monkeypatch.setattr(sys.modules[f"tpu_voice_agent.ops.{mod}"], "on_cpu", lambda: False)
    conf = json.loads((Path(__file__).parents[1] / "benchmark" / "configs" / "glm-5.2-int8.json").read_text())
    m, s = parse_stack.as_run(conf, False)
    eng = PagedDecodeEngine(
        cfg=glm_dsa_stack.llama_config(m, {**s, "site_context_tokens": 0}), tokenizer=default_tokenizer(),
        quant=s["quant"], batch_slots=s["batch_slots"], block_size=s["block_size"], pool_blocks=2,
        max_len=s["max_len"], prefill_buckets=tuple(s["prefill_buckets"]), fast_forward=s["fast_forward"],
        init_weights=False)
    params = jax.eval_shape(lambda: glm_dsa_stack.make_params(eng.cfg, s["weights_seed"]))
    return eng, s, params


def _pools(eng, S, blocks):
    planes = eng._cache_spec["planes"]
    return tuple({n: S((L, blocks, eng.block_size, w), BF16) for n, (L, w) in planes[p].items()} for p in "kv")


@pytest.mark.parametrize("width", [pytest.param("full", marks=pytest.mark.slow), "compact", "packed"])  # the chip runs "full" in every check
def test_the_glm_chunk_program_compiles_at_published_widths(tpu_devices, monkeypatch, width):
    """GLM-5.2's decode chunk as ``glm52_sitemap_flood`` serves it — eight
    layers unrolled, TWO with an indexer (32 heads over the 264-block plane,
    ``top_k`` of 2048 over the row's 69 blocks) whose selection the three
    layers behind each gather their own rows by, the selected kernel with 64
    heads a position in all eight, layer 0 dense at 12288, 16 held experts of
    2048 through the grouped kernel behind a 256-wide router, int8 weights, the
    19360-row head on one position a row: it fits the chip's 16 GB."""
    from tpu_voice_agent.serve import paged

    eng, s, params = _engine(monkeypatch)
    B, R, cfg = eng.batch_slots, eng.compact_rows, eng.cfg
    assert cfg.moe_impl == "grouped" and eng.sparse and eng.ffn_pack_rows == 96 and eng.max_blocks == 69
    chip = SingleDeviceSharding(tpu_devices[0])
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    shapes = lambda tree: jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), tree)
    k_pool, v_pool = _pools(eng, S, s["pool_blocks"])
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
    # (the cell's 8832 keys of table behind top-2048 WALK: the walked kernel under the scope's name)
    for kernel in ("indexer_scores", "threshold_members", "sparse_latent_attention", "walked_latent_attention",
                   "grouped_matmul"):
        assert kernel in text, kernel
    # (ISSUE 63) a walked tile's selection sorts no row of scores: the k-th score and its last tie, counted
    assert not re.search(r"f32\[\d+,8832\]\S* sort\(", text)
    assert "window_latent_attention" not in text and "conditional" not in text
    # the head on one position a row; no key or value of a cached position is ever decompressed
    assert f"f32[{n},19360]" in text and f"{n},9,19360]" not in text
    assert not any(f"[{blocks},128,64,{w}]" in text for blocks in (s["pool_blocks"], eng.max_blocks)
                   for w in (192, 256, 448))
    mem = compiled.memory_analysis()
    # the pools are not donated through ``__wrapped__``: two copies of 0.33 GB are in it
    assert mem.temp_size_in_bytes < 1.5 * 2 ** 30 and mem.argument_size_in_bytes + mem.temp_size_in_bytes < 10 << 30


@pytest.mark.parametrize("rows,bucket,blocks,cols", [(4, 64, 264, 69), (1, 1024, 65, 64), (1, 9, 264, 69)],
                         ids=["group", "prefix-chunk", "one-block"])
def test_the_glm_prefills_compile_at_published_widths(tpu_devices, monkeypatch, rows, bucket, blocks, cols):
    """A group's admission forward ((4, 64) suffixes behind the 8192-token
    head), a 1024-token chunk of the head through the 65-block scratch pool and
    the comparison's one-row 1 + 8 block — each through the model's ONE
    attention path, with its kernels."""
    from tpu_voice_agent.models import llama

    eng, s, params = _engine(monkeypatch)
    chip = SingleDeviceSharding(tpu_devices[0])
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    shapes = lambda tree: jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), tree)
    k_pool, v_pool = _pools(eng, S, blocks)
    n_real = S((rows,), I32) if bucket == 64 else None
    compiled = llama.forward_paged.__wrapped__.lower(
        shapes(params), eng.cfg, S((rows, bucket), I32), S((rows, bucket), I32), k_pool, v_pool,
        S((rows, cols), I32), attn_impl="pallas", n_real=n_real).compile()
    text = compiled.as_text()
    assert "indexer_scores" in text and "sparse_latent_attention" in text
    # (ISSUE 63) the select runs where a program chooses FEWER keys than its table spans: the head's chunks
    # behind a 64-column table choose 2048 of 8192, the suffixes and the block 2048 of 8832
    assert "threshold_members" in text and not re.search(r"f32\[\d+,\d+\]\S* sort\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30
