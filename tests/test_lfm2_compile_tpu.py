"""AOT-compile, for the TPU and without one (``tests/test_kernels_compile_tpu.py``
has the method), what ``lfm2_flood`` runs at published widths: the chunk program
at the full and the compacted width, a group's admission forward and the
prefix's chunk of the cell's own engine — 64-wide heads in pairs under the block
kernel, 22 layers of 32 experts through ``grouped_matmul``, 18 convolution
layers whose tails ride the k pool. Compiling is not running."""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

I32, F32 = jnp.int32, jnp.float32


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _engine(monkeypatch):
    from benchmark.builders import lfm2_stack, parse_stack
    from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
    from tpu_voice_agent.serve import PagedDecodeEngine

    for mod in ("paged_attention", "grouped_matmul"):  # not interpreted here
        monkeypatch.setattr(sys.modules[f"tpu_voice_agent.ops.{mod}"], "on_cpu", lambda: False)
    conf = json.loads((Path(__file__).parents[1] / "benchmark" / "configs"
                       / "lfm2-8b-a1b-int8.json").read_text())
    m, s = parse_stack.as_run(conf, False)
    eng = PagedDecodeEngine(
        cfg=lfm2_stack.llama_config(m, s), tokenizer=default_tokenizer(), quant=s["quant"],
        batch_slots=s["batch_slots"], block_size=s["block_size"], pool_blocks=2, max_len=s["max_len"],
        prefill_buckets=tuple(s["prefill_buckets"]), fast_forward=s["fast_forward"], init_weights=False)
    return eng, s, jax.eval_shape(lambda: lfm2_stack.make_params(eng.cfg, s["weights_seed"]))


def test_the_whole_model_is_8_3_billion_parameters_in_8_5_gb(monkeypatch):
    """The served tree at published widths, from shapes alone: 8.20 G int8 in the
    layers, the embedding twice (bf16 rows for the gather, the tied head's int8
    copy), and what stays bf16 / float32 beside them."""
    eng, _, params = _engine(monkeypatch)
    size = lambda t: sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(t))
    int8 = sum(x.size for x in jax.tree.leaves(params) if x.dtype == jnp.int8)
    assert int8 == 18 * 16_777_216 + 6 * 10_485_760 + 2 * 44_040_192 + 22 * 352_321_536 + 65536 * 2048
    assert 8.19e9 < int8 - 65536 * 2048 < 8.21e9 and 8.6e9 < size(params) < 8.75e9
    assert params["experts"]["moe_gate"]["q"].shape == (22, 32, 2048, 1792)
    assert params["embed"].dtype == jnp.bfloat16 and params["experts"]["router_bias"].dtype == F32


@pytest.mark.parametrize("width", [pytest.param("full", marks=pytest.mark.slow), "compact"])  # the chip runs "full" in every check
def test_the_chunk_program_compiles_at_published_widths(chip, monkeypatch, width):
    """The cell's decode chunk — 24 layers at published widths, int8 weights, 18
    layers' tails riding the k pool beside 6 K/V planes of (4, 128), nothing per
    slot in the v pool, the head on one position a row — at the full width (two
    conditionals a layer over 96 packed rows) and at the compacted one (8 rows:
    72 positions run whole). The layers are unrolled: 6 calls of the block kernel
    and three of grouped_matmul for each routed layer and branch."""
    from tpu_voice_agent.serve import paged

    eng, s, params = _engine(monkeypatch)
    B, R, cfg = eng.batch_slots, eng.compact_rows, eng.cfg
    assert (B, R) == (32, 8) and eng.family.name == "conv" and eng.ffn_pack_rows == 96
    assert cfg.moe_impl == "grouped" and (cfg.count("C"), cfg.count("F")) == (18, 6)
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    shapes = lambda tree: jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), tree)
    k_pool, v_pool = paged.build_pools(eng._cache_spec, s["pool_blocks"], eng.block_size, B, zeros=S)
    assert k_pool["kv"].shape == (6, 200, 128, 4, 128) and k_pool["tail"].shape == (18, 32, 4096)
    assert set(v_pool) == {"kv"}
    rows = {"rows_idx": S((R,), I32)} if width == "compact" else {}
    compiled = paged.paged_chunk_decode_loop.__wrapped__.lower(
        shapes(params), cfg, k_pool, v_pool,
        S((B, eng.max_blocks + 1), I32), S((B,), I32), S((B,), I32), S((B,), I32), S((B,), jnp.bool_),
        S((B,), I32), S((B,), I32), shapes(eng.tables_ff), shapes(eng.byte_len_table),
        shapes(jax.random.PRNGKey(0)), S((), F32), S((), I32), trash_idx=S((B,), I32), rules=None,
        logit_mask=None if eng.logit_mask is None else shapes(eng.logit_mask), **rows,
        chunk_steps=16, greedy=True, constrained=True, kernels="pallas", eos_id=eng.eos_id,
        pad_id=eng.pad_id, max_len=eng.max_len, kv_quant=None, quality_lanes=eng.quality_lanes,
        **({"ffn_pack": eng.ffn_pack_rows} if width == "full" else {})).compile()
    text = compiled.as_text()
    branches = 2 if width == "full" else 1  # the packed branch and the whole one
    assert text.count("tpu_custom_call") == 6 + 22 * 3 * branches
    n = R if width == "compact" else B
    assert f"f32[{n},65536]" in text and f"{n},9,65536]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30  # no copy of a layer's experts


@pytest.mark.parametrize("rows,T,blocks", [(4, 64, 8), pytest.param(1, 1024, 8, marks=pytest.mark.slow)])
def test_an_admissions_forward_compiles_at_published_widths(chip, monkeypatch, rows, T, blocks):
    """A group's suffix forward (4 rows, bucket 64 behind the cached prefix, the
    covered blocks gathered and viewed as heads of 64 again, the tails gathered
    at the real tokens' end) and the prefix's one chunk through a scratch pool."""
    from tpu_voice_agent.models import llama
    from tpu_voice_agent.serve import paged

    eng, s, params = _engine(monkeypatch)
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    shapes = lambda tree: jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), tree)
    k_pool, v_pool = paged.build_pools(eng._cache_spec, s["pool_blocks"], eng.block_size, eng.batch_slots, zeros=S)
    llama.forward_paged.__wrapped__.lower(
        shapes(params), eng.cfg, S((rows, T), I32), S((rows, T), I32), k_pool, v_pool,
        S((rows, eng.max_blocks + 1), I32), attn_impl="pallas", gather_blocks=blocks,
        n_real=S((rows,), I32), write_mask=S((rows,), jnp.bool_), logit_pos=S((rows,), I32),
        moe_stats=True).compile()
