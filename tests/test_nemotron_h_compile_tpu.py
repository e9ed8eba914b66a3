"""AOT-compile, for the TPU and without one (``tests/test_kernels_compile_tpu.py``
has the method), what ``nemotron3super_flood`` runs at published widths: the
``ssd_scan`` kernel at every block shape the cell dispatches, ``grouped_matmul``
on a latent expert's two planes, and the chunk program and an admission's
forward of the cell's own engine. Compiling is not running."""

import functools
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tpu_voice_agent import ops
from tpu_voice_agent.ops.ssd_scan import ssd_scan

BF16, I8, I32, F32 = jnp.bfloat16, jnp.int8, jnp.int32, jnp.float32
H, P, G, N, LAYERS, SLOTS = 128, 64, 8, 128, 10, 32  # the published Mamba-2 sizes, the cut's 10 layers


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("B,T", [(32, 9), (32, 1), (8, 9), (4, 64), (1, 64), (1, 1024)])
def test_the_ssd_scan_compiles_at_the_cells_shapes(chip, B, T):
    """The full and the compacted chunk widths (T = 9 and T = 1), a grouped and
    a single admission's suffix bucket, the prefix's chunk: a group's 16 heads
    a grid step, the state planes aliased in place — no copy of the 1.34 GB."""
    S = lambda shape, dt=F32: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    compiled = jax.jit(functools.partial(ssd_scan, interpret=False), donate_argnums=(5,)).lower(
        S((B, T, H, P)), S((B, T, H)), S((H,)), S((B, T, G, N)), S((B, T, G, N)),
        S((LAYERS, SLOTS, H, P, N)), S((B,), I32), S((), I32), S((B,), I32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("d,f", [(1024, 2688), (2688, 1024)])
def test_grouped_matmul_compiles_on_a_latent_experts_planes(chip, d, f):
    """128 held experts' up (1024 x 2688) and down planes, int8 and stacked over
    the 10 E layers, on the whole-plane path, at the tile of 16 rows that
    ~45 positions x 22 picks over a 512-wide router give."""
    from tpu_voice_agent.models.llama import moe_row_tile
    from tpu_voice_agent.ops.grouped_matmul import plane_tiles

    tm = moe_row_tile(96 * 22, 512)
    assert tm == 16 and plane_tiles(d, f, 1) == (d, f)
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    rows = -(-(96 * 22 + 128 * (tm - 1)) // tm) * tm
    jax.jit(functools.partial(ops.grouped_matmul, tm=tm, interpret=False)).lower(
        S((rows, d), BF16), {"q": S((10, 128, d, f), I8), "s": S((10, 128, 1, f), F32)},
        S((rows // tm,), I32), S((), I32), S((), I32)).compile()


def _engine(monkeypatch):
    from benchmark.builders import nemotron_h_stack, parse_stack
    from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
    from tpu_voice_agent.serve import PagedDecodeEngine

    for mod in ("paged_attention", "ssd_scan", "grouped_matmul"):  # not interpreted here
        monkeypatch.setattr(sys.modules[f"tpu_voice_agent.ops.{mod}"], "on_cpu", lambda: False)
    conf = json.loads((Path(__file__).parents[1] / "benchmark" / "configs"
                       / "nemotron-3-super-120b-a12b-int8.json").read_text())
    m, s = parse_stack.as_run(conf, False)
    eng = PagedDecodeEngine(
        cfg=nemotron_h_stack.llama_config(m, s), tokenizer=default_tokenizer(), quant=s["quant"],
        batch_slots=s["batch_slots"], block_size=s["block_size"], pool_blocks=2, max_len=s["max_len"],
        prefill_buckets=tuple(s["prefill_buckets"]), fast_forward=s["fast_forward"], init_weights=False)
    return eng, s, jax.eval_shape(lambda: nemotron_h_stack.make_params(eng.cfg, s["weights_seed"]))


@pytest.mark.parametrize("width", [pytest.param("full", marks=pytest.mark.slow), "compact"])  # the chip runs "full" in every check
def test_the_chunk_program_compiles_at_published_widths(chip, monkeypatch, width):
    """The cell's decode chunk — 22 layers at published widths, int8 weights,
    10 layers' float32 states and tails riding the pools beside 2 K/V planes,
    the head on one position a row — at the full width (its E layers walk
    tiles of 96 packed rows) and at the compacted one (8 rows, 72 slots: one
    tile). Three loops over (M, E) pairs, the rest unrolled: 5 traces of the
    scan, 5 of an expert layer's two planes, 2 of the block kernel (a constrained
    program with forced chains compiles the (rows, 9) body alone); the states are
    updated in place."""
    from tpu_voice_agent.serve import paged

    eng, s, params = _engine(monkeypatch)
    B, R, cfg = eng.batch_slots, eng.compact_rows, eng.cfg
    assert (B, R) == (32, 8) and eng.family.name == "ssd" and eng.ffn_pack_rows == 96
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    shapes = lambda tree: jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), tree)
    k_pool, v_pool = paged.build_pools(eng._cache_spec, s["pool_blocks"], eng.block_size, B, zeros=S)
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
    assert text.count("tpu_custom_call") == 5 + 2 * 5 + 2
    n = R if width == "compact" else B
    assert f"f32[{n},32768]" in text and f"{n},9,32768]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30  # no copy of the states, no plane in bf16


def test_an_admissions_forward_compiles_at_published_widths(chip, monkeypatch):
    """A group's suffix forward: 4 rows, bucket 64 behind the cached prefix,
    the covered blocks gathered, the scan masked to the real tokens, the E
    layers on the real positions alone."""
    from tpu_voice_agent.models import llama
    from tpu_voice_agent.serve import paged

    eng, s, params = _engine(monkeypatch)
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    shapes = lambda tree: jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), tree)
    k_pool, v_pool = paged.build_pools(eng._cache_spec, s["pool_blocks"], eng.block_size, eng.batch_slots, zeros=S)
    llama.forward_paged.__wrapped__.lower(
        shapes(params), eng.cfg, S((4, 64), I32), S((4, 64), I32), k_pool, v_pool,
        S((4, eng.max_blocks + 1), I32), attn_impl="pallas", gather_blocks=8,
        n_real=S((4,), I32), write_mask=S((4,), jnp.bool_), logit_pos=S((4,), I32)).compile()
