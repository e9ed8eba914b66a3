"""AOT-compile, for the TPU and without one (``tests/test_kernels_compile_tpu.py``
has the method), what ``olmohybrid_flood`` runs at published widths: the
``gated_delta_scan`` kernel at every block shape the cell dispatches, and the
chunk program, an admission's forward and the prefix's chunk of the cell's own
engine. Compiling is not running."""

import functools
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tpu_voice_agent.ops import gated_delta

I32, F32 = jnp.int32, jnp.float32
H, DK, DV, LAYERS, SLOTS = 30, 96, 192, 24, 32  # the published Gated-DeltaNet sizes


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_state_planes_are_dense_at_the_published_sizes():
    """Two heads side by side: 384 lanes = three whole tiles, 96 sublanes = twelve."""
    assert gated_delta.plane_shape(H, DK, DV) == (15, 96, 384)
    assert 384 % 128 == 0 and 96 % 8 == 0 and 15 * 96 * 384 == H * DK * DV


@pytest.mark.parametrize("B,T", [(32, 9), (32, 1), (8, 9), (4, 64), (1, 64), (1, 1024)])
def test_the_gated_delta_scan_compiles_at_the_cells_shapes(chip, B, T):
    """The full and the compacted chunk widths (T = 9 and T = 1), a grouped and
    a single admission's suffix bucket, the prefix's chunk: one row's whole
    state (2.21 MB) a grid step, the planes aliased in place — no copy of the 1.7 GB."""
    S = lambda shape, dt=F32: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    scan = functools.partial(gated_delta.gated_delta_scan.__wrapped__, interpret=False)
    compiled = jax.jit(scan, donate_argnums=(0,)).lower(
        S((LAYERS, SLOTS, *gated_delta.plane_shape(H, DK, DV))), S((B,), I32), S((), I32),
        S((B, T, H, DK)), S((B, T, H, DK)), S((B, T, H, DV)), S((B, T, H)), S((B, T, H)),
        S((B,), I32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 96 << 20


def _engine(monkeypatch):
    from benchmark.builders import olmo_hybrid_stack, parse_stack
    from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
    from tpu_voice_agent.serve import PagedDecodeEngine

    for mod in ("paged_attention", "gated_delta"):  # not interpreted here
        monkeypatch.setattr(sys.modules[f"tpu_voice_agent.ops.{mod}"], "on_cpu", lambda: False)
    conf = json.loads((Path(__file__).parents[1] / "benchmark" / "configs"
                       / "olmo-hybrid-7b-int8.json").read_text())
    m, s = parse_stack.as_run(conf, False)
    eng = PagedDecodeEngine(
        cfg=olmo_hybrid_stack.llama_config(m, s), tokenizer=default_tokenizer(), quant=s["quant"],
        batch_slots=s["batch_slots"], block_size=s["block_size"], pool_blocks=2, max_len=s["max_len"],
        prefill_buckets=tuple(s["prefill_buckets"]), fast_forward=s["fast_forward"], init_weights=False)
    return eng, s, jax.eval_shape(lambda: olmo_hybrid_stack.make_params(eng.cfg, s["weights_seed"]))


@pytest.mark.parametrize("width", [pytest.param("full", marks=pytest.mark.slow), "compact"])  # the chip runs "full" in every check
def test_the_chunk_program_compiles_at_published_widths(chip, monkeypatch, width):
    """The cell's decode chunk — 32 layers at published widths, int8 weights,
    24 layers' float32 states and tails riding the pools beside 8 K/V planes,
    the head on one position a row — at the full width (two walks a layer over
    tiles of 96 packed rows) and at the compacted one (8 rows, 72 slots: one
    tile). The layers are unrolled (a slice of a stacked int8 plane at a loop's
    index is a copy of it): 24 calls of the scan, 8 of the block kernel; the
    states are updated in place."""
    from tpu_voice_agent.serve import paged

    eng, s, params = _engine(monkeypatch)
    B, R, cfg = eng.batch_slots, eng.compact_rows, eng.cfg
    assert (B, R) == (32, 8) and eng.family.name == "gdn" and eng.ffn_pack_rows == 96
    assert cfg.pattern == "LLLF" * 8 and (cfg.dim, cfg.ffn_dim, cfg.vocab_size) == (3840, 11008, 100352)
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
    assert text.count("tpu_custom_call") == 24 + 8  # the scan a linear layer, the block kernel a full one
    assert "dynamic-slice_bitcast_fusion" not in text  # no plane is copied before its matmul
    n = R if width == "compact" else B
    assert f"f32[{n},100352]" in text and f"{n},9,100352]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30  # no copy of the states


@pytest.mark.parametrize("rows,T,blocks", [(4, 64, 8), pytest.param(1, 1024, 8, marks=pytest.mark.slow)])
def test_an_admissions_forward_compiles_at_published_widths(chip, monkeypatch, rows, T, blocks):
    """A group's suffix forward (4 rows, bucket 64 behind the cached prefix, the
    covered blocks gathered, the recurrence walked over the real tokens alone,
    everything position-wise on the real positions) and the prefix's one chunk
    through a scratch pool."""
    from tpu_voice_agent.models import llama
    from tpu_voice_agent.serve import paged

    eng, s, params = _engine(monkeypatch)
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    shapes = lambda tree: jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), tree)
    k_pool, v_pool = paged.build_pools(eng._cache_spec, s["pool_blocks"], eng.block_size, eng.batch_slots, zeros=S)
    llama.forward_paged.__wrapped__.lower(
        shapes(params), eng.cfg, S((rows, T), I32), S((rows, T), I32), k_pool, v_pool,
        S((rows, eng.max_blocks + 1), I32), attn_impl="pallas", gather_blocks=blocks,
        n_real=S((rows,), I32), write_mask=S((rows,), jnp.bool_), logit_pos=S((rows,), I32)).compile()
