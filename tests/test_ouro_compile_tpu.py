"""AOT-compile, for the TPU and without one (``tests/test_kernels_compile_tpu.py``
has the method), what ``ouro_flood`` runs at published widths: the chunk program
of the cell's own engine at both widths — a scan of four passes around the scan
of 48 layers, the 192-plane pool of 47 blocks carried through both in place —
the suffix forward behind the cached prefix and the prefix's own prefill through
the scratch pool. Compiling is not running."""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

BF16, I32, F32 = jnp.bfloat16, jnp.int32, jnp.float32


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _engine(monkeypatch):
    from benchmark.builders import ouro_stack, parse_stack
    from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
    from tpu_voice_agent.serve import PagedDecodeEngine

    monkeypatch.setattr(sys.modules["tpu_voice_agent.ops.paged_attention"], "on_cpu", lambda: False)
    conf = json.loads((Path(__file__).parents[1] / "benchmark" / "configs" / "ouro-2.6b-int8.json").read_text())
    m, s = parse_stack.as_run(conf, False)
    eng = PagedDecodeEngine(
        cfg=ouro_stack.llama_config(m, s), tokenizer=default_tokenizer(), quant=s["quant"],
        batch_slots=s["batch_slots"], block_size=s["block_size"], pool_blocks=2, max_len=s["max_len"],
        prefill_buckets=tuple(s["prefill_buckets"]), fast_forward=s["fast_forward"], init_weights=False)
    return eng, s, jax.eval_shape(lambda: ouro_stack.make_params(eng.cfg, s["weights_seed"]))


@pytest.mark.parametrize("width", ["full", "compact"])
def test_the_chunk_program_compiles_at_published_widths(chip, monkeypatch, width):
    """The cell's decode chunk: 8 rows (2 compacted) of 1 + 8 positions, no
    packed branch (72 positions under the 96 packed rows), ONE layer body in
    the text — one block-kernel call, whatever the 192 (pass, layer) pairs —
    the head on one position a row, and the 9.5 GB pool updated in place: the
    program's temporaries stay under a quarter of a GiB beside it."""
    from tpu_voice_agent.serve import paged

    eng, s, params = _engine(monkeypatch)
    B, R, cfg = eng.batch_slots, eng.compact_rows, eng.cfg
    assert (B, R) == (8, 2) and eng.family.name == "plain" and eng.admit_rows == 0
    assert [c.name for c in eng.family.counts] == ["attn", "loop", "kv"]
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    shapes = lambda tree: jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), tree)
    k_pool, v_pool = paged.build_pools(eng._cache_spec, s["pool_blocks"], eng.block_size, B, zeros=S)
    assert k_pool.shape == (192, 47, 128, 16, 128)
    rows = {"rows_idx": S((R,), I32)} if width == "compact" else {}
    compiled = paged.paged_chunk_decode_loop.__wrapped__.lower(
        shapes(params), cfg, k_pool, v_pool,
        S((B, eng.max_blocks), I32), S((B,), I32), S((B,), I32), S((B,), I32), S((B,), jnp.bool_),
        S((B,), I32), S((B,), I32), shapes(eng.tables_ff), shapes(eng.byte_len_table),
        shapes(jax.random.PRNGKey(0)), S((), F32), S((), I32), trash_idx=S((B,), I32), rules=None,
        logit_mask=None if eng.logit_mask is None else shapes(eng.logit_mask), **rows,
        chunk_steps=16, greedy=True, constrained=True, kernels="pallas", eos_id=eng.eos_id,
        pad_id=eng.pad_id, max_len=eng.max_len, kv_quant=None, quality_lanes=eng.quality_lanes).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    n = R if width == "compact" else B
    assert f"f32[{n},49152]" in text and f"{n},9,49152]" not in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 28
    # weights 2.77 GB + the pool 9.46 GB, donated: arguments and outputs alias
    assert 12.2e9 < mem.argument_size_in_bytes < 12.35e9 and mem.alias_size_in_bytes > 9.4e9
    # of the pool's or a plane's shape: the K/V write's in-place scatters alone — the walk
    # over tiles of the real rows (ISSUE 60) carries the pools through its ``while`` in place
    from tools.kv_write_check import shaped_ops

    found = shaped_ops(text, k_pool.shape)
    assert found and set(found) <= {"fusion", "scatter"}, found


@pytest.mark.parametrize("rows,bucket,blocks,fresh", [(1, 64, 12, False), (1, 128, 12, False),
                                                     (1, 1024, 8, True)])
def test_an_admissions_forward_compiles_at_published_widths(chip, monkeypatch, rows, bucket, blocks, fresh):
    """A lone suffix forward behind the 879-token prefix (8 slots: no grouped
    admission) — the covered blocks of the pass's own plane gathered a layer —
    and the prefix's own prefill, one fresh block of 1024 through the flash
    kernel into a scratch pool of 9 blocks (1.8 GB at 192 planes)."""
    from tpu_voice_agent.models import llama
    from tpu_voice_agent.serve import paged

    eng, s, params = _engine(monkeypatch)
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    shapes = lambda tree: jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), tree)
    n = (blocks + 1) if fresh else s["pool_blocks"]
    k_pool, v_pool = paged.build_pools(eng._cache_spec, n, eng.block_size, eng.batch_slots, zeros=S)
    kw = dict(attn_impl="pallas", fresh_block=True) if fresh else dict(
        attn_impl="xla", fresh_block=False, gather_blocks=8)
    compiled = llama.forward_paged.__wrapped__.lower(
        shapes(params), eng.cfg, S((rows, bucket), I32), S((rows, bucket), I32), k_pool, v_pool,
        S((rows, blocks), I32), **kw).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < (3 << 30 if fresh else 1 << 30)
