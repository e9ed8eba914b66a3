"""Moonlight-16B-A3B (``deepseek_v3``; the benchmark's ``moonlight-16b-a3b-int8``)
at test widths on the CPU: the served path — a LATENT cache behind absorbed
attention, leading dense layers, a router that selects by score + bias —
against its plain reference (``benchmark/reference/moonlight_decoder.py``,
which decompresses and knows nothing of absorption), the kernel against its
twin, what the pool holds, the router by hand, every refusal by type, and
what the engine asks of the model (prefix through the scratch pool, grouped
admission, both chunk widths, the counters). The AOT compile for the TPU at
the published widths is ``tests/test_kernels_compile_tpu.py``'s (one file
holds every such compile: the on-chip-measurement guide, section 2).
"""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import moonlight_stack, parse_stack
from benchmark.reference import decoder as dense_ref
from benchmark.reference import moonlight_decoder as ref
from tpu_voice_agent.models import llama, mla
from tpu_voice_agent.models.family import family
from tpu_voice_agent.models.llama import forward_paged, init_params, quantize_params

F32 = jnp.float32
CONF = json.loads((Path(__file__).parents[1] / "benchmark/configs/moonlight-16b-a3b-int8.json").read_text())
MODEL, SERVING = parse_stack.as_run(CONF, True)  # the file's rehearsal widths: 2 dense + 2 routed layers
CFG = dataclasses.replace(moonlight_stack.llama_config(MODEL, SERVING), max_seq_len=256)
BS, N = 16, 12
TABLE = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
TOKS = jax.random.randint(jax.random.key(1), (1, 50), 0, CFG.vocab_size)


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float((np.abs(got - want).max(-1) / np.abs(want).max(-1)).max())


def pools(cfg, dtype, n=N):
    return (jnp.zeros((cfg.n_layers, n, BS, cfg.kv_lora_rank), dtype),
            jnp.zeros((cfg.n_layers, n, BS, cfg.qk_rope_dim), dtype))


def through_the_pool(params, cfg, impl, dtype, steps=(37, 1, 1, 1, 9, 1), toks=TOKS):
    """50 tokens as the engine feeds them: a prefill of 37, three T = 1 steps,
    one 1 + 8 block, one more step — latents through the paged pool.
    -> (50, V) logits."""
    cp, rp = pools(cfg, dtype)
    rows, pos = [], 0
    for T in steps:
        out = forward_paged(params, cfg, toks[:, pos:pos + T], (pos + jnp.arange(T))[None], cp, rp,
                            TABLE, attn_impl=impl, fresh_block=pos == 0)
        rows.append(np.asarray(out[0][0]))
        cp, rp, pos = out[1], out[2], pos + T
    return np.concatenate(rows)


def test_the_configuration_keeps_the_published_widths_and_names_its_cut():
    """The file's top level is the catalog's ``config`` but for the depth;
    the program's configuration reads every size from it."""
    assert [CONF[k] for k in ("hidden_size", "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
                              "qk_rope_head_dim", "v_head_dim", "intermediate_size",
                              "moe_intermediate_size", "n_routed_experts", "num_experts_per_tok",
                              "n_shared_experts", "first_k_dense_replace", "vocab_size", "rope_theta",
                              "routed_scaling_factor")] == \
        [2048, 16, 512, 128, 64, 128, 11264, 1408, 64, 6, 2, 1, 163840, 50000, 2.446]
    assert (CONF["num_hidden_layers"], CONF["num_hidden_layers_published"], CONF["q_lora_rank"]) == (17, 27, None)
    mistral = json.loads((Path(__file__).parents[1] / "benchmark/configs/mistral-7b-v0.1-int8.json").read_text())
    same = {k: v for k, v in CONF["serving"].items() if k != "weights_seed"}
    assert same == {k: v for k, v in mistral["serving"].items() if k != "weights_seed"}
    full = moonlight_stack.llama_config(*parse_stack.as_run(CONF, False))
    assert (full.head_dim, full.kv_lora_rank, full.qk_rope_dim, full.v_head_dim, full.n_experts,
            full.n_held, full.top_k, full.first_dense_layers, full.dense_ffn_dim, full.ffn_dim) == \
        (192, 512, 64, 128, 64, 64, 6, 1, 11264, 1408)
    assert full.router_bias and full.shared_sum and full.router_scale == 2.446
    fam = family(full)  # the record the serving side reads
    assert (fam.name, fam.module, fam.scratch_prefix) == ("latent", mla, True)
    assert family(llama.PRESETS["test-tiny"]).name == "plain"
    assert fam.cache["planes"] == {"k": {"kv": (17, 512)}, "v": {"kv": (17, 64)}} and not fam.cache["by_name"]
    assert fam.token_bytes == 17 * 1152 == 19584
    assert (CFG.first_dense_layers, CFG.kv_lora_rank, CFG.qk_rope_dim) == (2, 48, 16)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prefill_then_decode_through_the_pool_is_the_reference_full_forward(impl):
    """Float32 weights and activations: prefill, T = 1 steps and a 1 + 8
    block through the latent pool — ABSORBED attention everywhere — against
    the reference's ONE full forward, which decompresses keys and values a
    head: two dense layers, two routed ones chosen by a nonzero bias, shared
    experts added, the untied head. Under "pallas" the latent kernel serves
    T = 1 and the block, and the grouped kernel the experts (interpreted).
    1e-4: float32 in another order; bf16 anywhere reads 1e-2."""
    params = init_params(CFG, jax.random.key(0), F32)
    assert float(jnp.abs(params["layers"]["router_bias"]).min()) > 0
    cfg = dataclasses.replace(CFG, moe_impl="grouped" if impl == "pallas" else "dense")
    want = ref.logits(params, MODEL, {"tokens": [int(t) for t in TOKS[0]], "rows": 50})
    with jax.default_matmul_precision("highest"):
        assert rel(through_the_pool(params, cfg, impl, F32), want) < 1e-4


@pytest.mark.parametrize("first_dense", [0, 1, 2])
def test_leading_dense_layers_run_before_the_scan_over_the_routed_ones(first_dense):
    """0, 1 or 2 of 4 layers dense: the parameter tree stacks them apart, the
    pool's planes are indexed by the layer's place in the MODEL, and the
    served path is the reference's forward each time."""
    model = {**MODEL, "first_k_dense_replace": first_dense}
    cfg = dataclasses.replace(moonlight_stack.llama_config(model, SERVING), max_seq_len=256)
    params = init_params(cfg, jax.random.key(2), F32)
    assert ("dense_layers" in params) == bool(first_dense)
    assert params["layers"]["router"].shape[0] == 4 - first_dense
    if first_dense:
        assert params["dense_layers"]["w_gate"].shape == (first_dense, cfg.dim, cfg.dense_ffn_dim)
    want = ref.logits(params, model, {"tokens": [int(t) for t in TOKS[0]], "rows": 50})
    with jax.default_matmul_precision("highest"):
        got = through_the_pool(params, cfg, "xla", F32)
    assert rel(got, want) < 1e-4
    with pytest.raises(NotImplementedError, match="latent model's alone"):
        llama.LlamaConfig(n_experts=4, first_dense_layers=1, dense_ffn_dim=64)


def test_absorbed_attention_is_decompressed_attention():
    """The identity the cache rests on, one layer, by hand: scores of the
    absorbed query against the latent and outputs through W_UV equal
    per-head keys and values decompressed from the latent."""
    p = jax.tree.map(lambda a: a[0], init_params(CFG, jax.random.key(5), F32)["layers"])
    T = 20
    x = jax.random.normal(jax.random.key(6), (1, T, CFG.dim), F32)
    pos = jnp.arange(T)[None]
    cos, sin = llama.rope_tables(pos, CFG.qk_rope_dim, CFG.rope_theta)
    from tpu_voice_agent.ops import latent_attention_reference

    with jax.default_matmul_precision("highest"):
        q_c, q_r, c, r = mla.latent_qkv(p, x, CFG, cos, sin)
        a = latent_attention_reference(q_c, q_r, c, r, pos, scale=CFG.head_dim ** -0.5)
        got = mla.latent_out(p, a, CFG, F32)[0] @ p["wo"]
        kw = ref.model_kw(MODEL)
        h = dense_ref.rms_norm(x[0], p["attn_norm"], kw["eps"])
        want = ref.attention_part(h, pos[0], p, dense_ref.dense, H=kw["H"], dn=kw["dn"], dr=kw["dr"],
                                  dv=kw["dv"], C=kw["C"], theta=kw["theta"], latent_eps=kw["latent_eps"])
    assert q_c.shape == (1, T, CFG.n_heads, CFG.kv_lora_rank) and c.shape == (1, T, CFG.kv_lora_rank)
    assert rel(got, want) < 1e-4


@pytest.mark.parametrize("T", [1, 9])
def test_the_latent_kernel_matches_its_twin_behind_a_common_prefix_and_ragged_own_blocks(T):
    """Interpret mode: six rows, four of which hold the same two leading
    blocks (the common pass reads those ONCE), one with a table of its own,
    one idle; frontiers that end in different blocks."""
    from tpu_voice_agent.ops import (latent_row_splits, paged_latent_attention,
                                     paged_latent_attention_reference)

    B, H, C, R, bs, M, L = 6, 4, 48, 16, 16, 6, 3
    ks = jax.random.split(jax.random.key(7), 4)
    c_pool = jax.random.normal(ks[0], (L, 40, bs, C), F32)
    r_pool = jax.random.normal(ks[1], (L, 40, bs, R), F32)
    q_c = jax.random.normal(ks[2], (B, T, H, C), F32)
    q_r = jax.random.normal(ks[3], (B, T, H, R), F32)
    tables = np.asarray([[1, 2, 10 + 4 * b, 11 + 4 * b, 12 + 4 * b, 13 + 4 * b] for b in range(B)], np.int32)
    tables[4] = [30, 31, 32, 33, 34, 35]  # a row that shares nothing
    first = np.asarray([40, 55, 33, 70, 50, 0])  # rides, rides, rides, rides, own table, idle
    live = jnp.asarray([True, True, True, True, True, False])
    pos = jnp.asarray(first[:, None] + np.arange(T)[None, :], jnp.int32)
    tables = jnp.asarray(tables)
    splits = latent_row_splits((B, T, H, C, R), tables, pos, live, bs, 4)
    assert len(splits) == 1 and int(splits[0].n_common) == 2 and int(splits[0].n_riders) == 4
    want = paged_latent_attention_reference(q_c, q_r, c_pool, r_pool, tables, pos, 1, scale=0.125)
    with jax.default_matmul_precision("highest"):
        for split in (splits, None):
            got = paged_latent_attention(q_c, q_r, c_pool, r_pool, tables, pos, jnp.int32(1), live,
                                         split, scale=0.125)
            assert rel(got[:5], want[:5]) < 1e-4 and float(jnp.abs(got[5]).max()) == 0.0


# the packed passes (ISSUE 49): six rows — four behind the same two leading
# blocks (they ride), one with a table of its own, one idle — each with
# ``n_real`` of its T positions real, the rest copies of its last real one
# (query, position) as the chunk program's ``ff_body`` builds them
_REAL_CASES = {
    # name: (T, n_real a row, rows a group or None)
    "one position, ragged": (1, [1, 0, 1, 1, 1, 1], None),
    "one position, every one real": (1, [1, 1, 1, 1, 1, 1], None),
    "a block, ragged": (9, [9, 0, 1, 4, 2, 3], None),
    "a block, every position real": (9, [9, 9, 9, 9, 9, 9], None),
    "a block, two groups of rows": (9, [2, 9, 0, 1, 3, 5], 3),
}


@pytest.mark.parametrize("case", list(_REAL_CASES))
def test_the_latent_kernel_multiplies_the_real_positions_alone(case, monkeypatch):
    """``n_real`` packs a row's real positions in both passes and carries the
    riders' state from the common pass into the own one: a real position's
    output is the ``n_real=None`` call's BIT FOR BIT (a query row's dots do
    not depend on which rows share its tile) and the plain twin's within
    tolerance; a position behind them returns its row's last real one's; a
    live row without one, and the idle row, return zeros and disturb nobody;
    ``common_query_rows`` counts the riders' real positions."""
    import sys

    from tpu_voice_agent.ops import (latent_row_splits, paged_latent_attention,
                                     paged_latent_attention_reference)

    T, n_real, rows_a_group = _REAL_CASES[case]
    B, H, C, R, bs, L = 6, 4, 48, 16, 16, 3
    ks = jax.random.split(jax.random.key(11), 4)
    n_real = np.asarray(n_real, np.int32)
    t_of = np.minimum(np.arange(T)[None, :], np.maximum(n_real[:, None] - 1, 0))  # the copies
    copies = lambda q: jnp.take_along_axis(q, jnp.asarray(t_of)[:, :, None, None], axis=1)
    c_pool = jax.random.normal(ks[0], (L, 40, bs, C), F32)
    r_pool = jax.random.normal(ks[1], (L, 40, bs, R), F32)
    q_c = copies(jax.random.normal(ks[2], (B, T, H, C), F32))
    q_r = copies(jax.random.normal(ks[3], (B, T, H, R), F32))
    tables = np.asarray([[1, 2, 10 + 4 * b, 11 + 4 * b, 12 + 4 * b, 13 + 4 * b] for b in range(B)], np.int32)
    tables[4] = [30, 31, 32, 33, 34, 35]  # a row that shares nothing
    first = np.asarray([40, 55, 33, 70, 50, 0])  # rides x 4, own table, idle
    live = jnp.asarray([True, True, True, True, True, False])
    pos = jnp.asarray(first[:, None] + t_of, jnp.int32)
    tables = jnp.asarray(tables)
    rows = np.asarray(live) & (n_real > 0)
    real = (np.arange(T)[None, :] < n_real[:, None]) & rows[:, None]

    if rows_a_group:  # the rows' state passes the kernel's budget: groups, each with a split of its own
        mod = sys.modules["tpu_voice_agent.ops.latent_attention"]
        monkeypatch.setattr(mod, "_rows_that_fit", lambda *a: rows_a_group)
        jax.clear_caches()  # the budget is read when the wrapper is traced
    made = lambda n: latent_row_splits((B, T, H, C, R), tables, pos, live, bs, 4, n)
    assert len(made(None)) == (B // rows_a_group if rows_a_group else 1)
    with jax.default_matmul_precision("highest"):
        call = lambda n, split: np.asarray(paged_latent_attention(
            q_c, q_r, c_pool, r_pool, tables, pos, jnp.int32(1), live, split, n, scale=0.125))
        whole = call(None, made(None))
        got = call(jnp.asarray(n_real), made(jnp.asarray(n_real)))
        # the split is the wrapper's own where the caller hands none
        np.testing.assert_array_equal(call(jnp.asarray(n_real), None), got)
        want = np.asarray(paged_latent_attention_reference(q_c, q_r, c_pool, r_pool, tables, pos, 1,
                                                           scale=0.125))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got[real], whole[real])
    np.testing.assert_array_equal(got, np.take_along_axis(got, t_of[:, :, None, None], axis=1))
    assert (got[~rows] == 0).all() and (whole[5] == 0).all()
    assert rel(got[rows], want[rows]) < 1e-4 and rel(whole[:5], want[:5]) < 1e-4
    splits = made(jnp.asarray(n_real))
    rides = np.concatenate([np.asarray(s.slot) < int(s.n_riders) for s in splits])
    assert rides[:4].all() or rows_a_group
    assert sum(int(s.counts[2]) for s in splits) == int(n_real[rides].sum())
    if rows_a_group:
        jax.clear_caches()


def test_the_pool_holds_576_values_a_token_a_layer():
    """At the PUBLISHED widths: two planes, a latent of 512 and one rotated
    key of 64 — 1152 B a token a layer in bf16, 19584 B over the 17 layers —
    read from the pool's own shapes, the engine's gauge and the byte plan."""
    from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
    from tpu_voice_agent.serve import PagedDecodeEngine, paged
    from tpu_voice_agent.utils import hbmledger, tracing

    m, s = parse_stack.as_run(CONF, False)
    full = moonlight_stack.llama_config(m, s)
    eng = PagedDecodeEngine(cfg=full, tokenizer=default_tokenizer(), quant="int8", batch_slots=2,
                            block_size=128, pool_blocks=4, max_len=256, prefill_buckets=(128,),
                            init_weights=False)
    assert eng.k_pool.shape == (17, 4, 128, 512) and eng.v_pool.shape == (17, 4, 128, 64)
    per_token_layer = (eng.k_pool.shape[-1] + eng.v_pool.shape[-1]) * eng.k_pool.dtype.itemsize
    assert per_token_layer == 1152
    pool_bytes = eng.k_pool.nbytes + eng.v_pool.nbytes
    assert pool_bytes == 4 * eng.kv_bytes_per_block == 4 * 128 * 19584
    assert hbmledger.engine_hbm_plan(eng)["kv_pool_bytes"] == pool_bytes
    fresh = tracing.Metrics()
    orig, tracing._GLOBAL_METRICS = tracing._GLOBAL_METRICS, fresh
    try:
        paged.record_pool_gauges(eng.allocator, engine=eng)
    finally:
        tracing._GLOBAL_METRICS = orig
    assert fresh.snapshot()["gauges"]["paged.kv_bytes_per_token"] == 19584


def test_the_bias_moves_the_chosen_set_and_not_the_gates():
    """By hand: scores s = sigmoid(x W); the chosen are the top of s + b, the
    gates s of the chosen WITHOUT b over their sum, times the scale — so they
    sum to 2.446. The dense dispatch's combine carries the same gates."""
    from tpu_voice_agent.models.moe import route_topk, route_topk_flat

    x = jnp.asarray([[1.0, 0.0], [0.0, 2.0]], F32)
    w = jnp.asarray([[0.0, 1.0, 2.0, -1.0], [1.0, 0.0, -1.0, 0.5]], F32)
    b = jnp.asarray([0.0, 0.0, -1.0, 0.6], F32)  # expert 2 pushed out, expert 3 pulled in
    sig = lambda z: 1.0 / (1.0 + np.exp(-z))
    plain, _ = route_topk_flat(w, x, 4, 2, True, "sigmoid")
    eids, gates = route_topk_flat(w, x, 4, 2, True, "sigmoid", bias=b, scale=2.446)
    assert plain.tolist() == [[2, 1], [0, 3]] and eids.tolist() == [[3, 1], [3, 0]]
    np.testing.assert_allclose(jnp.sum(gates, axis=1), [2.446, 2.446], rtol=1e-6)
    s0 = [sig(-1.0), sig(1.0)]  # row 0: experts 3 and 1 by their own scores
    np.testing.assert_allclose(gates[0], [2.446 * s0[0] / sum(s0), 2.446 * s0[1] / sum(s0)], rtol=1e-6)
    s1 = [sig(1.0), sig(2.0)]  # row 1: expert 3 FIRST (0.73 + 0.6 > 0.88), weighted 0.73
    np.testing.assert_allclose(gates[1], [2.446 * s1[0] / sum(s1), 2.446 * s1[1] / sum(s1)], rtol=1e-6)
    _, combine = route_topk(w, x, 4, 2, 2, True, "sigmoid", bias=b, scale=2.446)
    np.testing.assert_allclose(jnp.sum(combine, axis=2)[0], [0, gates[0, 1], 0, gates[0, 0]], rtol=1e-6)
    # the reference's rule is the same one
    np.testing.assert_allclose(ref.gates_of(x, w, b, 2, 2.446)[1], [gates[1, 1], 0, 0, gates[1, 0]], rtol=1e-6)
    # and a layer's output moves with the bias (selection) but a bias on a
    # model without the flag is never read
    p = jax.tree.map(lambda a: a[0], init_params(CFG, jax.random.key(3), F32)["layers"])
    u = jax.random.normal(jax.random.key(4), (1, 8, CFG.dim), F32)
    cfg = dataclasses.replace(CFG, moe_impl="grouped")
    out, stats = llama._moe_ffn(p, u, cfg)
    moved, _ = llama._moe_ffn({**p, "router_bias": -p["router_bias"] * 5}, u, cfg)
    assert int(stats[0]) == 8 * CFG.top_k and rel(moved[0], out[0]) > 1e-3


def test_the_served_precision_reads_inside_the_limit_and_int4_outside():
    """int8 weights, bf16 activations and a bf16 latent cache against the
    float32 reference on the same weights, and the int4 control; the chip's
    limit at published widths is the reference module's own."""
    params = quantize_params(init_params(CFG, jax.random.key(0)))
    assert params["dense_layers"]["w_kvb"]["q"].dtype == jnp.int8
    assert params["layers"]["router_bias"].dtype == F32 and params["layers"]["router"].dtype == jnp.bfloat16
    sample = {"tokens": [int(t) for t in TOKS[0]], "rows": 50}
    want = ref.logits(params, MODEL, sample)
    assert 1e-3 < rel(through_the_pool(params, CFG, "xla", jnp.bfloat16), want) < 0.08
    assert rel(ref.logits(params, MODEL, sample, control=True), want) > 0.08


def _engine(**kw):
    from tpu_voice_agent.serve import PagedDecodeEngine

    cfg = dataclasses.replace(CFG, max_seq_len=1536)
    args = dict(cfg=cfg, max_len=1536, batch_slots=8, prefill_buckets=(128, 256, 1024),
                fast_forward=8, block_size=128, pool_blocks=80, quant=None)
    return PagedDecodeEngine(**{**args, **kw})


def test_the_engine_serves_it_behind_the_batcher_at_both_chunk_widths(monkeypatch):
    """The normal path: the prompt prefix prefilled through the scratch pool
    (``forward`` and its dense cache refuse this model), admissions behind it,
    chunks at the compacted and the full width, the routed counters, the
    attention row-blocks and the latent reads published."""
    from tpu_voice_agent.serve import ContinuousBatcher
    from tpu_voice_agent.services.prompts import render_prompt
    from tpu_voice_agent.utils import tracing

    fresh = tracing.Metrics()
    monkeypatch.setattr(tracing, "_GLOBAL_METRICS", fresh)
    eng = _engine(kernels="pallas")
    assert eng.cfg.moe_impl == "grouped" and eng.compact_rows == 2 and eng.latent and not eng.hybrid
    with pytest.raises(NotImplementedError, match="forward_paged"):
        llama.forward(eng.params, eng.cfg, jnp.zeros((1, 4), jnp.int32), jnp.arange(4)[None],
                      llama.init_kv_cache(eng.cfg, 1, 8))
    texts = ("go back", "scroll down", "open the settings page", "search for red shoes")
    assert eng.set_prompt_prefix(*(render_prompt(t, {}) for t in texts[:2])) > 800
    assert eng._prefix_tail["k"].shape[-1] == CFG.kv_lora_rank and eng._prefix_tail["v"].shape[-1] == CFG.qk_rope_dim
    chunks, decode_chunk = [], eng.decode_chunk
    monkeypatch.setattr(eng, "decode_chunk", lambda *a, **k: chunks.append(decode_chunk(*a, **k)) or chunks[-1])
    batcher = ContinuousBatcher(eng, chunk_steps=4, max_new_tokens=12)
    solo = batcher.generate_many([render_prompt(texts[0], {})])
    many = batcher.generate_many([render_prompt(t, {}) for t in texts])
    assert all(r.error is None for r in solo + many)
    assert {c.rows for c in chunks} == {2, 8}  # one live row rides the compacted width, four the full one
    assert all([(k, v.shape) for k, v in c.counts.items()] == [("moe", (4,)), ("attn", (3,)), ("latent", (2,)), ("kv", (1,))] for c in chunks)
    assert many[0].token_ids == solo[0].token_ids  # the same plan at either width
    counters = fresh.snapshot()["counters"]
    assert counters["moe.assigned_rows"] > 0 and counters["attn.row_blocks"] > 0
    # behind the pinned prefix most attended row-blocks are common, and the
    # kernel reads a common block once: fewer positions than rows x blocks
    assert 0 < counters["attn.common_row_blocks"] < counters["attn.row_blocks"]
    layers, bs = eng.cfg.n_layers, eng.block_size
    assert 0 < counters["attn.latent_keys_read"] < counters["attn.row_blocks"] * layers * bs
    assert counters["attn.latent_query_rows"] > 0


def test_a_group_s_admission_is_the_per_slot_admissions(monkeypatch):
    """Grouped admission (16 slots: ``admit_rows`` 2) behind the cached
    prefix writes the latents and picks the logits the per-slot path does."""
    from tpu_voice_agent.services.prompts import render_prompt

    texts = ("go back", "scroll down to the bottom of the page")
    prompts = [render_prompt(t, {}) for t in texts]

    def admitted(grouped: bool):
        eng = _engine(batch_slots=16, pool_blocks=140)
        eng.set_prompt_prefix(*prompts)
        ids = [eng.tokenizer.encode(p, bos=True) for p in prompts]
        assert eng.admit_rows == 2
        if grouped:
            group = [eng.prepare_admission(i, s) for s, i in enumerate(ids)]
            out = eng.admit_group(group, pick=_pick_logits)
            logits = np.asarray(out.picked)
        else:
            logits = np.concatenate([np.asarray(eng.prefill_slot(i, s)) for s, i in enumerate(ids)])
        owned = [eng._slot_owned[s][0] for s in range(2)]
        return logits, [np.asarray(eng.k_pool[:, b]) for b in owned], [len(i) for i in ids], eng

    one, planes_one, lens, eng = admitted(False)
    grp, planes_grp, _, _ = admitted(True)
    assert rel(grp, one) < 1e-4
    P = len(eng.prefix_ids)
    for a, b, n in zip(planes_one, planes_grp, lens):  # the suffix's latents, position by position
        lo, hi = P % 128, P % 128 + (n - P)
        assert float(np.abs(a[:, lo:hi].astype(np.float32) - b[:, lo:hi].astype(np.float32)).max()) < 1e-4


def _pick_logits(logits, state, slots, ns):
    return logits[:, 0, :]


@pytest.mark.parametrize("what", ["radix", "kv_quant", "handoff", "mesh", "dense_engine",
                                  "dense_forward", "pipeline"])
def test_what_moves_k_and_v_planes_refuses_a_latent_cache_by_type(what):
    """ONE typed error, where each is built or called."""
    from tpu_voice_agent.serve import DecodeEngine

    if what == "dense_forward":  # ``paged_only``'s refusal, as every such model's
        params = init_params(CFG, jax.random.key(0), F32)
        with pytest.raises(NotImplementedError, match="forward_paged"):
            llama.forward(params, CFG, jnp.zeros((1, 4), jnp.int32), jnp.arange(4)[None],
                          llama.init_kv_cache(CFG, 1, 8))
        return
    with pytest.raises(mla.LatentCacheOnly):
        if what == "radix":
            _engine(radix_enable=True)
        elif what == "kv_quant":
            _engine(kv_quant="int8")
        elif what == "handoff":
            _engine().gather_chain_kv([1])
        elif what == "mesh":
            from tpu_voice_agent.parallel import make_mesh

            _engine(mesh=make_mesh(dp=2, tp=1, devices=jax.devices()[:2]))
        elif what == "dense_engine":
            DecodeEngine(cfg=CFG, max_len=256, batch_slots=2, quant=None)
        else:  # the layer front half the pipeline and long-context paths share
            p = jax.tree.map(lambda a: a[0], init_params(CFG, jax.random.key(0), F32)["layers"])
            llama._layer_qkv(p, jnp.zeros((1, 4, CFG.dim), F32), CFG, None, None)
    assert issubclass(mla.LatentCacheOnly, ValueError)
