"""Command A+ (``cohere2_moe``; the benchmark's ``command-a-plus-05-2026-int8``)
at test widths on the CPU: the served path against its plain reference
(``benchmark/reference/cohere2moe_decoder.py``) with a window that BINDS and
a full layer without positions, the chip's share of the experts against the
uncut layer, the sigmoid router against a hand computation, the grouped
kernel's tiled path, the interleaved rotation, and what the engine asks of
the model (prefix through the scratch pool, both chunk widths, the counters).
"""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import cohere2moe_stack, parse_stack
from benchmark.reference import cohere2moe_decoder as ref
from benchmark.reference import decoder as dense_ref
from tpu_voice_agent.models import llama
from tpu_voice_agent.models.llama import forward_paged, init_params, quantize_params

F32 = jnp.float32
CONF = json.loads((Path(__file__).parents[1] / "benchmark/configs/command-a-plus-05-2026-int8.json").read_text())
MODEL, SERVING = parse_stack.as_run(CONF, True)  # the file's rehearsal widths: window 16, 4 of 16 experts from id 4
MODEL["logit_scale"] = 0.5  # published 1: another value shows it is applied
CFG = dataclasses.replace(cohere2moe_stack.llama_config(MODEL, SERVING), max_seq_len=256)
BS, N = 16, 12
TABLE = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
TOKS = jax.random.randint(jax.random.key(1), (1, 50), 0, CFG.vocab_size)


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float((np.abs(got - want).max(-1) / np.abs(want).max(-1)).max())


def rel_all(got, want) -> float:
    """Over the whole array: a routed part has rows of zeros (no pick held)."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


def pools(cfg, dtype, n=N):
    shape = (cfg.n_layers, n, BS, cfg.n_kv_heads, cfg.head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def through_the_pool(params, cfg, impl, dtype, steps=(37, 1, 1, 1, 9, 1)):
    """50 tokens as the engine feeds them: a prefill of 37 (past the
    16-position window), three T = 1 steps, one 1 + 8 block, one more step —
    K/V through the paged pool. -> (50, V) logits."""
    kp, vp = pools(cfg, dtype)
    rows, pos = [], 0
    for T in steps:
        out = forward_paged(params, cfg, TOKS[:, pos:pos + T], (pos + jnp.arange(T))[None], kp, vp,
                            TABLE, attn_impl=impl, fresh_block=pos == 0)
        rows.append(np.asarray(out[0][0]))
        kp, vp, pos = out[1], out[2], pos + T
    return np.concatenate(rows)


def test_the_configuration_keeps_the_published_widths_and_names_its_cut():
    """The file's top level is the catalog's ``config`` but for the three
    keys in ``reduced``; the program's configuration reads every size from it."""
    assert [CONF[k] for k in ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
                              "intermediate_size", "num_experts_published", "num_experts_per_tok",
                              "num_shared_experts", "sliding_window", "rope_theta")] == \
        [4096, 128, 8, 128, 4096, 128, 8, 4, 4096, 50000]
    assert (CONF["num_hidden_layers"], CONF["num_experts"], CONF["vocab_size"]) == (8, 16, 32768)
    mistral = json.loads((Path(__file__).parents[1] / "benchmark/configs/mistral-7b-v0.1-int8.json").read_text())
    same = {k: v for k, v in CONF["serving"].items() if k != "weights_seed"}
    assert same == {k: v for k, v in mistral["serving"].items() if k != "weights_seed"}
    full = cohere2moe_stack.llama_config(*parse_stack.as_run(CONF, False))
    assert (full.head_dim, full.n_heads * full.head_dim, full.n_experts, full.n_held, full.top_k) == \
        (128, 16384, 128, 16, 8)
    assert full.layer_types == ("sliding", "sliding", "sliding", "full") * 2
    assert llama.bound_window(full) is None  # max_len 1536 <= 4096: the identity of ``assumed``
    assert llama.bound_window(dataclasses.replace(full, max_seq_len=5120)) == 4096
    assert llama.bound_window(CFG) == 16 and CFG.head_dim == 32 != CFG.dim // CFG.n_heads
    assert llama.layer_kinds(CFG) == ((True, 16),) * 3 + ((False, None),)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prefill_then_decode_through_the_pool_is_the_reference_full_forward(impl):
    """Float32 weights and activations: prefill, T = 1 steps and a 1 + 8
    block through the paged pool against the reference's ONE full forward —
    sliding layers behind a window of 16 (every step is past it), a full
    layer without positions, the parallel block, the sigmoid router over 16
    with 4 held from id 4, two shared experts, the tied head under a
    logit scale. Under "pallas" the block kernel walks the window at T = 1
    too, and the grouped kernel the held experts (interpreted). 1e-4: float32
    in another order; bf16 anywhere reads 1e-2."""
    params = init_params(CFG, jax.random.key(0), F32)
    cfg = dataclasses.replace(CFG, moe_impl="grouped" if impl == "pallas" else "dense")
    want = ref.logits(params, MODEL, {"tokens": [int(t) for t in TOKS[0]], "rows": 50})
    with jax.default_matmul_precision("highest"):
        assert rel(through_the_pool(params, cfg, impl, F32), want) < 1e-4


def test_a_window_that_binds_changes_the_answer_and_an_unbound_one_is_the_full_mask():
    """The identity the engine relies on: with ``max_seq_len`` at or under the
    window the sliding layers take the unbounded paths and give what the
    bounded ones give at a window no position reaches; at 16 the rows past
    position 16 differ."""
    params = init_params(CFG, jax.random.key(0), F32)
    wide = dataclasses.replace(CFG, sliding_window=256)  # max_seq_len 256: never binds
    assert llama.bound_window(wide) is None
    bound = dataclasses.replace(wide, max_seq_len=257)  # the same mask, through the window code
    with jax.default_matmul_precision("highest"):
        a, b = (through_the_pool(params, c, "pallas", F32) for c in (wide, bound))
        narrow = through_the_pool(params, CFG, "pallas", F32)
    assert rel(a, b) < 1e-5
    assert rel(narrow[:16], a[:16]) < 1e-5 and rel(narrow[20:], a[20:]) > 1e-2


def test_the_served_precision_reads_inside_the_limit_and_int4_outside():
    """int8 weights, bf16 activations and K/V against the float32 reference on
    the same weights, and the int4 control; the chip's limit at published
    widths is the reference module's own."""
    params = quantize_params(init_params(CFG, jax.random.key(0)))
    assert params["lm_head"]["q"].shape == (CFG.dim, CFG.vocab_size) and "mlp_norm" not in params["layers"]
    sample = {"tokens": [int(t) for t in TOKS[0]], "rows": 50}
    want = ref.logits(params, MODEL, sample)
    assert 1e-3 < rel(through_the_pool(params, CFG, "xla", jnp.bfloat16), want) < 0.06
    assert rel(ref.logits(params, MODEL, sample, control=True), want) > 0.06


def test_the_shares_add_up_to_the_uncut_layer():
    """The share test (model-configs guide, section 4): a 16-expert layer cut
    into four shares of 4. The routed parts the four chips compute, plus what
    every chip computes alike (the shared experts, attention) counted ONCE,
    add up to what the uncut reference gives for the whole layer; and one
    chip's whole layer is x + attention + its routed part + the shared mean."""
    whole_cfg = dataclasses.replace(CFG, experts_held=0, first_expert=0, moe_impl="grouped")
    p = jax.tree.map(lambda a: a[0], init_params(whole_cfg, jax.random.key(3), F32)["layers"])
    T = 24
    x = jax.random.normal(jax.random.key(4), (1, T, CFG.dim), F32)
    pos = jnp.arange(T)
    kw = {**ref.model_kw(MODEL), "first": 0}
    with jax.default_matmul_precision("highest"):
        uncut = ref.layer(x[0], pos, p, sliding=True, **kw)
        u = llama.layer_norm(x, p["attn_norm"], CFG.norm_eps)
        attn = ref.attention_part(u[0], pos, p, dense_ref.dense, nq=kw["nq"], nkv=kw["nkv"],
                                  theta=kw["theta"], window=kw["window"], sliding=True)
        shared = ref.shared_part(u[0], p, dense_ref.dense, n_shared=kw["n_shared"])
        parts, local = [], []
        for first in range(0, 16, 4):
            cfg = dataclasses.replace(whole_cfg, experts_held=4, first_expert=first)
            mine = {**p, **{k: p[k][first:first + 4] for k in ("moe_gate", "moe_up", "moe_down")}}
            out, stats = llama._moe_ffn(mine, u, cfg)
            assert rel_all(out[0], ref.routed_part(u[0], mine, dense_ref.dense, top_k=2, first=first)) < 1e-4
            parts.append(out[0])
            local.append(int(stats[4]))
            assert int(stats[0]) == T * 2 and stats.shape == (len(llama.MOE_SHARE_STATS),)
        assert sum(local) == T * 2  # every assignment lands on exactly one chip
        assert rel(x[0] + attn + shared + sum(parts), uncut) < 1e-4
        # all held: the same program as ever, four counters
        out, stats = llama._moe_ffn(p, u, whole_cfg)
        assert rel(out[0], sum(parts)) < 1e-4 and stats.shape == (4,) and int(stats[0]) == T * 2
        dense_out, dense_stats = llama._moe_ffn(
            {**p, **{k: p[k][4:8] for k in ("moe_gate", "moe_up", "moe_down")}}, u,
            dataclasses.replace(whole_cfg, experts_held=4, first_expert=4, moe_impl="dense"))
    assert rel_all(dense_out[0], parts[1]) < 1e-4 and int(dense_stats[4]) == local[1]


def test_an_assignment_to_an_absent_expert_takes_no_row_no_padding_and_no_tile():
    """Tokens whose picks all live elsewhere: zero rows computed, zero held
    experts touched, a zero routed part — and nothing that is not a number
    (the kernel leaves the rows of skipped tiles unwritten)."""
    cfg = dataclasses.replace(CFG, moe_impl="grouped")
    p = jax.tree.map(lambda a: a[0], init_params(cfg, jax.random.key(3), F32)["layers"])
    router = jnp.zeros((CFG.dim, 16), F32).at[0, :2].set(50.0)  # experts 0 and 1 win for x[..., 0] > 0
    u = jnp.abs(jax.random.normal(jax.random.key(5), (1, 8, CFG.dim), F32))
    out, stats = llama._moe_ffn({**p, "router": router}, u, cfg)
    assert [int(s) for s in stats] == [16, 0, 0, 0, 0] and float(jnp.abs(out).max()) == 0.0


def test_sigmoid_gates_are_each_expert_s_own_score_over_the_chosen_sum():
    """Against a hand computation; the softmax rules read as before."""
    from tpu_voice_agent.models.moe import route_topk, route_topk_flat

    x = jnp.asarray([[1.0, 0.0], [0.0, 2.0]], F32)
    w = jnp.asarray([[0.0, 1.0, 2.0, -1.0], [1.0, 0.0, -1.0, 0.5]], F32)
    sig = lambda z: 1.0 / (1.0 + np.exp(-z))
    eids, gates = route_topk_flat(w, x, 4, 2, True, "sigmoid")
    assert eids.tolist() == [[2, 1], [0, 3]]
    np.testing.assert_allclose(gates, [[sig(2) / (sig(2) + sig(1)), sig(1) / (sig(2) + sig(1))],
                                       [sig(2) / (sig(2) + sig(1)), sig(1) / (sig(2) + sig(1))]], rtol=1e-6)
    _, raw = route_topk_flat(w, x, 4, 2, False, "sigmoid")
    np.testing.assert_allclose(raw, [[sig(2), sig(1)], [sig(2), sig(1)]], rtol=1e-6)
    _, combine = route_topk(w, x, 4, 2, 2, True, "sigmoid")
    np.testing.assert_allclose(jnp.sum(combine, axis=2)[0], [0, gates[0, 1], gates[0, 0], 0], rtol=1e-6)
    soft = np.exp([0.0, 1.0, 2.0, -1.0]) / np.exp([0.0, 1.0, 2.0, -1.0]).sum()
    e2, g2 = route_topk_flat(w, x, 4, 2, False)
    assert e2[0].tolist() == [2, 1]
    np.testing.assert_allclose(g2[0], [soft[2], soft[1]], rtol=1e-6)
    with pytest.raises(ValueError, match="softmax or sigmoid"):
        route_topk_flat(w, x, 4, 2, True, "tanh")


def test_the_interleaved_rotation_pairs_neighbouring_lanes():
    """``rope_gptj``: lanes (2i, 2i+1) rotate by pos * theta^(-2i/hd) — the
    published pairing, by hand; the half-split form pairs (i, i + hd/2)."""
    hd, theta = 8, 50000.0
    x = jax.random.normal(jax.random.key(2), (1, 3, 2, hd), F32)
    pos = jnp.asarray([[0, 5, 77]])
    cos, sin = llama.rope_tables(pos, hd, theta)
    got = np.asarray(llama.apply_rope_interleaved(x, cos, sin))
    want = np.empty_like(got)
    for t, p in enumerate([0, 5, 77]):
        for i in range(hd // 2):
            ang = p * theta ** (-2 * i / hd)
            a, b = np.asarray(x[0, t, :, 2 * i]), np.asarray(x[0, t, :, 2 * i + 1])
            want[0, t, :, 2 * i] = a * np.cos(ang) - b * np.sin(ang)
            want[0, t, :, 2 * i + 1] = b * np.cos(ang) + a * np.sin(ang)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got, ref.rope_pairs(x[0], pos[0], theta)[None], rtol=1e-5, atol=1e-6)
    half = np.asarray(llama.apply_rope(x, cos, sin))
    assert np.abs(half - got)[0, 1:].max() > 1e-2 and np.allclose(half[0, 0], got[0, 0])


@pytest.mark.parametrize("leaf", ["int8", "raw"])
def test_the_tiled_grouped_matmul_matches_its_twin_at_a_plane_past_the_vmem_budget(leaf):
    """A (2048, 4096) plane is 8 MiB as int8, over ``_PLANE_BYTES``: the
    kernel walks it in (tk, tn) tiles with the float32 accumulator, chosen by
    ``plane_tiles`` itself. Tiles past ``n_tiles`` are skipped — their block
    indices stand still — and an expert without rows is never named."""
    import importlib

    gm = importlib.import_module("tpu_voice_agent.ops.grouped_matmul")  # the package re-exports the function
    E, d, f, tm = 3, 2048, 4096, 16
    raw = jax.random.normal(jax.random.key(0), (E, d, f), F32) * d ** -0.5
    w = llama.quantize_leaf(raw) if leaf == "int8" else raw.astype(jnp.bfloat16)
    size = 1 if leaf == "int8" else 2
    tk, tn = gm.plane_tiles(d, f, size)
    assert d * f * size > gm._PLANE_BYTES and (tk, tn) != (d, f) and tk * tn * size <= gm._PLANE_BYTES
    x = jax.random.normal(jax.random.key(1), (5 * tm, d), F32).astype(jnp.bfloat16)
    experts = jnp.asarray([0, 2, 2, 2, 2], jnp.int32)  # expert 1 has no rows; the last tile is past n_tiles
    got = gm.grouped_matmul(x, w, experts, jnp.int32(4), tm=tm)
    want = gm.grouped_matmul_reference(x, w, experts, tm)
    assert rel(got[: 4 * tm], want[: 4 * tm]) < 0.01
    stacked = jax.tree.map(lambda a: jnp.stack([jnp.zeros_like(a), a]), w)  # the layer in the scalar prefetch
    again = gm.grouped_matmul(x, stacked, experts, jnp.int32(4), jnp.int32(1), tm=tm)
    assert rel(again[: 4 * tm], want[: 4 * tm]) < 0.01
    nothing = gm.grouped_matmul(x, w, jnp.zeros((5,), jnp.int32), jnp.int32(0), tm=tm)
    assert nothing.shape == (5 * tm, f)  # no tile is real: nothing computed, nothing read back


def _engine(**kw):
    from tpu_voice_agent.serve import PagedDecodeEngine

    cfg = dataclasses.replace(CFG, max_seq_len=1536)
    args = dict(cfg=cfg, max_len=1536, batch_slots=8, prefill_buckets=(128, 256, 1024),
                fast_forward=8, block_size=128, pool_blocks=80, quant=None)
    return PagedDecodeEngine(**{**args, **kw})


def test_the_engine_serves_it_behind_the_batcher_at_both_chunk_widths(monkeypatch):
    """The normal path: the prompt prefix prefilled through the scratch pool
    (``forward`` and its dense cache refuse this model), admissions behind it,
    chunks at the compacted and the full width, the five routed counters and
    the attention row-blocks published — and the prefix's K/V equal to what a
    whole prefill of the same prompt writes."""
    from tpu_voice_agent.serve import ContinuousBatcher
    from tpu_voice_agent.services.prompts import render_prompt
    from tpu_voice_agent.utils import tracing

    fresh = tracing.Metrics()
    monkeypatch.setattr(tracing, "_GLOBAL_METRICS", fresh)
    eng = _engine()
    assert eng.cfg.moe_impl == "grouped" and eng.compact_rows == 2 and not eng.hybrid
    with pytest.raises(NotImplementedError, match="forward_paged"):
        llama.forward(eng.params, eng.cfg, jnp.zeros((1, 4), jnp.int32), jnp.arange(4)[None],
                      llama.init_kv_cache(eng.cfg, 1, 8))
    texts = ("go back", "scroll down", "open the settings page", "search for red shoes")
    assert eng.set_prompt_prefix(*(render_prompt(t, {}) for t in texts[:2])) > 800
    chunks, decode_chunk = [], eng.decode_chunk
    monkeypatch.setattr(eng, "decode_chunk", lambda *a, **k: chunks.append(decode_chunk(*a, **k)) or chunks[-1])
    batcher = ContinuousBatcher(eng, chunk_steps=4, max_new_tokens=12)
    solo = batcher.generate_many([render_prompt(texts[0], {})])
    many = batcher.generate_many([render_prompt(t, {}) for t in texts])
    assert all(r.error is None for r in solo + many)
    assert {c.rows for c in chunks} == {2, 8}  # one live row rides the compacted width, four the full one
    assert all(c.counts["moe"].shape == (5,) and c.counts["attn"].shape == (3,) for c in chunks)
    assert many[0].token_ids == solo[0].token_ids  # the same plan at either width
    counters = fresh.snapshot()["counters"]
    assert 0 < counters["moe.local_rows"] < counters["moe.assigned_rows"]
    assert counters["moe.padded_rows"] >= counters["moe.local_rows"] and counters["attn.row_blocks"] > 0


def test_costs_and_budgets_count_the_held_planes_or_refuse_by_name():
    """``param_count`` and the cost model count what THIS chip holds and
    computes — 16 held and 4 shared planes a layer, 8 x 16/128 = 1 routed and 4
    shared experts a token, the tied head once — and the pp x tp budget
    refuses a routed model rather than size a dense decoder."""
    from tpu_voice_agent.utils import costmodel, hbm_budget

    full = cohere2moe_stack.llama_config(*parse_stack.as_run(CONF, False))
    per_layer = 142.6e6 + 0.5e6 + 4 * 50.33e6 + 16 * 50.33e6
    assert abs(llama.param_count(full) - (8 * per_layer + 32768 * 4096)) < 0.01 * 8 * per_layer
    step = costmodel.decode_step_bytes(full, batch=32, context_tokens=1024)
    assert abs(step["weights_bytes"] - (8 * (per_layer - 0.5e6) + 32768 * 4096)) < 0.01 * 8 * per_layer
    assert step["kv_read_bytes"] == 32 * 1024 * 8 * 8 * 128 * 2 * 2
    flops = costmodel.llm_token_flops(full)
    want = 2 * (8 * (142.6e6 + (1 + 4) * 50.33e6 + 4096 * 128) + 32768 * 4096)
    assert abs(flops - want) < 0.01 * want
    olmoe = llama.LlamaConfig(dim=2048, n_layers=16, n_heads=16, n_kv_heads=16, ffn_dim=1024,
                              n_experts=64, top_k=8, vocab_size=50304)
    assert costmodel.decode_step_bytes(olmoe, 1, 0)["weights_bytes"] > 16 * 64 * 3 * 2048 * 1024
    with pytest.raises(ValueError, match="128 experts"):
        hbm_budget.pp_tp_hbm_per_chip(full, 2, 4, batch_slots=32, max_len=2048)
