"""The LFM2 decoder with routed experts (``models/lfm2.py``; the benchmark's
``lfm2-8b-a1b-int8``) against its plain reference
(``benchmark/reference/lfm2_decoder.py``) at test widths on the CPU: each kind
of mixer alone, prefill then decode through the pool and the tails, ragged
blocks (a tail takes one, two or no new rows), the packed branch, the planted
faults, and what serving it asks of the paged engine with an EMPTY per-slot
side in the v pool (snapshot and restore, the compacted width, a grouped
admission, preemption and replay, the counters, the refusals)."""

import dataclasses
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import lfm2_stack, parse_stack
from benchmark.lib import refcheck
from benchmark.reference import decoder as dense_ref
from benchmark.reference import lfm2_decoder as ref
from tpu_voice_agent.models import lfm2, sambay
from tpu_voice_agent.models.family import family, tree_owner
from tpu_voice_agent.models.llama import forward_paged, init_params, quantize_params
from tpu_voice_agent.services.prompts import render_prompt

F32 = jnp.float32
# both mixers in an irregular order, ONE dense layer then routed ones, heads of 16 in pairs
CFG = lfm2.PRESETS["lfm2-test"]
GROUPED = dataclasses.replace(CFG, moe_impl="grouped")
BS, N, SLOTS = 16, 12, 3
ROOT = Path(__file__).resolve().parents[1]


def model_keys(cfg) -> dict:
    return {"num_hidden_layers": cfg.n_layers, "layer_kinds": cfg.pattern + "CC",
            "num_dense_layers": cfg.first_dense_layers, "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "num_experts_per_tok": cfg.top_k, "routed_scaling_factor": cfg.router_scale}


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float((np.abs(got - want).max(-1) / np.abs(want).max(-1)).max())


def pools(cfg, dtype=jnp.bfloat16, slots=SLOTS):
    from tpu_voice_agent.serve.paged import build_pools

    return build_pools(lfm2.cache_spec(cfg), N, BS, slots, zeros=lambda shape, dt: jnp.zeros(shape, dtype))


TABLE = jnp.asarray([[1, 2, 3, 4, 1]], jnp.int32)  # four blocks, then the slot's index
TOKS = jax.random.randint(jax.random.key(1), (1, 50), 0, CFG.vocab_size)
SAMPLE = {"tokens": [int(t) for t in TOKS[0]], "rows": 50}


def through_the_pool(params, cfg, impl, dtype, steps=(37, 1, 1, 1, 9, 1), **kw):
    """50 tokens as the engine feeds them: a prefill of 37, three T = 1 steps,
    one T = 9 block, one more step. -> (50, V) logits."""
    kp, vp = pools(cfg, dtype)
    rows, pos = [], 0
    for T in steps:
        out = forward_paged(params, cfg, TOKS[:, pos:pos + T], (pos + jnp.arange(T))[None], kp, vp,
                            TABLE, attn_impl=impl, **kw)
        rows.append(np.asarray(out[0][0]))
        kp, vp, pos = out[1], out[2], pos + T
    return np.concatenate(rows)


@pytest.fixture(scope="module")
def f32():
    params = init_params(CFG, jax.random.key(0), F32)
    return params, np.asarray(ref.logits(params, model_keys(CFG), SAMPLE))


def test_the_published_layer_types_give_18_and_6_and_two_dense_layers():
    pub = json.loads((ROOT / "benchmark/configs/lfm2-8b-a1b-int8.json").read_text())
    kinds = "".join(lfm2_stack._KINDS[k] for k in pub["layer_types"])
    assert kinds == pub["layer_kinds"] == "CCFCCCFCCCFCCCFCCCFCCFCC" and len(kinds) == pub["num_hidden_layers"] == 24
    assert kinds != (kinds[:4] * 6)  # not a period: the list ends F C C
    m, s = parse_stack.as_run(pub, False)
    cfg = lfm2_stack.llama_config(m, s)
    assert (cfg.pattern, cfg.count("C"), cfg.count("F")) == (kinds, 18, 6)
    assert [cfg.routed(i) for i in range(4)] == [False, False, True, True]
    assert (cfg.dim, cfg.dense_ffn_dim, cfg.ffn_dim, cfg.vocab_size, cfg.head_dim) == (2048, 7168, 1792, 65536, 64)
    assert (cfg.n_experts, cfg.top_k, cfg.d_conv, cfg.rope_theta, cfg.norm_eps) == (32, 4, 3, 1e6, 1e-5)
    # two K/V heads a 128-lane row; a request's tail 147 KB, its K/V 12288 B a token
    spec = lfm2.cache_spec(cfg)
    assert spec["planes"]["k"]["kv"] == (6, 4, 128) and spec["slot_planes"]["v"] == {}
    assert spec["slot_planes"]["k"]["tail"][0] == (18, 2 * 2048)
    assert family(cfg).token_bytes == 12288 and 18 * 2 * 2048 * 2 == 147456


@pytest.mark.parametrize("bad", [{"pattern": "FFFF"}, {"pattern": "CXF"}, {"n_kv_heads": 1, "n_heads": 8},
                                 {"d_conv": 1}, {"first_dense_layers": 7}, {"top_k": 9}])
def test_the_configuration_refuses_what_the_forward_does_not_run(bad):
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, **bad)


@pytest.mark.parametrize("kind", ["C", "F"])
def test_each_kind_of_mixer_alone_matches_the_plain_reference(kind):
    """One mixer of each kind, float32 weights and activations, 40 positions,
    the served functions against the reference's: 1e-4 of the output's range."""
    cfg, T = CFG, 40
    params = lfm2.init_params(cfg, jax.random.key(5), F32)
    x = jax.random.normal(jax.random.key(6), (1, T, cfg.dim), F32)
    p = jax.tree.map(lambda a: a[1], params[lfm2.KINDS[kind]])
    pos = jnp.arange(T)
    with jax.default_matmul_precision("highest"):
        if kind == "C":
            d = cfg.dim
            bcu = x @ p["in_proj"]
            gated = jnp.concatenate([bcu[..., :d] * bcu[..., 2 * d:], bcu[..., d:2 * d]], -1)
            mixed, tail = lfm2.short_conv(p["conv_w"], gated, jnp.zeros((1, 2, d), F32), jnp.asarray([T]))
            got = mixed @ p["out_proj"]
            want = ref.short_conv(x[0], p, dense_ref.dense)
            assert np.array_equal(np.asarray(tail[0]), np.asarray(gated[0, -2:, :d]))  # (g_{T-2}, g_{T-1})
        else:
            hd, nq, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
            qkv = x @ p["wqkv"]
            heads = lambda a, g: dense_ref.rms_norm(a.reshape(1, T, -1, hd), g, cfg.norm_eps)
            cos, sin = lfm2.rope_tables(pos[None], hd, cfg.rope_theta)
            q = lfm2.apply_rope(heads(qkv[..., :nq * hd], p["q_norm"]), cos, sin)
            k = lfm2.apply_rope(heads(qkv[..., nq * hd:(nq + nkv) * hd], p["k_norm"]), cos, sin)
            a = sambay._attend(q, k, qkv[..., (nq + nkv) * hd:].reshape(1, T, nkv, hd), pos[None], 1 << 30, hd ** -0.5)
            got = a.reshape(1, T, -1) @ p["wo"]
            want = ref.attention(x[0], pos, p, dense_ref.dense, nq=nq, nkv=nkv, eps=cfg.norm_eps, theta=cfg.rope_theta)
    assert rel(got[0], want) < 1e-4


def test_two_heads_stand_side_by_side_on_a_row_and_each_reads_its_own():
    """``pair_q`` / ``unpair`` around attention over K/V rows viewed in pairs IS
    attention over heads of their own width: the block kernel sees (n_kv / 2)
    heads of 2 hd, the result is the 16-wide heads' own — op by op AND under
    ``jit`` (on the TPU the pair written as slices and stacks was wrong under
    ``jit`` alone: ``benchmark/tools/shortconv_check.py --pairs`` is the chip's check)."""
    cfg, T = CFG, 9
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (2, T, cfg.n_heads, cfg.head_dim), F32)
    k = jax.random.normal(ks[1], (2, T, cfg.n_kv_heads, cfg.head_dim), F32)
    v = jax.random.normal(ks[2], (2, T, cfg.n_kv_heads, cfg.head_dim), F32)
    pos = jnp.tile(jnp.arange(T)[None], (2, 1))
    want = sambay._attend(q, k, v, pos, 1 << 30, 0.25)
    rows, side = cfg.kv_lanes
    assert (rows, side) == (2, 2)
    paired = lambda a: a.reshape(2, T, rows, side * cfg.head_dim)
    packed = lfm2.pair_q(q, cfg)
    assert packed.shape == (2, T, cfg.n_heads, 2 * cfg.head_dim)
    assert float(jnp.sum(packed != 0)) == float(jnp.sum(q != 0))  # zeros on the neighbour's lanes
    through = lambda q, k, v: lfm2.unpair(sambay._attend(lfm2.pair_q(q, cfg), paired(k), paired(v), pos, 1 << 30, 0.25), cfg)
    for got in (through(q, k, v), jax.jit(through)(q, k, v)):
        assert rel(got.reshape(2 * T, -1), want.reshape(2 * T, -1)) < 1e-5


@pytest.mark.parametrize("impl,cfg", [("xla", CFG), ("pallas", CFG), ("pallas", GROUPED)])
def test_prefill_then_decode_through_pool_and_tails_is_the_full_forward(impl, cfg, f32):
    """The whole stack in float32: prefill, T = 1 steps and a T = 9 block
    through the K/V pool and the tails against the reference's ONE full forward
    from an empty tail, LOGITS, on both attention paths and both dispatches
    (the Pallas kernels interpreted). 1e-4: float32 in another order."""
    params, want = f32
    assert rel(through_the_pool(params, cfg, impl, F32), want) < 1e-4


@pytest.mark.parametrize("steps", [(1, 1, 1, 9, 38), (2, 9, 9, 9, 16, 5), (50,)])
def test_suffixes_shorter_and_longer_than_the_convolution(steps, f32):
    """A first call of 1 or 2 positions (under the convolution's 3: the tail is
    part zeros, part inputs), blocks of 9 back to back, one call of 50."""
    params, want = f32
    assert rel(through_the_pool(params, CFG, "xla", F32, steps=steps), want) < 1e-4


def ragged_run(params, cfg, reals, pack, dtype=F32, **kw):
    """The 50 tokens through 1 + 8 blocks of which only ``reals[i]`` positions
    are real (the rest repeat the last real one, as a fast-forward block's
    padding does), beside a second row that is idle: what the chunk program
    feeds. -> the real positions' logits in order."""
    kp, vp = pools(cfg, dtype)
    tables = jnp.asarray([[1, 2, 3, 4, 1], [5, 6, 7, 8, 2]], jnp.int32)
    live = jnp.asarray([True, False])
    run = jax.jit(lambda toks, at, kp, vp, n: lfm2.forward_paged(  # (the module's own: it takes ``fault``)
        params, cfg, toks, at, kp, vp, tables, attn_impl="pallas", write_mask=live, n_real=n,
        ffn_pack=pack, **kw))
    rows, pos = [], 0
    for n in reals:
        t = jnp.minimum(jnp.arange(9), max(n - 1, 0))
        toks = jnp.stack([TOKS[0, pos + t], TOKS[0, t]])
        at = jnp.stack([pos + t, t])
        out = run(toks, at, kp, vp, jnp.asarray([n, 4], jnp.int32))
        rows.append(np.asarray(out[0][0, :n]))
        kp, vp, pos = out[1], out[2], pos + n
    return np.concatenate(rows), kp


@pytest.mark.parametrize("pack", [0, 8, 16])
def test_a_ragged_block_moves_a_tail_by_one_two_or_no_rows(pack, f32):
    """``n_real`` 0, 1, 2 and >= 3 a row: the tail takes no, one or two new rows
    (a row of 1 keeps one old row beside the new). Unpacked, packed where the
    real positions fit 8 or 16 slots (9 do not fit 8: that forward runs whole),
    the routed layers told their filler rows: the reference's logits."""
    params, want = f32
    reals = (3, 0, 1, 2, 1, 9, 0, 5, 2, 1, 7, 4, 9, 6)
    got, _ = ragged_run(params, GROUPED, reals, pack)
    assert sum(reals) == 50 and rel(got, want) < 1e-4


def test_a_row_with_no_real_position_keeps_its_tail_bit_for_bit():
    """Three rows of a 1 + 8 block: row 0 has 3 real positions, row 1 is idle,
    row 2 all 9, row 3 is live with NONE. Poisoning the tokens at every position
    that is not real leaves each row's tail, K/V outside the trash block and
    the real positions' logits BIT-equal; the idle row's and the empty row's
    tails are what they were."""
    params = init_params(CFG, jax.random.key(0))
    tables = jnp.asarray([[1, 2, 3, 0, 0], [4, 5, 6, 0, 1], [7, 8, 9, 0, 2], [10, 11, 0, 0, 3]], jnp.int32)
    n_real = jnp.asarray([3, 5, 9, 0], jnp.int32)
    live = jnp.asarray([True, False, True, True])
    pos = jnp.asarray([20, 0, 30, 7])[:, None] + jnp.minimum(jnp.arange(9)[None], jnp.maximum(n_real[:, None] - 1, 0))
    toks = jax.random.randint(jax.random.key(2), (4, 9), 0, CFG.vocab_size)
    real = (jnp.arange(9)[None] < n_real[:, None]) & live[:, None]
    poisoned = jnp.where(real, toks, (toks + 17) % CFG.vocab_size)
    noise = jax.random.normal(jax.random.key(4), pools(CFG, slots=4)[0]["tail"].shape).astype(jnp.bfloat16)

    def run(tokens):
        kp, vp = pools(CFG, slots=4)
        kp["tail"] = kp["tail"] + noise  # tails to keep
        return forward_paged(params, CFG, tokens, pos, kp, vp, tables, attn_impl="pallas",
                             write_mask=live, n_real=n_real, hybrid_stats=True, moe_stats=True,
                             attn_stats=True, kv_stats=True)

    a, b = run(toks), run(poisoned)
    assert np.array_equal(np.asarray(a[1]["tail"], np.float32), np.asarray(b[1]["tail"], np.float32))
    assert np.array_equal(np.asarray(a[1]["kv"][:, 1:], np.float32), np.asarray(b[1]["kv"][:, 1:], np.float32))
    assert np.array_equal(np.asarray(a[0])[np.asarray(real)], np.asarray(b[0])[np.asarray(real)])
    tail = np.asarray(a[1]["tail"], np.float32)
    assert np.array_equal(tail[:, [1, 3]], np.asarray(noise, np.float32)[:, [1, 3]])  # idle, and live with none
    assert not np.array_equal(tail[:, 0], np.asarray(noise, np.float32)[:, 0])  # a row that advanced did move
    assert set(a[2]) == {"kv"} and set(a[1]) == {"kv", "tail"}  # nothing per slot on the v side
    nc = CFG.count("C")
    assert np.asarray(a[5]).tolist() == [nc * 12, nc * 36, nc * 2]  # advanced, computed, tails moved
    assert np.asarray(a[6]).shape == (4,) and np.asarray(a[7]).shape == (3,) and np.asarray(a[8]).shape == (1,)


@pytest.mark.parametrize("fault", lfm2.FAULTS)
def test_every_planted_fault_moves_the_logits(fault, f32):
    """What ``benchmark/tools/shortconv_check.py`` plants on the chip moves the
    float32 logits far past the 1e-5 the sound forward reads here — over a
    prefill and ragged blocks, so that a tail taken at the block's end
    (``tail_at_T``) is not the tail at ``n_real``."""
    params, want = f32
    got, _ = ragged_run(params, GROUPED, (9, 9, 2, 9, 1, 3, 9, 8), 0, fault=fault)
    assert rel(got, want) > 1e-3
    sound, _ = ragged_run(params, GROUPED, (9, 9, 2, 9, 1, 3, 9, 8), 0)
    assert rel(sound, want) < 1e-4


def test_an_unknown_fault_is_refused():
    kp, vp = pools(CFG, F32)
    with pytest.raises(ValueError):
        lfm2.forward_paged(None, CFG, TOKS[:, :1], jnp.zeros((1, 1), jnp.int32), kp, vp, TABLE, fault="nope")


def test_the_bias_selects_and_the_gates_are_the_unbiased_scores():
    """A bias large enough to force experts 0 and 1 on every token: the picks
    are those two, the gates their sigmoid scores renormalised — the reference's
    gate matrix and ``moe.route_topk_flat`` agree, and neither carries the bias."""
    from tpu_voice_agent.models.moe import route_topk_flat

    h = jax.random.normal(jax.random.key(7), (12, 64), F32)
    router = jax.random.normal(jax.random.key(8), (64, 8), F32) * 0.125
    bias = jnp.asarray([5.0, 4.0, 0, 0, 0, 0, 0, 0])
    eids, gates = route_topk_flat(router, h, 8, 2, True, "sigmoid", bias=bias)
    assert np.asarray(eids).tolist() == [[0, 1]] * 12
    s = np.asarray(jax.nn.sigmoid(h @ router))[:, :2]
    assert np.allclose(np.asarray(gates), s / s.sum(1, keepdims=True), atol=1e-6)
    want = np.asarray(ref.gate_matrix(h, router, bias, top_k=2, scale=1.0))
    assert np.allclose(want[:, :2], np.asarray(gates), atol=1e-5) and not want[:, 2:].any()


def test_the_served_precision_reads_inside_the_limit_and_int4_outside():
    """int8 weights, bf16 activations, K/V and tails against the float32
    reference on the same weights, at the builder's recipe: the median row reads
    ~1-2 % (a row behind a fourth pick that flipped on a near tie several times
    that at these widths, where an expert is a larger share of the stream than
    at 2048: 2.6 %) where int4 weights, the precision below, read 15 % and more in
    EVERY row. The chip's limit at published widths is the reference module's own."""
    cfg = dataclasses.replace(GROUPED, dim=128, dense_ffn_dim=320, ffn_dim=96)
    params = lfm2.init_params(cfg, jax.random.key(0), quant=True, embed_std=lfm2_stack.EMBED_STD,
                              bias_std=lfm2_stack.BIAS_STD, routed_gain=lfm2_stack.ROUTED_GAIN,
                              mixer_gain=lfm2_stack.MIXER_GAIN)
    want = np.asarray(ref.logits(params, model_keys(cfg), SAMPLE))
    rows = lambda got: np.abs(np.asarray(got) - want).max(-1) / np.abs(want).max(-1)
    served = rows(through_the_pool(params, cfg, "xla", jnp.bfloat16))
    assert 1e-3 < np.median(served) < 0.03 and served.max() < ref.TOLERANCE
    control = rows(ref.logits(params, model_keys(cfg), SAMPLE, control=True))
    assert control.min() > 2 * ref.TOLERANCE and np.median(control) > 4 * ref.TOLERANCE


def test_a_tree_is_quantised_by_the_module_that_owns_it():
    params = init_params(CFG, jax.random.key(0))
    assert tree_owner(params) is lfm2 and "lm_head" not in params  # the head is the embedding
    q = quantize_params(params)
    assert set(q["shortconv"]["in_proj"]) == {"q", "s"} and q["shortconv"]["in_proj"]["q"].dtype == jnp.int8
    assert q["shortconv"]["conv_w"].dtype == jnp.bfloat16 and q["experts"]["router_bias"].dtype == F32
    assert q["experts"]["router"].dtype == jnp.bfloat16 and set(q["experts"]["moe_down"]) == {"q", "s"}
    assert set(q["attn"]["wqkv"]) == {"q", "s"} and set(q["dense"]["w_up"]) == {"q", "s"}
    # the tied head's int8 copy: the embedding's own values, a scale a vocabulary row
    assert q["lm_head"]["q"].shape == (CFG.dim, CFG.vocab_size) and q["lm_head"]["s"].shape == (1, CFG.vocab_size)
    back = np.asarray(q["lm_head"]["q"].astype(F32) * q["lm_head"]["s"]).T
    assert np.abs(back - np.asarray(params["embed"], np.float32)).max() < 0.01 * np.abs(back).max()
    drawn = lfm2.init_params(CFG, jax.random.key(0), quant=True)
    assert jax.tree.structure(drawn) == jax.tree.structure(q)
    gains = np.asarray(params["attn"]["q_norm"], np.float32)
    assert 0.5 <= gains.min() < 0.9 and 1.1 < gains.max() <= 1.5  # a norm's place shows by its gain alone


# ---------------------------------------------------------------- the engine


class _Inline:
    def submit_call(self, fn):
        fn()
        return self

    def result(self):
        return None


CONF = json.loads((ROOT / "benchmark/configs/lfm2-8b-a1b-int8.json").read_text())


def _engine(kernels="xla", batch_slots=4, **kw):
    """The configuration file's rehearsal widths through the builder's own functions."""
    from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
    from tpu_voice_agent.serve import PagedDecodeEngine
    from tpu_voice_agent.services.brain import install_prompt_prefix

    m, s = parse_stack.as_run(CONF, True)
    eng = PagedDecodeEngine(cfg=lfm2_stack.llama_config(m, s), tokenizer=default_tokenizer(),
                            quant="int8", batch_slots=batch_slots, block_size=128, pool_blocks=48,
                            max_len=1536, kernels=kernels, prefill_buckets=(128, 256, 1024),
                            fast_forward=8, init_weights=False, **kw)
    eng.load_params(lfm2_stack.make_params(eng.cfg, 23))
    install_prompt_prefix(eng)
    return eng, m


@pytest.fixture(scope="module")
def engine():
    return _engine()


def _generate(eng, texts, **kw):
    from tpu_voice_agent.serve import ContinuousBatcher

    bat = ContinuousBatcher(eng, chunk_steps=4, max_new_tokens=40, **kw)
    rids = [bat.submit(render_prompt(t, {})) for t in texts]
    chunks = []
    while not all(r in bat.results for r in rids):
        chunks.append(bat.step())
    assert all(bat.results[r].error is None for r in rids)
    return [bat.results[r].token_ids for r in rids], chunks


TEXTS = ("search for laptops under 1000", "go back", "scroll down")


def test_the_record_is_the_new_familys(engine):
    eng, _ = engine
    fam = family(eng.cfg)
    assert fam is eng.family and fam.name == "conv" and fam.module is lfm2
    assert [c.name for c in fam.counts] == ["hybrid", "moe", "attn", "kv"]
    assert fam.count("hybrid").metrics == lfm2.HYBRID_STATS
    assert fam.count("moe").metrics == ("moe.assigned_rows", "moe.padded_rows", "moe.experts_touched", "moe.load_max")
    assert fam.n_real == "always" and fam.one_head and fam.pack_rows == 96 and fam.scratch_prefix
    assert fam.cache["state_column"] and fam.cache["slot_planes"]["v"] == {}
    assert set(fam.cache["slot_planes"]["k"]) == {"tail"} and fam.kv_by_head
    c = eng.cfg
    assert eng.cfg.moe_impl == "grouped"  # chosen by the engine, no knob
    assert set(eng.v_pool) == {"kv"} and set(eng.k_pool) == {"kv", "tail"}
    assert eng.k_pool["tail"].shape == (c.count("C"), eng.batch_slots, 2 * c.dim)
    assert eng.k_pool["kv"].shape[0] == c.count("F") and eng.k_pool["kv"].shape[3:] == (2, 32)
    assert fam.token_bytes == 2 * 2 * c.n_kv_heads * c.head_dim * c.count("F")
    assert set(eng._prefix_state) == {"tail"}  # the snapshot: the tails alone


@pytest.mark.parametrize("kernels", ["xla", "pallas"])
def test_the_served_engine_matches_the_reference_on_the_comparisons_sample(kernels, engine):
    """What ``refcheck.sample_paged_decoder`` takes, blind to the block inside:
    the prefix's tails restored into the slot, the suffix prefilled behind them,
    three T = 1 steps and one T = 9 block through pool and tails — 13 rows
    against the reference's full forward, inside the cell's own ``TOLERANCE``,
    the int4 control outside it."""
    eng, m = engine if kernels == "xla" else _engine(kernels)
    served = SimpleNamespace(engine=eng, dims={"model": m}, parser=SimpleNamespace(runtime=_Inline()))
    params, model, sample, rows, _ = refcheck.sample_paged_decoder(served, seed=3)
    assert rows.shape[0] == 13 == sample["rows"]
    want = np.asarray(ref.logits(params, model, sample))
    by_row = lambda got: np.abs(np.asarray(got, np.float32) - want).max(-1) / np.abs(want).max(-1)
    assert by_row(rows).max() < ref.TOLERANCE
    assert by_row(ref.logits(params, model, sample, control=True)).max() > 2 * ref.TOLERANCE


def test_restoring_the_snapshot_is_prefilling_the_prefix_afresh(engine):
    eng, _ = engine
    ids = eng.tokenizer.encode(render_prompt("open the settings page", {}), bos=True)
    warm = np.asarray(eng.prefill_slot(ids, 0), np.float32)
    assert eng._last_cached_tokens == len(eng.prefix_ids) == 879
    warm_tail = np.asarray(eng.k_pool["tail"][:, 0], np.float32)
    eng.release_slot(0, ok=False)
    kept, eng.prefix_kv = eng.prefix_kv, None  # _split_prefix: no cached prefix applies
    try:
        cold = np.asarray(eng.prefill_slot(ids, 1), np.float32)
        assert eng._last_cached_tokens == 0
        cold_tail = np.asarray(eng.k_pool["tail"][:, 1], np.float32)
    finally:
        eng.prefix_kv = kept
        eng.release_slot(1, ok=False)
    assert rel(warm, cold) < 0.03
    assert np.abs(warm_tail - cold_tail).max() < 0.05 * np.abs(cold_tail).max()


def test_the_compacted_width_and_a_slot_used_again(engine):
    """One request alone rides the compacted chunk program (its table row, and
    with it its slot index, gathered by ``rows_idx``); beside two others the
    full width. The same tokens — snapshot -> restore -> decode is decode
    without an admission between: a request admitted into a slot another left
    gets the snapshot, not the leftover tail."""
    eng, _ = engine
    alone, chunks = _generate(eng, TEXTS[:1])
    assert {c.rows for c in chunks} == {eng.compact_rows} == {1}
    assert all(c.counts["hybrid"].shape == (3,) and c.counts["moe"].shape == (4,)
               and c.counts["attn"].shape == (3,) for c in chunks)
    together, chunks = _generate(eng, TEXTS)
    assert eng.batch_slots in {c.rows for c in chunks}
    assert together[0] == alone[0] and len(alone[0]) >= 8
    assert _generate(eng, TEXTS[:1])[0] == alone  # the slot was used in between


def test_a_preempted_request_replays_to_the_same_tokens(engine):
    """A request thrown out of its slot mid-stream (``release_slot(ok=False)``)
    and submitted again is admitted behind the snapshot and replays: the
    tokens it gave before."""
    from tpu_voice_agent.serve import ContinuousBatcher

    eng, _ = engine
    whole, _ = _generate(eng, TEXTS[:1])
    bat = ContinuousBatcher(eng, chunk_steps=4, max_new_tokens=40)
    bat.submit(render_prompt(TEXTS[0], {}))
    bat.step()
    bat.step()  # some tokens in: the slot's tail has moved on
    bat.reset()  # every slot released, nothing kept
    assert _generate(eng, TEXTS[:1])[0] == whole


def test_a_grouped_admission_is_the_admissions_one_by_one():
    """16 slots: two requests waiting when a step starts share ONE suffix
    forward (``admit_rows`` = 2) behind the restored snapshot; their streams are
    the ones they get alone."""
    eng, _ = _engine(batch_slots=16)
    assert eng.admit_rows == 2
    one_by_one = [_generate(eng, [t])[0][0] for t in TEXTS[:2]]
    grouped, _ = _generate(eng, TEXTS[:2])
    assert grouped == one_by_one


def test_the_batcher_publishes_the_tail_and_expert_counters(engine):
    from tpu_voice_agent.serve.paged import record_pool_gauges
    from tpu_voice_agent.utils import get_metrics

    eng, _ = engine
    before = dict(get_metrics().counter_state()[0])
    _generate(eng, TEXTS)
    after = get_metrics().counter_state()[0]
    d = {k: after.get(k, 0.0) - before.get(k, 0.0) for k in after}
    c = eng.cfg
    nc, routed = c.count("C"), c.n_layers - c.first_dense_layers
    assert d["conv.positions"] == d["scheduler.forward_rows"] * 9 * nc
    assert d["conv.positions_advanced"] == d["scheduler.tokens_generated"] * nc  # a token, a position
    assert 0 < d["conv.tail_rows_moved"] <= d["scheduler.forward_rows"] * nc
    # a block of 4 x 9 positions is under the packed width: it runs whole, every position routed
    assert d["moe.assigned_rows"] == d["scheduler.forward_rows"] * 9 * routed * c.top_k
    assert 0 < d["moe.experts_touched"] <= d["scheduler.forwards"] * routed * c.n_experts
    assert d["ssm.state_restores"] == 3  # the counter's name is older than this family
    record_pool_gauges(eng.allocator, eng)
    g = get_metrics().snapshot()["gauges"]
    assert g["paged.kv_bytes_per_token"] == 2 * 2 * c.n_kv_heads * c.head_dim * c.count("F")
    assert g["paged.state_bytes_per_slot"] == nc * (c.d_conv - 1) * c.dim * 2  # the tails, nothing else


@pytest.mark.parametrize("what", ["radix", "kv_quant", "mesh", "handoff", "chunked_prefill", "dense_cache"])
def test_every_refusal_raises_its_reason(what, engine):
    from tpu_voice_agent.serve import DecodeEngine

    eng, _ = engine
    fam = eng.family
    with pytest.raises(lfm2.StateNotCarried, match=what):
        fam.refuse(what)
    assert "Lfm2Config" in fam.refuses[what] or "convolution tail" in fam.refuses[what]
    if what == "handoff":
        with pytest.raises(lfm2.StateNotCarried):
            eng.gather_chain_kv([1])
    elif what == "chunked_prefill":
        ids = eng.tokenizer.encode(render_prompt("go back", {}), bos=True)
        assert eng.begin_chunked_prefill(ids, 0, 16) is None
    elif what == "dense_cache":
        with pytest.raises(lfm2.StateNotCarried):
            DecodeEngine(cfg=eng.cfg, tokenizer=eng.tokenizer, max_len=256, init_weights=False)
    else:
        kw = {"radix": {"radix_enable": True}, "kv_quant": {"kv_quant": "int8"},
              "mesh": {"mesh": jax.sharding.Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "tp"))}}[what]
        with pytest.raises(lfm2.StateNotCarried):
            _engine(**kw)


@pytest.mark.parametrize("name,noun", [("hybrid", "recurrent state"), ("ssd", "Mamba-2 state"),
                                       ("gdn", "delta-rule state"), ("conv", "convolution tail")])
def test_the_four_state_families_refuse_the_same_six_things(name, noun):
    """One function of the state's description builds every table: the same
    features, each reason naming its own state."""
    from tpu_voice_agent.models import family as fam_mod

    table = {"hybrid": fam_mod._HYBRID_REFUSES, "ssd": fam_mod._SSD_REFUSES, "gdn": fam_mod._GDN_REFUSES,
             "conv": fam_mod._CONV_REFUSES}[name]
    six = {"kv_quant", "radix", "mesh", "handoff", "chunked_prefill", "dense_cache"}
    assert six <= set(table) and set(table) - six <= {"ffn_pack"}
    for feature in six - {"dense_cache"}:
        assert noun in table[feature]
