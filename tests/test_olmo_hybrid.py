"""The OLMo hybrid decoder (``models/olmo_hybrid.py``; the benchmark's
``olmo-hybrid-7b-int8``) against its plain reference
(``benchmark/reference/olmo_hybrid_decoder.py``) at test widths on the CPU:
each kind of mixer alone, prefill then decode through pool and state planes,
the reordered norm, the planted faults, and what serving it asks of the paged
engine (snapshot and restore, the compacted width, a grouped admission,
preemption and replay, the counters, the refusals)."""

import dataclasses
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import olmo_hybrid_stack, parse_stack
from benchmark.lib import refcheck
from benchmark.reference import decoder as dense_ref
from benchmark.reference import olmo_hybrid_decoder as ref
from tpu_voice_agent.models import olmo_hybrid as oh
from tpu_voice_agent.models import sambay
from tpu_voice_agent.models.family import family, tree_owner
from tpu_voice_agent.models.llama import forward_paged, init_params, quantize_params
from tpu_voice_agent.ops import gated_delta
from tpu_voice_agent.services.prompts import render_prompt

F32 = jnp.float32
# the published period (three linear layers, one full) TWICE; d_v = 1.5 d_k and no lane multiple
CFG = oh.PRESETS["olmo-hybrid-test"]
BS, N, SLOTS = 16, 12, 3
ROOT = Path(__file__).resolve().parents[1]


def model_keys(cfg) -> dict:
    return {"num_hidden_layers": cfg.n_layers, "layer_kinds": cfg.pattern + "LLLF",
            "linear_num_value_heads": cfg.gdn_heads, "linear_key_head_dim": cfg.gdn_key_dim,
            "linear_value_head_dim": cfg.gdn_value_dim, "linear_allow_neg_eigval": cfg.neg_eigval,
            "num_attention_heads": cfg.n_heads, "num_key_value_heads": cfg.n_kv_heads,
            "rms_norm_eps": cfg.norm_eps}


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float((np.abs(got - want).max(-1) / np.abs(want).max(-1)).max())


def pools(cfg, dtype=jnp.bfloat16, slots=SLOTS):
    from tpu_voice_agent.serve.paged import build_pools

    return build_pools(oh.cache_spec(cfg), N, BS, slots,
                       zeros=lambda shape, dt: jnp.zeros(shape, dt if dt == jnp.float32 else dtype))


TABLE = jnp.asarray([[1, 2, 3, 4, 1]], jnp.int32)  # four blocks, then the slot's state index
TOKS = jax.random.randint(jax.random.key(1), (1, 50), 0, CFG.vocab_size)


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


def test_the_published_pattern_is_three_linear_layers_and_a_full_one_eight_times():
    pub = json.loads((ROOT / "benchmark/configs/olmo-hybrid-7b-int8.json").read_text())
    kinds = "".join(olmo_hybrid_stack._KINDS[k] for k in pub["layer_types"])
    assert kinds == pub["layer_kinds"] == "LLLF" * 8 and len(kinds) == pub["num_hidden_layers"] == 32
    m, s = parse_stack.as_run(pub, False)
    cfg = olmo_hybrid_stack.llama_config(m, s)
    assert (cfg.pattern, cfg.count("L"), cfg.count("F")) == (kinds, 24, 8)
    assert (cfg.dim, cfg.ffn_dim, cfg.vocab_size, cfg.head_dim) == (3840, 11008, 100352, 128)
    assert (cfg.gdn_heads, cfg.gdn_key_dim, cfg.gdn_value_dim, cfg.d_conv) == (30, 96, 192, 4)
    assert (cfg.key_dim, cfg.value_dim, cfg.conv_dim, cfg.kv_heads_held) == (2880, 5760, 11520, 32)
    with pytest.raises(ValueError):
        oh.OlmoHybridConfig(pattern="FFFF")


@pytest.mark.parametrize("kind", ["L", "F"])
def test_each_kind_of_mixer_alone_matches_the_plain_reference(kind):
    """One mixer of each kind, float32 weights and activations, 40 positions,
    the served functions against the reference's: 1e-4 of the output's range."""
    cfg, T = CFG, 40
    params = oh.init_params(cfg, jax.random.key(5), F32)
    x = jax.random.normal(jax.random.key(6), (1, T, cfg.dim), F32)
    p = jax.tree.map(lambda a: a[1], params[oh.KINDS[kind]])
    dense = dense_ref.dense
    with jax.default_matmul_precision("highest"):
        if kind == "L":
            _, vp = pools(cfg, F32)
            tail = jnp.zeros((1, cfg.d_conv - 1, cfg.conv_dim), F32)
            mixed, _, _ = oh.gdn_mix(p, x @ p["in_proj"], x @ p["ab"], tail, vp["gdn"], jnp.asarray([1]),
                                     jnp.int32(0), jnp.asarray([T]), cfg, "xla")
            got = mixed @ p["wo"]
            want = ref.gated_deltanet(x[0], p, dense, H=cfg.gdn_heads, dk=cfg.gdn_key_dim,
                                      dv=cfg.gdn_value_dim, neg=True, eps=cfg.norm_eps)
        else:
            hd, nq = cfg.head_dim, cfg.n_heads * cfg.head_dim
            qkv = x @ p["wqkv"]
            norm = lambda a, g: dense_ref.rms_norm(a, g, cfg.norm_eps)
            heads = lambda a: a.reshape(1, T, -1, hd)
            a = sambay._attend(heads(norm(qkv[..., :nq], p["q_norm"])), heads(norm(qkv[..., nq:2 * nq], p["k_norm"])),
                               heads(qkv[..., 2 * nq:]), jnp.arange(T)[None], 1 << 30, hd ** -0.5)
            got = a.reshape(1, T, -1) @ p["wo"]
            want = ref.attention(x[0], p, dense, nq=cfg.n_heads, nkv=cfg.n_kv_heads, eps=cfg.norm_eps)
    assert rel(got[0], want) < 1e-4


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prefill_then_decode_through_pool_and_state_is_the_full_forward(impl):
    """The whole stack in float32: prefill, T = 1 steps and a T = 9 block
    through the K/V pool and the state planes against the reference's ONE full
    forward from an empty state, LOGITS, on both attention / scan paths (the
    Pallas kernels interpreted). 1e-4: float32 in another order."""
    params = init_params(CFG, jax.random.key(0), F32)
    want = ref.logits(params, model_keys(CFG), {"tokens": [int(t) for t in TOKS[0]], "rows": 50})
    assert rel(through_the_pool(params, CFG, impl, F32), want) < 1e-4


@pytest.mark.parametrize("steps", [(2, 1, 1, 9, 37), (3, 9, 9, 9, 16, 4), (50,)])
def test_suffixes_shorter_and_longer_than_the_convolution(steps):
    """A first call of 2 or 3 positions (under the convolution's 4: the tail is
    part zeros, part inputs), blocks of 9 back to back, one call of 50."""
    params = init_params(CFG, jax.random.key(0), F32)
    want = ref.logits(params, model_keys(CFG), {"tokens": [int(t) for t in TOKS[0]], "rows": 50})
    assert rel(through_the_pool(params, CFG, "xla", F32, steps=steps), want) < 1e-4


def test_the_norm_stands_on_each_sub_layers_output():
    """h = x + norm(Mixer(x)): scaling a mixer's out projection by 4 changes
    nothing but what the norm's eps sees (the norm divides it out: 0.5 % here),
    scaling its norm's gain by 4 does — the opposite of a pre-norm block."""
    params = init_params(CFG, jax.random.key(0), F32)
    base = through_the_pool(params, CFG, "xla", F32, steps=(50,))
    scaled = {**params, "gdn": {**params["gdn"], "wo": params["gdn"]["wo"] * 4.0}}
    assert rel(through_the_pool(scaled, CFG, "xla", F32, steps=(50,)), base) < 0.02
    gained = {**params, "gdn": {**params["gdn"], "mixer_norm": params["gdn"]["mixer_norm"] * 4.0}}
    assert rel(through_the_pool(gained, CFG, "xla", F32, steps=(50,)), base) > 0.1


@pytest.mark.parametrize("fault", oh.FAULTS)
def test_every_planted_fault_moves_the_logits(fault):
    """What ``benchmark/tools/gdn_check.py`` plants on the chip moves the
    float32 logits far past the 1e-5 the sound forward reads here (the state
    rounded to bf16 where it is read the least, on the decoded rows alone) —
    on ONE period of the pattern, a prefill and two blocks."""
    cfg = dataclasses.replace(CFG, pattern="LLLF")
    params = init_params(cfg, jax.random.key(0), F32)
    want = ref.logits(params, model_keys(cfg), {"tokens": [int(t) for t in TOKS[0]], "rows": 50})
    kp, vp = pools(cfg, F32)
    rows, pos = [], 0
    for T in (37, 9, 4):
        out = oh.forward_paged(params, cfg, TOKS[:, pos:pos + T], (pos + jnp.arange(T))[None], kp, vp,
                               TABLE, attn_impl="xla", fault=fault)
        rows.append(np.asarray(out[0][0]))
        kp, vp, pos = out[1], out[2], pos + T
    assert not rel(np.concatenate(rows), want) <= 2e-4  # (without the l2 norm the state overflows: nan)


def test_an_unknown_fault_is_refused():
    kp, vp = pools(CFG, F32)
    with pytest.raises(ValueError):
        oh.forward_paged(None, CFG, TOKS[:, :1], jnp.zeros((1, 1), jnp.int32), kp, vp, TABLE, fault="nope")


def test_the_served_precision_reads_inside_the_limit_and_int4_outside():
    """int8 weights, bf16 activations, K/V and convolution tail, float32 state
    against the float32 reference on the same weights, at the rehearsal's
    recipe (``olmo_hybrid_stack``'s gains): the median row reads ~1 % where
    int4 weights, the precision below, read over 10 % in every row. The chip's
    limit at published widths is the reference module's own."""
    cfg = dataclasses.replace(CFG, dim=128, ffn_dim=192, head_size=32, gdn_key_dim=32, gdn_value_dim=48)
    params = oh.init_params(cfg, jax.random.key(0), quant=True, embed_std=olmo_hybrid_stack.EMBED_STD,
                            mixer_gain=olmo_hybrid_stack.MIXER_GAIN)
    sample = {"tokens": [int(t) for t in TOKS[0]], "rows": 50}
    want = np.asarray(ref.logits(params, model_keys(cfg), sample))
    rows = lambda got: np.abs(np.asarray(got) - want).max(-1) / np.abs(want).max(-1)
    served = rows(through_the_pool(params, cfg, "xla", jnp.bfloat16))
    assert 1e-3 < np.median(served) < 0.03 and served.max() < 0.06
    control = rows(ref.logits(params, model_keys(cfg), sample, control=True))
    assert control.min() > 0.05 and np.median(control) > 0.15


def test_a_tree_is_quantised_by_the_module_that_owns_it():
    params = init_params(CFG, jax.random.key(0))
    assert tree_owner(params) is oh
    q = quantize_params(params)
    assert set(q["gdn"]["in_proj"]) == {"q", "s"} and q["gdn"]["in_proj"]["q"].dtype == jnp.int8
    assert q["gdn"]["ab"].dtype == jnp.bfloat16 and q["gdn"]["A_log"].dtype == F32
    assert set(q["attn"]["wqkv"]) == {"q", "s"} and set(q["lm_head"]) == {"q", "s"}
    drawn = oh.init_params(CFG, jax.random.key(0), quant=True)
    assert jax.tree.structure(drawn) == jax.tree.structure(q)
    # the published initialisation: log-decay rates in (0, 16), a step in [1e-3, 1e-1]
    a = np.exp(np.asarray(params["gdn"]["A_log"]))
    dt = np.log1p(np.exp(np.asarray(params["gdn"]["dt_bias"])))
    assert 0 < a.min() and a.max() < 16 and 1e-4 <= dt.min() and dt.max() <= 0.1 + 1e-6


def test_the_state_advances_over_the_real_positions_and_no_others():
    """Three rows of a 1 + 8 block: row 0 has 3 real positions, row 1 is idle,
    row 2 all 9. Poisoning the tokens at every position that is NOT real
    leaves each row's state, tail, K/V outside the trash block and the real
    positions' logits BIT-equal, and the idle row's state and tail are what
    they were."""
    params = init_params(CFG, jax.random.key(0))
    tables = jnp.asarray([[1, 2, 3, 0, 0], [4, 5, 6, 0, 1], [7, 8, 9, 0, 2]], jnp.int32)
    n_real = jnp.asarray([3, 5, 9], jnp.int32)
    live = jnp.asarray([True, False, True])
    pos = jnp.asarray([20, 0, 30])[:, None] + jnp.minimum(jnp.arange(9)[None], n_real[:, None] - 1)
    toks = jax.random.randint(jax.random.key(2), (3, 9), 0, CFG.vocab_size)
    real = (jnp.arange(9)[None] < n_real[:, None]) & live[:, None]
    poisoned = jnp.where(real, toks, (toks + 17) % CFG.vocab_size)

    def run(tokens):
        kp, vp = pools(CFG)
        vp["gdn"] = vp["gdn"] + 0.25  # a state to keep
        kp["tail"] = kp["tail"] + 0.5
        return forward_paged(params, CFG, tokens, pos, kp, vp, tables, attn_impl="pallas",
                             write_mask=live, n_real=n_real, hybrid_stats=True, attn_stats=True)

    a, b = run(toks), run(poisoned)
    assert np.array_equal(np.asarray(a[2]["gdn"]), np.asarray(b[2]["gdn"]))
    assert np.array_equal(np.asarray(a[1]["tail"], np.float32), np.asarray(b[1]["tail"], np.float32))
    assert np.array_equal(np.asarray(a[1]["kv"][:, 1:], np.float32), np.asarray(b[1]["kv"][:, 1:], np.float32))
    assert np.array_equal(np.asarray(a[0])[np.asarray(real)], np.asarray(b[0])[np.asarray(real)])
    assert np.all(np.asarray(a[2]["gdn"][:, 1]) == 0.25) and np.all(np.asarray(a[1]["tail"][:, 1], np.float32) == 0.5)
    assert not np.all(np.asarray(a[2]["gdn"][:, 0]) == 0.25)  # a live row's did move
    nl = CFG.count("L")
    assert np.asarray(a[5]).tolist() == [nl * 12, nl * 27, nl * 2]  # advanced, computed, states moved
    # the planes hold the K/V heads in whole sublane tiles: the heads past the model's are zeros
    assert a[1]["kv"].shape[3] == CFG.kv_heads_held == 8 > CFG.n_kv_heads
    assert not np.any(np.asarray(a[1]["kv"][:, :, :, CFG.n_kv_heads:], np.float32))


# ---------------------------------------------------------------- the engine


class _Inline:
    def submit_call(self, fn):
        fn()
        return self

    def result(self):
        return None


CONF = json.loads((ROOT / "benchmark/configs/olmo-hybrid-7b-int8.json").read_text())


def _engine(kernels="xla", batch_slots=4, **kw):
    """The configuration file's rehearsal widths through the builder's own functions."""
    from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
    from tpu_voice_agent.serve import PagedDecodeEngine
    from tpu_voice_agent.services.brain import install_prompt_prefix

    m, s = parse_stack.as_run(CONF, True)
    eng = PagedDecodeEngine(cfg=olmo_hybrid_stack.llama_config(m, s), tokenizer=default_tokenizer(),
                            quant="int8", batch_slots=batch_slots, block_size=128, pool_blocks=48,
                            max_len=1536, kernels=kernels, prefill_buckets=(128, 256, 1024),
                            fast_forward=8, init_weights=False, **kw)
    eng.load_params(olmo_hybrid_stack.make_params(eng.cfg, 23))
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
    assert fam is eng.family and fam.name == "gdn" and fam.module is oh
    assert [c.name for c in fam.counts] == ["hybrid", "attn", "kv"]
    assert fam.count("hybrid").metrics == oh.HYBRID_STATS
    assert fam.n_real == "always" and fam.one_head and fam.pack_rows == 96 and fam.scratch_prefix
    assert fam.cache["state_column"] and set(fam.cache["slot_planes"]["v"]) == {"gdn"}
    assert set(fam.cache["slot_planes"]["k"]) == {"tail"}
    c = eng.cfg
    assert eng.v_pool["gdn"].shape == (c.count("L"), eng.batch_slots,
                                       *gated_delta.plane_shape(c.gdn_heads, c.gdn_key_dim, c.gdn_value_dim))
    assert eng.k_pool["kv"].shape[0] == c.count("F")
    assert fam.token_bytes == 2 * 2 * c.kv_heads_held * c.head_dim * c.count("F")


@pytest.mark.parametrize("kernels", ["xla", "pallas"])
def test_the_served_engine_matches_the_reference_on_the_comparisons_sample(kernels, engine):
    """What ``refcheck.sample_paged_decoder`` takes, blind to the block inside:
    the prefix's state snapshot restored into the slot, the suffix prefilled
    behind it, three T = 1 steps and one T = 9 block through pool and state —
    13 rows against the reference's full forward, inside the cell's own
    ``TOLERANCE``, the int4 control outside it."""
    eng, m = engine if kernels == "xla" else _engine(kernels)
    served = SimpleNamespace(engine=eng, dims={"model": m}, parser=SimpleNamespace(runtime=_Inline()))
    params, model, sample, rows, _ = refcheck.sample_paged_decoder(served, seed=3)
    assert rows.shape[0] == 13 == sample["rows"]
    want = np.asarray(ref.logits(params, model, sample))
    by_row = lambda got: np.abs(np.asarray(got, np.float32) - want).max(-1) / np.abs(want).max(-1)
    assert by_row(rows).max() < ref.TOLERANCE
    assert by_row(ref.logits(params, model, sample, control=True)).max() > 3 * ref.TOLERANCE


def test_restoring_the_snapshot_is_prefilling_the_prefix_afresh(engine):
    eng, _ = engine
    ids = eng.tokenizer.encode(render_prompt("open the settings page", {}), bos=True)
    warm = np.asarray(eng.prefill_slot(ids, 0), np.float32)
    assert eng._last_cached_tokens == len(eng.prefix_ids) == 879
    warm_state = np.asarray(eng.v_pool["gdn"][:, 0])
    eng.release_slot(0, ok=False)
    kept, eng.prefix_kv = eng.prefix_kv, None  # _split_prefix: no cached prefix applies
    try:
        cold = np.asarray(eng.prefill_slot(ids, 1), np.float32)
        assert eng._last_cached_tokens == 0
        cold_state = np.asarray(eng.v_pool["gdn"][:, 1])
    finally:
        eng.prefix_kv = kept
        eng.release_slot(1, ok=False)
    assert rel(warm, cold) < 0.03
    assert np.abs(warm_state - cold_state).max() < 0.03 * np.abs(cold_state).max()


def test_the_compacted_width_and_a_slot_used_again(engine):
    """One request alone rides the compacted chunk program (its table row, and
    with it its state index, gathered by ``rows_idx``); beside two others the
    full width. The same tokens — snapshot -> restore -> decode is decode
    without an admission between: a request admitted into a slot another left
    gets the snapshot, not the leftover state."""
    eng, _ = engine
    alone, chunks = _generate(eng, TEXTS[:1])
    assert {c.rows for c in chunks} == {eng.compact_rows} == {1}
    assert all(c.counts["hybrid"].shape == (3,) and c.counts["attn"].shape == (3,) for c in chunks)
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
    bat.step()  # some tokens in: the slot's state has moved on
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


def test_the_batcher_publishes_the_state_counters(engine):
    from tpu_voice_agent.serve.paged import record_pool_gauges
    from tpu_voice_agent.utils import get_metrics

    eng, _ = engine
    before = dict(get_metrics().counter_state()[0])
    _generate(eng, TEXTS)
    after = get_metrics().counter_state()[0]
    d = {k: after.get(k, 0.0) - before.get(k, 0.0) for k in after}
    nl = eng.cfg.count("L")
    assert d["gdn.positions"] == d["scheduler.forward_rows"] * 9 * nl
    assert d["gdn.positions_advanced"] == d["scheduler.tokens_generated"] * nl  # a token, a position
    assert 0 < d["gdn.state_rows_moved"] <= d["scheduler.forward_rows"] * nl
    assert d["ssm.state_restores"] == 3  # the counter's name is older than this family
    record_pool_gauges(eng.allocator, eng)
    g = get_metrics().snapshot()["gauges"]
    c = eng.cfg
    assert g["paged.kv_bytes_per_token"] == 2 * 2 * c.kv_heads_held * c.head_dim * c.count("F")
    assert g["paged.state_bytes_per_slot"] == nl * (c.gdn_heads * c.gdn_key_dim * c.gdn_value_dim * 4
                                                    + (c.d_conv - 1) * c.conv_dim * 2)


@pytest.mark.parametrize("what", ["radix", "kv_quant", "mesh", "handoff", "chunked_prefill", "dense_cache"])
def test_every_refusal_raises_its_reason(what, engine):
    from tpu_voice_agent.serve import DecodeEngine

    eng, _ = engine
    fam = eng.family
    with pytest.raises(oh.StateNotCarried, match=what):
        fam.refuse(what)
    assert "OlmoHybridConfig" in fam.refuses[what] or "delta-rule" in fam.refuses[what]
    if what == "handoff":
        with pytest.raises(oh.StateNotCarried):
            eng.gather_chain_kv([1])
    elif what == "chunked_prefill":
        ids = eng.tokenizer.encode(render_prompt("go back", {}), bos=True)
        assert eng.begin_chunked_prefill(ids, 0, 16) is None
    elif what == "dense_cache":
        with pytest.raises(oh.StateNotCarried):
            DecodeEngine(cfg=eng.cfg, tokenizer=eng.tokenizer, max_len=256, init_weights=False)
    else:
        kw = {"radix": {"radix_enable": True}, "kv_quant": {"kv_quant": "int8"},
              "mesh": {"mesh": jax.sharding.Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "tp"))}}[what]
        with pytest.raises(oh.StateNotCarried):
            _engine(**kw)
