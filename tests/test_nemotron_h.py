"""The Nemotron-H hybrid decoder (``models/nemotron_h.py``; the benchmark's
``nemotron-3-super-120b-a12b-int8``) against its plain reference
(``benchmark/reference/nemotron_h_decoder.py``) at test widths on the CPU:
each kind of block alone, prefill then decode through pool and state planes,
the chip's share of the latent experts, the router's rule, the two-plane
relu2 expert through the ONE grouped dispatch, and what serving it asks of
the paged engine (snapshot and restore, the compacted width, a grouped
admission, the counters, the refusals)."""

import dataclasses
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import nemotron_h_stack, parse_stack
from benchmark.lib import refcheck
from benchmark.reference import decoder as dense_ref
from benchmark.reference import nemotron_h_decoder as ref
from tpu_voice_agent.models import llama, moe, sambay
from tpu_voice_agent.models import nemotron_h as nh
from tpu_voice_agent.models.family import family
from tpu_voice_agent.models.llama import forward_paged, init_params, quantize_params
from tpu_voice_agent.services.prompts import render_prompt

F32 = jnp.float32
# "MEME*EM": a run of pairs (the loop), then one block of each kind; 2 groups of 2 Mamba-2
# heads, a latent (32) under the hidden size (64), 8 of 16 experts held from id 4, 3 a token
CFG = dataclasses.replace(nh.PRESETS["nemotron-h-test"], moe_impl="grouped")
BS, N, SLOTS = 16, 12, 3
ROOT = Path(__file__).resolve().parents[1]


def model_keys(cfg) -> dict:
    return {"num_hidden_layers": cfg.n_layers, "hybrid_override_pattern": cfg.pattern + "MEME",
            "mamba_num_heads": cfg.mamba_heads, "n_groups": cfg.n_groups, "ssm_state_size": cfg.d_state,
            "num_attention_heads": cfg.n_heads, "num_key_value_heads": cfg.n_kv_heads,
            "norm_eps": cfg.norm_eps, "layer_norm_epsilon": cfg.group_norm_eps,
            "num_experts_per_tok": cfg.top_k, "routed_scaling_factor": cfg.router_scale,
            "norm_topk_prob": cfg.norm_topk, "first_expert": cfg.first_expert}


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float((np.abs(got - want).max(-1) / np.abs(want).max(-1)).max())


def pools(cfg, dtype=jnp.bfloat16, slots=SLOTS):
    from tpu_voice_agent.serve.paged import build_pools

    return build_pools(nh.cache_spec(cfg), N, BS, slots,
                       zeros=lambda shape, dt: jnp.zeros(shape, dt if dt == jnp.float32 else dtype))


TABLE = jnp.asarray([[1, 2, 3, 4, 1]], jnp.int32)  # four blocks, then the slot's state index
TOKS = jax.random.randint(jax.random.key(1), (1, 50), 0, CFG.vocab_size)


def through_the_pool(params, cfg, impl, dtype, **kw):
    """50 tokens as the engine feeds them: a prefill of 37, three T = 1 steps,
    one T = 9 block, one more step. -> (50, V) logits."""
    kp, vp = pools(cfg, dtype)
    rows, pos = [], 0
    for T in (37, 1, 1, 1, 9, 1):
        out = forward_paged(params, cfg, TOKS[:, pos:pos + T], (pos + jnp.arange(T))[None], kp, vp,
                            TABLE, attn_impl=impl, **kw)
        rows.append(np.asarray(out[0][0]))
        kp, vp, pos = out[1], out[2], pos + T
    return np.concatenate(rows)


def test_the_pattern_is_walked_in_runs_of_pairs_and_single_blocks():
    pub = json.loads((ROOT / "benchmark/configs/nemotron-3-super-120b-a12b-int8.json").read_text())
    pattern = pub["hybrid_override_pattern"]
    assert len(pattern) == 88 and [pattern.count(k) for k in "ME*"] == [40, 40, 8]
    served = pattern[:pub["num_hidden_layers"]]
    assert [served.count(k) for k in "ME*"] == [10, 10, 2]
    seg = nh.segments(served)
    assert seg == (("pairs", 0, 0, 3), ("M", 3, -1, 1), ("*", 0, -1, 1), ("E", 3, -1, 1),
                   ("pairs", 4, 4, 3), ("M", 7, -1, 1), ("*", 1, -1, 1), ("E", 7, -1, 1), ("pairs", 8, 8, 2))
    # every layer once, in order, whatever the pattern
    for p in (served, pattern, "EMEMEM*E", "M", "*M"):
        walked = "".join("ME" * n if k == "pairs" else k for k, _, _, n in nh.segments(p))
        assert walked == p


@pytest.mark.parametrize("kind", ["M", "*", "E"])
def test_each_kind_of_block_alone_matches_the_plain_reference(kind):
    """One block of each kind, float32 weights and activations, 40 positions,
    the served functions against the reference's: 1e-4 of the output's range."""
    cfg, T = CFG, 40
    params = nh.init_params(cfg, jax.random.key(5), F32)
    u = jax.random.normal(jax.random.key(6), (1, T, cfg.dim), F32)
    p = jax.tree.map(lambda a: a[1], params[nh.KINDS[kind]])
    dense = dense_ref.dense
    with jax.default_matmul_precision("highest"):
        if kind == "M":
            _, vp = pools(cfg, F32)
            tail = jnp.zeros((1, cfg.d_conv - 1, cfg.conv_dim), F32)
            got, _, _ = nh.mamba_mix(p, u, tail, vp["ssm"], jnp.asarray([1]), jnp.int32(0),
                                     jnp.asarray([T]), cfg, "xla")
            want = ref.mamba2(u[0], p, dense, H=cfg.mamba_heads, G=cfg.n_groups, N=cfg.d_state,
                              eps=cfg.group_norm_eps)
        elif kind == "E":
            got, _ = nh.expert_layer(p, u, cfg)
            want = ref.latent_experts(u[0], p, dense, top_k=cfg.top_k, scale=cfg.router_scale,
                                      renorm=True, first=cfg.first_expert)
        else:
            q, k, v = (u @ p[n] for n in ("wq", "wk", "wv"))
            hd = cfg.head_dim
            a = sambay._attend(q.reshape(1, T, -1, hd), k.reshape(1, T, -1, hd), v.reshape(1, T, -1, hd),
                               jnp.arange(T)[None], 1 << 30, hd ** -0.5)
            got = a.reshape(1, T, -1) @ p["wo"]
            want = ref.attention(u[0], p, dense, nq=cfg.n_heads, nkv=cfg.n_kv_heads)
    assert rel(got[0], want) < 1e-4


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prefill_then_decode_through_pool_and_state_is_the_full_forward(impl):
    """The whole stack in float32: prefill, T = 1 steps and a T = 9 block
    through the K/V pool and the state planes against the reference's ONE full
    forward from an empty state, on both attention / scan / dispatch paths (the
    Pallas kernels interpreted). 1e-4: float32 in another order."""
    params = init_params(CFG, jax.random.key(0), F32)
    want = ref.logits(params, model_keys(CFG), {"tokens": [int(t) for t in TOKS[0]], "rows": 50})
    assert rel(through_the_pool(params, CFG, impl, F32), want) < 1e-4


@pytest.mark.parametrize("fault", nh.FAULTS)
def test_every_planted_fault_moves_the_logits(fault):
    """What ``benchmark/tools/ssd_check.py`` plants on the chip moves the
    float32 logits far past the 1.4e-6 the sound forward reads here (the state
    rounded to bf16 where it is read: 4.8e-4, on the decoded rows alone; each
    of the others 1.1-1.5 of the range)."""
    params = init_params(CFG, jax.random.key(0), F32)
    want = ref.logits(params, model_keys(CFG), {"tokens": [int(t) for t in TOKS[0]], "rows": 50})
    kp, vp = pools(CFG, F32)
    rows, pos = [], 0
    for T in (37, 1, 9, 3):
        out = nh.forward_paged(params, CFG, TOKS[:, pos:pos + T], (pos + jnp.arange(T))[None], kp, vp,
                               TABLE, attn_impl="xla", fault=fault)
        rows.append(np.asarray(out[0][0]))
        kp, vp, pos = out[1], out[2], pos + T
    assert rel(np.concatenate(rows), want) > 2e-4


def test_the_served_precision_reads_inside_the_limit_and_int4_outside():
    """int8 weights, bf16 activations, K/V and convolution tail, float32 state
    against the float32 reference on the same weights. At these widths (3 of
    16 experts a token on 64-wide bf16 rows, gates x 5) a rounding flips a
    pick in a few of the 50 rows and such a row reads 10-60 %: the MEDIAN row
    reads 1.2 % where int4 weights, the precision below, read 70 % (their
    best row 26 %). The chip's limit at published widths is the reference
    module's own."""
    params = quantize_params(init_params(CFG, jax.random.key(0)))
    sample = {"tokens": [int(t) for t in TOKS[0]], "rows": 50}
    want = np.asarray(ref.logits(params, model_keys(CFG), sample))
    rows = lambda got: np.abs(np.asarray(got) - want).max(-1) / np.abs(want).max(-1)
    served = rows(through_the_pool(params, CFG, "xla", jnp.bfloat16))
    assert 1e-3 < np.median(served) < 0.03 and (served < 0.08).sum() >= 40
    control = rows(ref.logits(params, model_keys(CFG), sample, control=True))
    assert control.min() > 0.15 and np.median(control) > 0.3


def test_the_shares_add_up():
    """The guide's test of the cut: the routed parts of the FOUR shares of an E
    layer (experts 0-3, 4-7, 8-11, 12-15: each summed in the latent and each
    through ``fc2``) plus what every chip computes alike — the shared expert,
    counted once — equal the layer with all 16 experts held."""
    whole = dataclasses.replace(CFG, experts_held=0, first_expert=0)
    p = jax.tree.map(lambda a: a[0], nh.init_params(whole, jax.random.key(3), F32)["experts"])
    h = jax.random.normal(jax.random.key(4), (1, 24, CFG.dim), F32)
    with jax.default_matmul_precision("highest"):
        want, _ = nh.expert_layer(p, h, whole)
        shared = nh.relu2(h @ p["shared_up"]) @ p["shared_down"]
        total, local = shared, 0
        for first in (0, 4, 8, 12):
            cfg = dataclasses.replace(CFG, experts_held=4, first_expert=first)
            part = {**p, "moe_up": p["moe_up"][first:first + 4], "moe_down": p["moe_down"][first:first + 4]}
            y, st = nh.expert_layer(part, h, cfg)
            total = total + (y - shared)
            assert int(st[0]) == 24 * CFG.top_k  # every share counts what the router assigned
            local += int(st[4])
        # and the plain reference's share is the program's
        ref_part = ref.latent_experts(h[0], part, dense_ref.dense, top_k=CFG.top_k, scale=CFG.router_scale,
                                      renorm=True, first=12)
    assert local == 24 * CFG.top_k  # each assignment fell on exactly one share
    assert rel(total[0], want[0]) < 1e-5
    assert rel(y[0], ref_part) < 1e-5


def test_the_routers_rule():
    """The bias moves the chosen set and not the gates; the gates are the
    chosen scores over their sum, TIMES the scale; top_k of them."""
    E, K, d = 16, 3, 8
    w = jax.random.normal(jax.random.key(0), (d, E), F32)
    x = jax.random.normal(jax.random.key(1), (5, d), F32)
    s = np.asarray(jax.nn.sigmoid(x @ w))
    bias = jnp.zeros((E,), F32).at[7].set(10.0)  # expert 7 is chosen by every token
    eids, gates = moe.route_topk_flat(w, x, E, K, True, "sigmoid", bias=bias, scale=5.0)
    eids, gates = np.asarray(eids), np.asarray(gates)
    assert eids.shape == (5, K) and (eids == 7).any(axis=1).all()
    plain, _ = moe.route_topk_flat(w, x, E, K, True, "sigmoid", bias=jnp.zeros((E,)), scale=5.0)
    assert not np.array_equal(np.sort(np.asarray(plain)), np.sort(eids))  # the set moved
    picked = np.take_along_axis(s, eids, axis=1)
    assert np.allclose(gates, 5.0 * picked / picked.sum(1, keepdims=True), rtol=1e-5)  # no bias inside
    assert np.allclose(gates.sum(1), 5.0, rtol=1e-5)


@pytest.mark.parametrize("form", ["swiglu", "relu2", "silu"])
@pytest.mark.parametrize("latent", [False, True])
def test_one_grouped_dispatch_serves_every_expert_form(form, latent):
    """``llama._moe_ffn_grouped`` against the dense dispatch, its exact twin:
    SwiGLU's three planes and the two-plane forms, at the model's width and at
    a latent's — one function, told the form by the configuration and the
    dispatched rows by its caller."""
    d, w, f, E, K, H = 32, (16 if latent else 32), 24, 8, 3, 4
    ks = jax.random.split(jax.random.key(2), 6)
    mat = lambda k, *shape: jax.random.normal(k, shape, F32) * shape[-2] ** -0.5
    p = {"router": mat(ks[0], d, E), "moe_up": mat(ks[1], H, w, f), "moe_down": mat(ks[2], H, f, w),
         "moe_gate": mat(ks[3], H, w, f)}
    h = jax.random.normal(ks[4], (2, 7, d), F32)
    lat = jax.random.normal(ks[5], (2, 7, w), F32) if latent else None
    cfg = SimpleNamespace(n_experts=E, top_k=K, n_held=H, first_expert=2, experts_held=H, norm_topk=True,
                          router_fn="sigmoid", router_bias=False, router_scale=1.0, expert_form=form,
                          capacity_factor=E / K)
    with jax.default_matmul_precision("highest"):
        got, st = llama._moe_ffn_grouped(p, h, cfg, **({"lat": lat} if latent else {}))
        want, st_d = llama._moe_ffn_dense(p, h, cfg, **({"lat": lat} if latent else {}))
    assert got.shape == (2, 7, w) and rel(got.reshape(14, w), want.reshape(14, w)) < 1e-5
    assert int(st[4]) == int(st_d[4]) and int(st[2]) == int(st_d[2])  # local rows, experts touched


def test_the_state_advances_over_the_real_positions_and_no_others():
    """Three rows of a 1 + 8 block: row 0 has 3 real positions, row 1 is idle,
    row 2 all 9. Poisoning the tokens at every position that is NOT real
    leaves each row's state, tail, K/V outside the trash block and the real
    positions' logits BIT-equal — the E layers never saw them — and the idle
    row's state and tail are what they were."""
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
        vp["ssm"] = vp["ssm"] + 0.25  # a state to keep
        kp["conv"] = kp["conv"] + 0.5
        return forward_paged(params, CFG, tokens, pos, kp, vp, tables, attn_impl="pallas",
                             write_mask=live, n_real=n_real, hybrid_stats=True, moe_stats=True)

    a, b = run(toks), run(poisoned)
    assert np.array_equal(np.asarray(a[2]["ssm"]), np.asarray(b[2]["ssm"]))
    assert np.array_equal(np.asarray(a[1]["conv"], np.float32), np.asarray(b[1]["conv"], np.float32))
    assert np.array_equal(np.asarray(a[1]["kv"][:, 1:], np.float32), np.asarray(b[1]["kv"][:, 1:], np.float32))
    assert np.array_equal(np.asarray(a[0])[np.asarray(real)], np.asarray(b[0])[np.asarray(real)])
    assert np.all(np.asarray(a[2]["ssm"][:, 1]) == 0.25) and np.all(np.asarray(a[1]["conv"][:, 1], np.float32) == 0.5)
    assert not np.all(np.asarray(a[2]["ssm"][:, 0]) == 0.25)  # a live row's did move
    nm = CFG.count("M")
    assert np.asarray(a[5]).tolist() == [nm * 12, nm * 27, nm * 2]  # advanced, computed, states moved
    assert np.array_equal(np.asarray(a[6]), np.asarray(b[6]))  # the router's counts: the same rows


# ---------------------------------------------------------------- the engine


class _Inline:
    def submit_call(self, fn):
        fn()
        return self

    def result(self):
        return None


CONF = json.loads((ROOT / "benchmark/configs/nemotron-3-super-120b-a12b-int8.json").read_text())


def _engine(kernels="xla", batch_slots=4, **kw):
    """The configuration file's rehearsal widths through the builder's own functions."""
    from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
    from tpu_voice_agent.serve import PagedDecodeEngine
    from tpu_voice_agent.services.brain import install_prompt_prefix

    m, s = parse_stack.as_run(CONF, True)
    eng = PagedDecodeEngine(cfg=nemotron_h_stack.llama_config(m, s), tokenizer=default_tokenizer(),
                            quant="int8", batch_slots=batch_slots, block_size=128, pool_blocks=48,
                            max_len=1536, kernels=kernels, prefill_buckets=(128, 256, 1024),
                            fast_forward=8, init_weights=False, **kw)
    eng.load_params(nemotron_h_stack.make_params(eng.cfg, 23))
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
    assert fam is eng.family and fam.name == "ssd" and fam.module is nh
    assert [c.name for c in fam.counts] == ["hybrid", "moe", "attn", "kv"]
    assert fam.count("hybrid").metrics == nh.HYBRID_STATS and fam.count("moe").metrics[-1] == "moe.local_rows"
    assert fam.n_real == "always" and fam.one_head and fam.pack_rows == 96 and fam.scratch_prefix
    assert fam.cache["state_column"] and set(fam.cache["slot_planes"]["v"]) == {"ssm"}
    assert eng.v_pool["ssm"].shape[:2] == (eng.cfg.count("M"), eng.batch_slots)
    assert eng.k_pool["kv"].shape[0] == eng.cfg.count("*")
    assert fam.token_bytes == 2 * 2 * eng.cfg.n_kv_heads * eng.cfg.head_dim * eng.cfg.count("*")


@pytest.mark.parametrize("kernels", ["xla", "pallas"])
def test_the_served_engine_matches_the_reference_on_the_comparisons_sample(kernels, engine):
    """What ``refcheck.sample_paged_decoder`` takes, blind to the block inside:
    the prefix's state snapshot restored into the slot, the suffix prefilled
    behind it, three T = 1 steps and one T = 9 block through pool and state —
    13 rows against the reference's full forward. At these widths (3 of 16
    experts a token on 64-wide bf16 rows) a rounding flips a pick in a row or
    two of the 13 and such a row reads ~10 %: the median row reads ~1 %, the
    int4 control 16-34 % in every row."""
    eng, m = engine if kernels == "xla" else _engine(kernels)
    served = SimpleNamespace(engine=eng, dims={"model": m}, parser=SimpleNamespace(runtime=_Inline()))
    params, model, sample, rows, _ = refcheck.sample_paged_decoder(served, seed=3)
    assert rows.shape[0] == 13 == sample["rows"]
    want = np.asarray(ref.logits(params, model, sample))
    by_row = lambda got: np.abs(np.asarray(got, np.float32) - want).max(-1) / np.abs(want).max(-1)
    assert np.median(by_row(rows)) < 0.03 and by_row(rows).max() < 0.25
    assert by_row(ref.logits(params, model, sample, control=True)).min() > 0.12


def test_restoring_the_snapshot_is_prefilling_the_prefix_afresh(engine):
    eng, _ = engine
    ids = eng.tokenizer.encode(render_prompt("open the settings page", {}), bos=True)
    warm = np.asarray(eng.prefill_slot(ids, 0), np.float32)
    assert eng._last_cached_tokens == len(eng.prefix_ids) == 879
    warm_state = np.asarray(eng.v_pool["ssm"][:, 0])
    eng.release_slot(0, ok=False)
    kept, eng.prefix_kv = eng.prefix_kv, None  # _split_prefix: no cached prefix applies
    try:
        cold = np.asarray(eng.prefill_slot(ids, 1), np.float32)
        assert eng._last_cached_tokens == 0
        cold_state = np.asarray(eng.v_pool["ssm"][:, 1])
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
    assert all(c.counts["hybrid"].shape == (3,) and c.counts["moe"].shape == (5,) for c in chunks)
    together, chunks = _generate(eng, TEXTS)
    assert eng.batch_slots in {c.rows for c in chunks}
    assert together[0] == alone[0] and len(alone[0]) == 40
    assert _generate(eng, TEXTS[:1])[0] == alone  # the slot was used in between


def test_a_grouped_admission_is_the_admissions_one_by_one():
    """16 slots: two requests waiting when a step starts share ONE suffix
    forward (``admit_rows`` = 2) behind the restored snapshot; their streams are
    the ones they get alone."""
    eng, _ = _engine(batch_slots=16)
    assert eng.admit_rows == 2
    one_by_one = [_generate(eng, [t])[0][0] for t in TEXTS[:2]]
    grouped, _ = _generate(eng, TEXTS[:2])
    assert grouped == one_by_one


def test_the_batcher_publishes_the_state_and_share_counters(engine):
    from tpu_voice_agent.serve.paged import record_pool_gauges
    from tpu_voice_agent.utils import get_metrics

    eng, _ = engine
    before = dict(get_metrics().counter_state()[0])
    _generate(eng, TEXTS)
    after = get_metrics().counter_state()[0]
    d = {k: after.get(k, 0.0) - before.get(k, 0.0) for k in after}
    nm = eng.cfg.count("M")
    assert d["ssm.positions"] == d["scheduler.forward_rows"] * 9 * nm
    assert d["ssm.positions_advanced"] == d["scheduler.tokens_generated"] * nm  # a token, a position
    assert 0 < d["ssm.state_rows_moved"] <= d["scheduler.forward_rows"] * nm
    assert 0 < d["moe.local_rows"] < d["moe.assigned_rows"] and d["moe.padded_rows"] >= d["moe.local_rows"]
    assert d["ssm.state_restores"] == 3
    record_pool_gauges(eng.allocator, eng)
    g = get_metrics().snapshot()["gauges"]
    c = eng.cfg
    assert g["paged.kv_bytes_per_token"] == 2 * 2 * c.n_kv_heads * c.head_dim * c.count("*")
    assert g["paged.state_bytes_per_slot"] == nm * (c.mamba_heads * c.mamba_head_dim * c.d_state * 4
                                                    + (c.d_conv - 1) * c.conv_dim * 2)


@pytest.mark.parametrize("what", ["radix", "kv_quant", "mesh", "handoff", "chunked_prefill", "dense_cache"])
def test_every_refusal_raises_its_reason(what, engine):
    from tpu_voice_agent.serve import DecodeEngine

    eng, _ = engine
    fam = eng.family
    with pytest.raises(nh.StateNotCarried, match=what):
        fam.refuse(what)
    assert "NemotronHConfig" in fam.refuses[what] or "Mamba-2" in fam.refuses[what]
    if what == "handoff":
        with pytest.raises(nh.StateNotCarried):
            eng.gather_chain_kv([1])
    elif what == "chunked_prefill":
        ids = eng.tokenizer.encode(render_prompt("go back", {}), bos=True)
        assert eng.begin_chunked_prefill(ids, 0, 16) is None
    elif what == "dense_cache":
        with pytest.raises(nh.StateNotCarried):
            DecodeEngine(cfg=eng.cfg, tokenizer=eng.tokenizer, max_len=256, init_weights=False)
    else:
        kw = {"radix": {"radix_enable": True}, "kv_quant": {"kv_quant": "int8"},
              "mesh": {"mesh": jax.sharding.Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "tp"))}}[what]
        with pytest.raises(nh.StateNotCarried):
            _engine(**kw)
