"""Ouro-2.6B (``ouro``; the benchmark's ``ouro-2.6b-int8``) at test widths on the
CPU, seeded weights: layers that run ``total_ut_steps`` times a token with K/V of
their own for every (pass, layer), under a sandwich norm and an exit gate — the
served path (prefill, T = 1 and T = 9 through the U x L-plane pool, both attention
paths; the engine behind the batcher) against ONE full forward of the equations
(``benchmark/reference/ouro_decoder.py``), on logits; every fault
``benchmark/tools/ouro_check.py`` plants; the exit gate's selection under a
threshold that binds; what the family's record refuses."""

import dataclasses
import json
from concurrent.futures import Future
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import ouro_stack, parse_stack
from benchmark.lib import refcheck
from benchmark.reference import ouro_decoder as ref
from benchmark.tools import ouro_check
from tpu_voice_agent.models import llama
from tpu_voice_agent.models.family import family
from tpu_voice_agent.models.llama import forward_paged, init_params, quantize_params
from tpu_voice_agent.services.prompts import render_prompt

F32 = jnp.float32
ROOT = Path(__file__).resolve().parents[1]
CONF = json.loads((ROOT / "benchmark/configs/ouro-2.6b-int8.json").read_text())
REHEARSAL = parse_stack.as_run(CONF, True)  # two passes over two layers, four heads of 32
BS, N = 16, 12
TABLE = jnp.asarray([[1, 2, 3, 4]], jnp.int32)


def model_of(passes: int) -> dict:
    return {**REHEARSAL[0], "total_ut_steps": passes}


def cfg_of(passes: int, **over):
    return dataclasses.replace(ouro_stack.llama_config(model_of(passes), REHEARSAL[1]),
                               max_seq_len=256, **over)


TOKS = jax.random.randint(jax.random.key(1), (1, 50), 0, cfg_of(2).vocab_size)
SAMPLE = {"tokens": [int(t) for t in TOKS[0]], "rows": 50}


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float((np.abs(got - want).max(-1) / np.abs(want).max(-1)).max())


def pools(cfg, dtype, n=N):
    shape = (cfg.ut_steps * cfg.n_layers, n, BS, cfg.n_kv_heads, cfg.head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def seeded(cfg, gate_bias: float = 0.0):
    """Float32 weights with every gain of the four norms a layer and of the
    final norm drawn around 1 (ones would hide a norm that reads another's
    gain), the embedding at the recipe's scale, a gate bias if asked."""
    p = init_params(cfg, jax.random.key(0), F32)
    ks = iter(jax.random.split(jax.random.key(7), 8))
    gains = lambda a: 1.0 + 0.3 * jax.random.normal(next(ks), a.shape, F32)
    layers = {k: gains(v) if k.endswith("_norm") else v for k, v in p["layers"].items()}
    return {**p, "embed": p["embed"] * (3.0 * cfg.dim ** 0.5), "layers": layers,
            "final_norm": gains(p["final_norm"]),
            "exit_gate": {"w": p["exit_gate"]["w"], "b": jnp.asarray(gate_bias, F32)}}


def through_the_pool(params, cfg, impl, steps=(37, 1, 1, 1, 9, 1), dtype=F32, **kw):
    """50 tokens as the engine feeds them: a prefill of 37, three T = 1 steps,
    one 1 + 8 block, one more step — K/V through the paged pool.
    -> ((50, V) logits, the k pool, the last forward's extras)."""
    kp, vp = pools(cfg, dtype)
    rows, pos = [], 0
    with jax.default_matmul_precision("highest"):
        for T in steps:
            out = forward_paged(params, cfg, TOKS[:, pos:pos + T], (pos + jnp.arange(T))[None], kp, vp,
                                TABLE, attn_impl=impl, fresh_block=pos == 0, **kw)
            rows.append(np.asarray(out[0][0]))
            kp, vp, pos = out[1], out[2], pos + T
    return np.concatenate(rows), kp, out[5:]


def test_the_configuration_keeps_every_published_width_and_reduces_nothing():
    """The file's top level is the catalog's ``config``, key for key; the
    program's configuration reads every size from it; the record is the plain
    family's with the planes of every pass."""
    assert [CONF[k] for k in ("hidden_size", "intermediate_size", "num_hidden_layers",
                              "num_attention_heads", "num_key_value_heads", "head_dim", "vocab_size",
                              "total_ut_steps", "early_exit_threshold", "rope_theta", "rms_norm_eps",
                              "max_position_embeddings", "max_window_layers")] == \
        [2048, 5632, 48, 16, 16, 128, 49152, 4, 1, 1000000, 1e-06, 65536, 48]
    assert CONF["layer_types"] == ["full_attention"] * 48 and CONF["use_sliding_window"] is False
    mistral = json.loads((ROOT / "benchmark/configs/mistral-7b-v0.1-int8.json").read_text())
    own = ("weights_seed", "batch_slots", "pool_blocks")
    assert {k: v for k, v in CONF["serving"].items() if k not in own} == \
        {k: v for k, v in mistral["serving"].items() if k not in own}
    full = ouro_stack.llama_config(*parse_stack.as_run(CONF, False))
    assert (full.dim, full.ffn_dim, full.n_layers, full.n_heads, full.n_kv_heads, full.head_dim) == \
        (2048, 5632, 48, 16, 16, 128)
    assert (full.ut_steps, full.sandwich_norm, full.exit_threshold, full.rope_theta) == (4, True, 1.0, 1e6)
    fam = family(full)
    assert fam.name == "plain" and fam.module is llama and fam.scratch_prefix and fam.one_head
    assert [c.name for c in fam.counts] == ["attn", "loop", "kv"]
    assert fam.count("loop").metrics == ("loop.passes", "loop.exit_rows", "loop.exit_last")
    # 192 planes, 1.5 MiB a token, 201.3 MB a block; the weights' layers are counted ONCE
    assert fam.cache["planes"]["k"]["kv"] == (192, 16, 128) and fam.token_bytes == 1_572_864
    assert fam.token_bytes * 128 == 201_326_592
    assert llama.param_count(full) == 48 * (16_777_216 + 34_603_008 + 4 * 2048) + 2 * 49152 * 2048 + 2048 + 2049
    s = CONF["serving"]  # under the full reservation of 55 = 6 + 8 x 6 + 1 (the file's ``assumed`` has why)
    assert s["pool_blocks"] == 879 // 128 + s["batch_slots"] * 5 + 1 == 47


def test_one_layers_sandwich_norm_is_the_references():
    """One layer, one pass's worth: x + N(Attn(N(x))) then x + N(MLP(N(x))), every
    gain its own — the program's two halves against the reference's ``layer``."""
    cfg = cfg_of(2)
    p = jax.tree.map(lambda a: a[1], seeded(cfg)["layers"])
    x = jax.random.normal(jax.random.key(3), (1, 9, cfg.dim), F32) * 3.0
    pos = jnp.arange(9, dtype=jnp.int32)
    with jax.default_matmul_precision("highest"):
        cos, sin = llama.rope_tables(pos[None], cfg.head_dim, cfg.rope_theta)
        q, k, v = llama._layer_qkv(p, x, cfg, cos, sin)
        attn = llama._attend(q, k, v, pos[None], jnp.ones((1, 9), bool))
        got = llama._layer_out(p, x, attn, cfg)
        want = ref.layer(x[0], pos, p, nq=cfg.n_heads, nkv=cfg.n_kv_heads, hd=cfg.head_dim,
                         eps=cfg.norm_eps, theta=cfg.rope_theta)
    assert rel(got[0], want) < 1e-5
    # the norm stands on the OUTPUT: a sub-layer's scale divides out, its gain does not
    scaled = llama._layer_out({**p, "w_down": p["w_down"] * 4.0}, x, attn, cfg)
    gained = llama._layer_out({**p, "mlp_post_norm": p["mlp_post_norm"] * 4.0}, x, attn, cfg)
    assert rel(scaled[0], got[0]) < 1e-3 < 0.1 < rel(gained[0], got[0])


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("passes", [2, 4])
def test_prefill_then_decode_through_the_pool_is_the_references_one_full_forward(passes, impl):
    """A prefill, T = 1 steps and a 1 + W block, each pass writing and attending
    planes of its own, under the kernels and under the XLA masks: the logits of
    the reference's ONE forward over the 50 tokens. The pool holds U x L planes
    and pass 1's are not pass 0's; the forward counts its passes and reads the
    LAST pass at the published threshold."""
    cfg, model = cfg_of(passes), model_of(passes)
    params = seeded(cfg)
    got, kp, extras = through_the_pool(params, cfg, impl, attn_stats=True, loop_stats=True)
    want, picked = ref.forward(params, SAMPLE["tokens"], model, last=50, picked=True)
    assert rel(got, want) < 2e-5
    assert kp.shape[0] == passes * cfg.n_layers == family(cfg).cache["planes"]["k"]["kv"][0]
    written = np.asarray(kp[:, 1:5]).reshape(passes, cfg.n_layers, 4 * BS, -1)[:, :, :50]
    assert all(np.abs(written[u] - written[0]).max() > 0.1 for u in range(1, passes))
    assert np.asarray(extras[-1]).tolist() == [passes, 1, 1]  # LOOP_STATS of the last T = 1 forward
    assert np.asarray(picked).tolist() == [passes - 1] * 50


def test_a_threshold_that_binds_selects_the_earlier_passes_state_as_the_reference_does():
    """Under a threshold of 0.5 with a gate bias that splits the positions, some
    rows read pass 0's state, some a later one's: the program's float32 gate and
    selection, on the positions the head reads, are the reference's — through
    whole blocks of logits and through ``logit_pos``."""
    cfg, model = cfg_of(4, exit_threshold=0.5), {**model_of(4), "early_exit_threshold": 0.5}
    params = seeded(cfg, gate_bias=-0.2)
    want, picked = ref.forward(params, SAMPLE["tokens"], model, last=50, picked=True)
    picked = np.asarray(picked)
    assert len(set(picked.tolist())) >= 3 and 5 < int((picked == 3).sum()) < 45
    got, _, _ = through_the_pool(params, cfg, "xla", steps=(37, 13))
    assert rel(got, want) < 2e-5
    kp, vp = pools(cfg, F32)
    with jax.default_matmul_precision("highest"):
        out = forward_paged(params, cfg, TOKS, jnp.arange(50)[None], kp, vp, TABLE, attn_impl="xla",
                            fresh_block=True, loop_stats=True, logit_pos=jnp.asarray([41]))
    assert out[0].shape[1] == 1 and rel(out[0][0], want[41:42]) < 2e-5
    assert np.asarray(out[-1]).tolist() == [4, 1, int(picked[41] == 3)]
    # every position read: the count of those whose selected pass is the last
    with jax.default_matmul_precision("highest"):
        out = forward_paged(params, cfg, TOKS, jnp.arange(50)[None], *pools(cfg, F32), TABLE,
                            attn_impl="xla", fresh_block=True, loop_stats=True,
                            n_real=jnp.asarray([40]))
    assert np.asarray(out[-1]).tolist() == [4, 40, int((picked[:40] == 3).sum())]


@pytest.mark.parametrize("fault", ouro_check.FAULTS)
def test_every_planted_fault_moves_the_logits_past_the_limit(fault):
    """What ``benchmark/tools/ouro_check.py`` plants on the chip — K/V shared
    across passes among them — leaves the reference's forward by more than the
    cell's limit, where the sound program reads 2e-5."""
    cfg = cfg_of(4)
    params = seeded(cfg)
    want = ref.forward(params, SAMPLE["tokens"], model_of(4), last=50)
    with ouro_check.faulty_program(fault, cfg) as faulty:
        got, _, _ = through_the_pool(params, faulty, "xla", steps=(37, 1, 9, 3))
    assert rel(got, want) > 2 * ref.TOLERANCE
    with pytest.raises(ValueError, match="no fault"):
        with ouro_check.faulty_program("nope", cfg):
            pass


def test_the_served_precision_reads_inside_the_limit_and_int4_outside():
    """The int8 tree the engine serves, bf16 activations and pools, against the
    float32 reference on the SAME int8 weights, and the int4 control: the
    protocol of ``lib/refcheck.py`` at test widths."""
    cfg = cfg_of(4)
    served = quantize_params(init_params(cfg, jax.random.key(0), jnp.bfloat16))
    assert served["exit_gate"]["w"].dtype == F32 and "q" in served["layers"]["wq"]
    kp, vp = pools(cfg, jnp.bfloat16)
    rows, pos = [], 0
    for T in (37, 1, 1, 1, 9, 1):
        out = forward_paged(served, cfg, TOKS[:, pos:pos + T], (pos + jnp.arange(T))[None], kp, vp, TABLE,
                            attn_impl="pallas", fresh_block=pos == 0)
        rows.append(np.asarray(out[0][0], np.float32))
        kp, vp, pos = out[1], out[2], pos + T
    want = np.asarray(ref.logits(served, model_of(4), SAMPLE))
    assert rel(np.concatenate(rows), want) < ref.TOLERANCE < rel(
        ref.logits(served, model_of(4), SAMPLE, control=True), want)


def test_a_configuration_the_loop_does_not_cover_is_refused_where_it_is_made():
    for bad in (dict(n_experts=4), dict(parallel_block=True), dict(tie_embeddings=True),
                dict(layer_types=("full", "sliding"), sliding_window=8), dict(qk_norm=True)):
        with pytest.raises(NotImplementedError, match="looped layers"):
            dataclasses.replace(cfg_of(2), **bad)
    with pytest.raises(ValueError, match="ut_steps"):
        dataclasses.replace(cfg_of(2), ut_steps=0)
    cfg = cfg_of(2)
    with pytest.raises(ValueError, match="loop_stats"):  # a count the record does not name
        forward_paged(None, dataclasses.replace(cfg, ut_steps=1), TOKS[:, :1], jnp.zeros((1, 1), jnp.int32),
                      *pools(cfg, F32), TABLE, loop_stats=True)


# ---- the engine behind the batcher


class _Inline:
    def submit_call(self, fn):
        out = Future()
        out.set_result(fn())
        return out


def _engine(kernels="xla", batch_slots=4, passes=2, float32=False, **kw):
    """The configuration file's rehearsal widths through the builder's own functions."""
    from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
    from tpu_voice_agent.serve import PagedDecodeEngine
    from tpu_voice_agent.services.brain import install_prompt_prefix

    m, s = model_of(passes), REHEARSAL[1]
    eng = PagedDecodeEngine(cfg=ouro_stack.llama_config(m, s), tokenizer=default_tokenizer(),
                            quant="int8", batch_slots=batch_slots, block_size=128, pool_blocks=48,
                            max_len=1536, kernels=kernels, prefill_buckets=(128, 256, 1024),
                            fast_forward=8, init_weights=False, **kw)
    eng.load_params(ouro_stack.make_params(eng.cfg, 23))
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


def test_the_engines_pool_holds_a_plane_for_every_pass_and_layer(engine):
    eng, _ = engine
    c = eng.cfg
    assert eng.family is family(c) and eng.family.name == "plain"
    assert eng.k_pool.shape == (c.ut_steps * c.n_layers, 48, 128, c.n_kv_heads, c.head_dim) == eng.v_pool.shape
    assert eng.kv_bytes_per_block == 128 * 2 * 2 * c.ut_steps * c.n_layers * c.n_kv_heads * c.head_dim
    assert eng.admit_rows == 0 and eng.compact_rows == 1 and len(eng.prefix_ids) == 879
    # the prefix went through a scratch pool of the same planes: pass 1's blocks are not pass 0's
    blocks = np.asarray(eng._prefix_blocks[0])
    k = np.asarray(eng.k_pool[:, blocks], np.float32)
    assert np.abs(k[c.n_layers:] - k[:c.n_layers]).max() > 0.1 and eng._prefix_tail["k"].shape[0] == k.shape[0]


@pytest.mark.parametrize("kernels", ["xla", "pallas"])
def test_the_served_engine_matches_the_reference_on_the_comparisons_sample(kernels, engine):
    """What ``refcheck.sample_paged_decoder`` takes, blind to the block inside:
    the suffix prefilled behind the cached prefix, three T = 1 steps and one
    T = 9 block through the pool's planes of every pass — 13 rows against the
    reference's full forward, inside the cell's own ``TOLERANCE``, the int4
    control outside it."""
    eng, m = engine if kernels == "xla" else _engine(kernels)
    served = SimpleNamespace(engine=eng, dims={"model": m}, parser=SimpleNamespace(runtime=_Inline()))
    params, model, sample, rows, _ = refcheck.sample_paged_decoder(served, seed=3)
    assert rows.shape[0] == 13 == sample["rows"]
    want = np.asarray(ref.logits(params, model, sample))
    assert rel(rows, want) < ref.TOLERANCE < rel(ref.logits(params, model, sample, control=True), want)


def test_the_compacted_width_a_slot_used_again_and_a_preempted_request(engine):
    """One request alone rides the compacted chunk program, beside two others the
    full width: the same tokens; a slot another request left gives them again;
    a request thrown out of its slot mid-stream and submitted again replays them."""
    from tpu_voice_agent.serve import ContinuousBatcher

    eng, _ = engine
    alone, chunks = _generate(eng, TEXTS[:1])
    assert {c.rows for c in chunks} == {eng.compact_rows}
    assert all(c.counts["loop"].shape == (3,) and c.counts["attn"].shape == (3,) for c in chunks)
    together, chunks = _generate(eng, TEXTS)
    assert eng.batch_slots in {c.rows for c in chunks}
    assert together[0] == alone[0] and len(alone[0]) >= 8
    assert _generate(eng, TEXTS[:1])[0] == alone  # the slot was used in between
    bat = ContinuousBatcher(eng, chunk_steps=4, max_new_tokens=40)
    bat.submit(render_prompt(TEXTS[0], {}))
    bat.step()
    bat.step()  # some tokens in: K/V written in every pass's planes
    bat.reset()  # every slot released, nothing kept
    assert _generate(eng, TEXTS[:1])[0] == alone


def test_a_grouped_admission_is_the_admissions_one_by_one():
    """16 slots: two requests waiting when a step starts share ONE suffix forward
    (``admit_rows`` = 2, the head on each row's last position, the gate read
    there); their streams are the ones they get alone. Its 1 + 8 blocks are wider
    than the packed rows: the passes carry the packed pair."""
    eng, _ = _engine(batch_slots=16)
    assert eng.admit_rows == 2
    one_by_one = [_generate(eng, [t])[0][0] for t in TEXTS[:2]]
    grouped, chunks = _generate(eng, TEXTS[:2])
    assert grouped == one_by_one
    eng.ffn_pack_rows = 24  # (16 x 9 positions pack into 24 rows: the packed regions inside the passes)
    packed, chunks = _generate(eng, TEXTS)
    assert packed[:2] == one_by_one and any("ffn" in c.counts for c in chunks)


def test_the_batcher_publishes_the_loops_counters_and_the_planes(engine):
    from tpu_voice_agent.serve.paged import record_pool_gauges
    from tpu_voice_agent.utils import get_metrics
    from tpu_voice_agent.utils.hbmledger import engine_hbm_plan, hbm_report, measure_hbm

    eng, _ = engine
    before = dict(get_metrics().counter_state()[0])
    _generate(eng, TEXTS)
    after = get_metrics().counter_state()[0]
    d = {k: after.get(k, 0.0) - before.get(k, 0.0) for k in after}
    assert d["loop.passes"] == eng.cfg.ut_steps * d["scheduler.forwards"]
    assert 0 < d["loop.exit_rows"] == d["loop.exit_last"] <= d["scheduler.forward_rows"]
    record_pool_gauges(eng.allocator, eng)
    g = get_metrics().snapshot()["gauges"]
    c = eng.cfg
    assert g["paged.kv_planes"] == c.ut_steps * c.n_layers
    assert g["paged.kv_bytes_per_token"] == eng.family.token_bytes == 4 * g["paged.kv_planes"] * c.n_kv_heads * c.head_dim
    # the byte plan: planes from the record, layers from the configuration — as close to the
    # measured tree as a dense engine's plan is to its own
    plan, meas = engine_hbm_plan(eng), measure_hbm(eng)
    assert plan["kv_pool_bytes"] == meas["kv_pool_bytes"] == 48 * eng.kv_bytes_per_block
    assert abs(plan["weights_bytes"] - meas["weights_bytes"]) < 0.01 * meas["weights_bytes"]
    assert abs(hbm_report(eng)["drift"]) < 0.01


@pytest.mark.parametrize("what", ["radix", "kv_quant", "mesh", "handoff", "chunked_prefill", "dense_cache"])
def test_every_refusal_raises_its_reason(what, engine):
    from tpu_voice_agent.serve import DecodeEngine

    eng, _ = engine
    fam = eng.family
    with pytest.raises(NotImplementedError, match=what):
        fam.refuse(what)
    assert "pass" in fam.refuses[what]
    if what == "handoff":
        with pytest.raises(NotImplementedError):
            eng.gather_chain_kv([1])
    elif what == "chunked_prefill":
        ids = eng.tokenizer.encode(render_prompt("go back", {}), bos=True)
        assert eng.begin_chunked_prefill(ids, 0, 16) is None
    elif what == "dense_cache":
        with pytest.raises(NotImplementedError):
            DecodeEngine(cfg=eng.cfg, tokenizer=eng.tokenizer, max_len=256, init_weights=False)
        with pytest.raises(NotImplementedError, match="dense_cache"):
            llama.forward(None, eng.cfg, TOKS[:, :4], jnp.arange(4)[None], None)
    else:
        kw = {"radix": {"radix_enable": True}, "kv_quant": {"kv_quant": "int8"},
              "mesh": {"mesh": jax.sharding.Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "tp"))}}[what]
        with pytest.raises(NotImplementedError):
            _engine(**kw)
