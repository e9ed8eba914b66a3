"""SmallThinker-21BA3B-Instruct (``smallthinker``; the benchmark's
``smallthinker-21b-a3b-int8``) at test widths on the CPU, float32, seeded
weights: the served path — prefill, T = 1 and T = 9 through the paged pool,
packed and whole-block, an admission behind a cached head longer than the
largest bucket — against the plain forward of the equations
(``benchmark/reference/smallthinker_decoder.py``) with a window that BINDS,
a GQA group of 7, the router on the layer's INPUT and ReGLU experts; each
assumed reading flipped is another model; what the engine asks of the model.
"""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import parse_stack, smallthinker_stack
from benchmark.reference import smallthinker_decoder as ref
from tpu_voice_agent.models import llama, moe
from tpu_voice_agent.models.family import family
from tpu_voice_agent.models.llama import forward_paged, init_params, quantize_params

F32 = jnp.float32
CONF = json.loads((Path(__file__).parents[1] / "benchmark/configs/smallthinker-21b-a3b-int8.json").read_text())
REHEARSAL = parse_stack.as_run(CONF, True)  # 7 query heads on 1 K/V head, two periods F S S S, 8 experts 3 a token
MODEL = {**REHEARSAL[0], "sliding_window_size": 17}  # a window of 17 under 50 tokens: it binds
CFG = dataclasses.replace(
    smallthinker_stack.llama_config(MODEL, {**REHEARSAL[1], "site_context_tokens": 0}), max_seq_len=256)
BS, N = 16, 12
TABLE = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
TOKS = jax.random.randint(jax.random.key(1), (1, 50), 0, CFG.vocab_size)
SAMPLE = {"tokens": [int(t) for t in TOKS[0]], "rows": 50}


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float((np.abs(got - want).max(-1) / np.abs(want).max(-1)).max())


def pools(cfg, dtype, n=N, bs=BS):
    shape = (cfg.n_layers, n, bs, cfg.n_kv_heads, cfg.head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


@pytest.fixture(scope="module")
def params():
    """Float32 weights, the embedding at the recipe's scale (the router reads
    the residual itself: its logits have that scale)."""
    p = init_params(CFG, jax.random.key(0), F32)
    return {**p, "embed": p["embed"] * (3.0 * CFG.dim ** 0.5)}


def through_the_pool(params, cfg, impl, steps=(37, 1, 1, 1, 9, 1), **kw):
    """50 tokens as the engine feeds them: a prefill of 37 (past the
    17-position window), three T = 1 steps, one 1 + 8 block, one more step —
    K/V through the paged pool. -> (50, V) logits."""
    kp, vp = pools(cfg, F32)
    rows, pos = [], 0
    with jax.default_matmul_precision("highest"):
        for T in steps:
            out = forward_paged(params, cfg, TOKS[:, pos:pos + T], (pos + jnp.arange(T))[None], kp, vp,
                                TABLE, attn_impl=impl, fresh_block=pos == 0, **kw)
            rows.append(np.asarray(out[0][0]))
            kp, vp, pos = out[1], out[2], pos + T
    return np.concatenate(rows)


def reference(params, **departures):
    return np.asarray(ref.forward(params, SAMPLE["tokens"], MODEL, last=50, **departures))


def test_the_configuration_keeps_the_published_widths_and_names_its_cut():
    """The file's top level is the catalog's ``config`` but for the one key in
    ``reduced``; the program's configuration reads every size from it."""
    assert [CONF[k] for k in ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
                              "moe_ffn_hidden_size", "moe_num_primary_experts",
                              "moe_num_active_primary_experts", "sliding_window_size", "vocab_size",
                              "rope_theta", "rms_norm_eps", "max_position_embeddings")] == \
        [2560, 28, 4, 128, 768, 64, 6, 4096, 151936, 1500000, 1e-06, 16384]
    assert (CONF["num_hidden_layers"], CONF["num_hidden_layers_published"]) == (24, 52)
    assert CONF["rope_layout"] == CONF["sliding_window_layout"] == [0, 1, 1, 1] * 13
    assert CONF["layer_kinds"] == "FSSS" * 6 and (CONF["stage"], CONF["stages"]) == (0, 2)
    dots = json.loads((Path(__file__).parents[1] / "benchmark/configs/dots3-note-prev-int8.json").read_text())
    same = {k: v for k, v in CONF["serving"].items() if k != "weights_seed"}
    assert same == {k: v for k, v in dots["serving"].items() if k != "weights_seed"}
    full = smallthinker_stack.llama_config(*[{**x, "site_context_tokens": 0} if "max_len" in x else x
                                             for x in parse_stack.as_run(CONF, False)])
    assert (full.head_dim, full.n_heads // full.n_kv_heads, full.n_experts, full.top_k, full.ffn_dim) == \
        (128, 7, 64, 6, 768)
    assert full.layer_types == ("full", "sliding", "sliding", "sliding") * 6
    assert (full.router_input, full.gate_act, full.norm_topk, full.router_fn) == ("layer", "relu", True, "softmax")
    assert llama.bound_window(full) == 4096  # max_len 8832 passes it: the window binds
    fam = family(full)
    assert fam.name == "plain" and fam.scratch_prefix and fam.one_head and fam.block_real
    assert [c.name for c in fam.counts] == ["moe", "attn", "window", "kv"]
    assert {"mesh", "kv_quant", "dense_cache"} <= set(fam.refuses)
    # the arithmetic of the cut (benchmark/lib/peaks_smallthinker.py has the rest)
    assert llama.param_count(full) == 24 * (20_971_520 + 377_487_360 + 2560 * 64 + 2 * 2560) + 2 * 151936 * 2560 + 2560


@pytest.mark.parametrize("dispatch", ["grouped", "dense"])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prefill_then_decode_through_the_pool_is_the_reference_full_forward(params, impl, dispatch):
    """Through the window (17 of up to 50 positions), the full layers without
    rotation, a group of 7 query heads a K/V head, the picks made on the
    layer's input and carried across attention, ReGLU experts — under the
    block kernel (T = 1 too behind a window) and under the XLA masks, through
    the grouped kernel and through the dense dispatch."""
    cfg = dataclasses.replace(CFG, moe_impl=dispatch)
    assert rel(through_the_pool(params, cfg, impl), reference(params)) < 2e-4


@pytest.mark.parametrize("n_real", [(9, 9), (1, 3), (4, 0)], ids=["all_real", "few_real", "a_row_with_none"])
def test_the_packed_block_is_the_whole_block_on_its_real_positions(params, n_real):
    """Two rows' 1 + 8 blocks behind their own prefills: both position-wise
    regions packed into 24 rows (the picks ride the packed rows) against the
    whole block, on the positions that are real; the counters agree."""
    cfg = dataclasses.replace(CFG, moe_impl="grouped")
    kp, vp = pools(cfg, F32)
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    toks = jax.random.randint(jax.random.key(3), (2, 49), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        out = forward_paged(params, cfg, toks[:, :40], jnp.broadcast_to(jnp.arange(40), (2, 40)), kp, vp,
                            tables, attn_impl="xla", fresh_block=True)
        blk = (toks[:, 40:], 40 + jnp.broadcast_to(jnp.arange(9), (2, 9)), out[1], out[2], tables)
        kw = dict(attn_impl="pallas", n_real=jnp.asarray(n_real, jnp.int32), moe_stats=True,
                  attn_stats=True, window_stats=True, write_mask=jnp.asarray([n > 0 for n in n_real]))
        whole = forward_paged(params, cfg, blk[0], blk[1], jnp.array(blk[2]), jnp.array(blk[3]), blk[4], **kw)
        packed = forward_paged(params, cfg, *blk, ffn_pack=8, **kw)
    assert int(packed[-1][0]) == (sum(n_real) <= 8)  # FFN_STATS: the packed branches ran where they fit
    for b, n in enumerate(n_real):
        if n:
            assert rel(packed[0][b, :n], whole[0][b, :n]) < 1e-4
    np.testing.assert_array_equal(np.asarray(packed[7]), np.asarray(whole[7]))  # the window's walk
    live = sum(n > 0 for n in n_real)
    # 6 sliding layers; a live row holds 4 blocks (positions 40-48) and walks 3 (from 40 - 16 = 24);
    # the two rows' tables name no block in common: no range, nothing taken off their walks
    assert np.asarray(whole[7]).tolist() == [6 * 3 * live, 6 * 4 * live, 0]


def test_the_routers_picks_are_a_softmax_over_the_chosen_logits_by_both_readings(params):
    """``moe_primary_router_apply_softmax`` with ``norm_topk_prob``: a softmax
    over all the experts renormalised over the chosen IS the softmax over the
    chosen logits — the program's rule, the reference's and a hand computation
    of each reading agree, on the layer's INPUT."""
    x = jax.random.normal(jax.random.key(5), (2, 9, CFG.dim), F32) * 3.0
    p = jax.tree.map(lambda a: a[1], params["layers"])
    with jax.default_matmul_precision("highest"):
        eids, gates = llama._route_ahead(p, x, CFG)
        r = x.reshape(18, -1) @ p["router"]
        top, chosen = jax.lax.top_k(r, CFG.top_k)
        first = jax.nn.softmax(top, axis=-1)  # softmax(r[S])
        probs = jax.nn.softmax(r, axis=-1)
        second = jnp.take_along_axis(probs, chosen, axis=-1)
        second = second / second.sum(-1, keepdims=True)  # softmax over 64, renormalised over S
        dense = ref.gates_of(r, CFG.top_k)
    assert eids.shape == gates.shape == (2, 9, CFG.top_k)
    np.testing.assert_array_equal(np.asarray(eids).reshape(18, -1), np.asarray(chosen))
    np.testing.assert_allclose(np.asarray(gates).reshape(18, -1), np.asarray(first), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(first), np.asarray(second), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(jnp.take_along_axis(dense, chosen, axis=-1)), np.asarray(first), rtol=1e-5)
    # and the dense dispatch of picks made ahead fills the slots route_topk fills
    C = 18
    d1, c1 = moe.dispatch_topk(eids.reshape(18, -1), gates.reshape(18, -1), CFG.n_experts, C)
    d2, c2 = moe.route_topk(p["router"], x.reshape(18, -1), CFG.n_experts, CFG.top_k, C, True, "softmax")
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), atol=1e-6)


# each reading this model does NOT take, in the program (a configuration that
# differs in that one property) and in the reference (its departure): the
# served path follows the program's reading and leaves the published one
FLIPS = {
    "router_on_h2": (dict(router_input="ffn"), dict(router_on="ffn")),
    "silu_for_relu": (dict(gate_act="silu"), dict(act="silu")),
    "gates_not_renormalised": (dict(norm_topk=False), dict(renorm=False)),
    "no_window": (dict(sliding_window=1 << 20), dict(windowed=False)),
    "rotation_on_the_full_layers": (None, dict(rotate_full=True)),
}


@pytest.mark.parametrize("flip", sorted(FLIPS))
def test_each_assumed_reading_flipped_is_another_model(params, flip):
    """Moving the router's input to h2 CHANGES the output (the test that holds
    the placement), as do SiLU for ReLU, gates not renormalised, no window and
    a rotated full layer: each flipped program is its flipped reference and is
    far from the published reading."""
    over, departure = FLIPS[flip]
    want, other = reference(params), reference(params, **departure)
    assert rel(other, want) > 0.02
    if over is None:  # (a full layer that rotates is no LlamaConfig of this family: the reference alone)
        return
    got = through_the_pool(params, dataclasses.replace(CFG, moe_impl="grouped", **over), "pallas")
    assert rel(got, other) < 2e-4 and rel(got, want) > 0.02


def test_an_expert_layer_that_is_handed_no_picks_refuses():
    """Nothing routes behind attention in silence: a caller that does not
    route ahead (the dense-cache forwards, the pipeline) is refused, as is the
    configuration where another forward would have to."""
    h = jnp.zeros((1, 2, CFG.dim), F32)
    p = jax.tree.map(lambda a: a[0], init_params(CFG, jax.random.key(0), F32)["layers"])
    with pytest.raises(NotImplementedError, match="routes ahead"):
        llama._ffn(p, h, dataclasses.replace(CFG, moe_impl="dense"))
    with pytest.raises(NotImplementedError, match="dense_cache"):
        llama.forward(init_params(CFG, jax.random.key(0), F32), CFG, TOKS[:, :4], jnp.arange(4)[None],
                      llama.init_kv_cache(CFG, 1, 16))
    for bad in (dict(kv_lora_rank=8, qk_nope_dim=8, qk_rope_dim=8, v_head_dim=8, head_size=16), dict(parallel_block=True)):
        with pytest.raises(NotImplementedError, match="router on the layer's input"):
            dataclasses.replace(CFG, layer_types=(), **bad)
    with pytest.raises(ValueError, match="router_input"):
        dataclasses.replace(CFG, gate_act="gelu")


def test_the_served_precision_reads_inside_the_limit_and_int4_outside(params):
    """The int8 tree the engine serves, bf16 activations and pools, against the
    float32 reference on the SAME int8 weights, and the int4 control: the
    protocol of ``lib/refcheck.py`` at test widths."""
    served = quantize_params(jax.tree.map(lambda a: a.astype(jnp.bfloat16), params))
    kp, vp = pools(CFG, jnp.bfloat16)
    cfg = dataclasses.replace(CFG, moe_impl="grouped")
    rows, pos = [], 0
    for T in (37, 1, 1, 1, 9, 1):
        out = forward_paged(served, cfg, TOKS[:, pos:pos + T], (pos + jnp.arange(T))[None], kp, vp, TABLE,
                            attn_impl="pallas", fresh_block=pos == 0)
        rows.append(np.asarray(out[0][0], np.float32))
        kp, vp, pos = out[1], out[2], pos + T
    want = np.asarray(ref.logits(served, MODEL, SAMPLE))
    ctrl = np.asarray(ref.logits(served, MODEL, SAMPLE, control=True))
    errs = np.abs(np.concatenate(rows) - want).max(-1) / np.abs(want).max(-1)
    # a tiny model's bf16 router flips a pick in a row or two (that row reads ~10 %): the median holds
    assert float(np.median(errs)) < 0.03 < rel(ctrl, want)


# ---- the engine: the head installed in chunks, admissions behind it, the chunk program


def _float32(tree):
    return jax.tree.map(lambda a: a.astype(F32) if a.dtype == jnp.bfloat16 else a, tree)


def _engine(float32=False, **kw):
    from tpu_voice_agent.serve import PagedDecodeEngine

    # the rehearsal's OWN window (129 under its head of 1024 tokens), buckets the head is LONGER than
    cfg = dataclasses.replace(smallthinker_stack.llama_config(*REHEARSAL), max_seq_len=1536)
    args = dict(cfg=cfg, max_len=1536, batch_slots=8, prefill_buckets=(128, 256),
                fast_forward=8, block_size=128, pool_blocks=80, quant=None)
    eng = PagedDecodeEngine(**{**args, **kw})
    if float32:
        eng.params, eng.k_pool, eng.v_pool = _float32(eng.params), _float32(eng.k_pool), _float32(eng.v_pool)
    return eng


@pytest.fixture(autouse=True)
def _no_site_context_left_behind():
    """``llama_config`` puts the rehearsal's site context into the process's
    prompt head (a global): no test of another module may find it there."""
    yield
    from tpu_voice_agent.services import prompts as P

    P.set_site_context("")


@pytest.fixture(scope="module")
def prompts():
    """Prompts behind the rehearsal's SITE CONTEXT (``llama_config`` puts its
    145 tokens into the prompt head: 879 + 145 = 1024, eight whole blocks);
    taken away again behind the module's tests."""
    from tpu_voice_agent.services import prompts as P

    smallthinker_stack.llama_config(*REHEARSAL)
    assert P.site_context()
    yield [P.render_prompt(t, {}) for t in ("go back", "scroll down to the bottom of the page",
                                            "open the settings page", "search for red shoes")]
    P.set_site_context("")


def test_the_chunked_head_and_a_suffix_behind_it_are_the_reference(prompts):
    """What the cell's comparison holds at published widths, here in float32:
    a head LONGER than the largest bucket through the scratch pool in four
    chunks of 256, whole blocks cached, every chunk under its layer's own mask
    (the window binds from position 129), a suffix admitted behind it — the
    reference's one full forward over the same tokens."""
    eng = _engine(float32=True)
    assert eng._prefix_in_chunks(1024) and not eng._prefix_in_chunks(200)
    assert eng.set_prompt_prefix(*prompts[:2]) == 1024 > eng.prefill_buckets[-1]
    assert not eng._prefix_tail and len(eng._prefix_blocks[0]) == 8
    ids = eng.tokenizer.encode(prompts[1], bos=True)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(eng.prefill_slot(ids, 0))
        want = ref.forward(eng.params, ids, REHEARSAL[0], last=1)
    assert len(ids) > 1024 + 20 and rel(got.reshape(1, -1), want) < 2e-4


def test_a_head_that_fits_a_bucket_is_cached_as_it_always_was():
    """879 tokens under a top bucket of 1024: one fresh block, the sub-block
    tail kept (six of the seven flood cells' heads)."""
    from tpu_voice_agent.services import prompts as P

    eng = _engine(prefill_buckets=(128, 1024))
    P.set_site_context("")  # (``_engine``'s ``llama_config`` put the rehearsal's in: the bare head)
    n = eng.set_prompt_prefix(P.render_prompt("go back", {}), P.render_prompt("open the settings page", {}))
    assert 800 < n < 1024 and not eng._prefix_in_chunks(n)
    assert len(eng._prefix_blocks[0]) == n // 128 and eng._prefix_tail["k"].shape[1] == n % 128


def _pick_logits(logits, state, slots, ns):
    return logits[:, 0, :]


def test_a_group_s_admission_is_the_per_slot_admissions(prompts):
    """Grouped admission (16 slots: ``admit_rows`` 2) behind the cached head:
    the suffix bucket attends the head under each layer's own mask and picks
    the logits the per-slot path does."""
    def admitted(grouped: bool):
        eng = _engine(float32=True, batch_slots=16, pool_blocks=140)
        eng.set_prompt_prefix(*prompts[:2])
        ids = [eng.tokenizer.encode(p, bos=True) for p in prompts[:2]]
        assert eng.admit_rows == 2
        with jax.default_matmul_precision("highest"):
            if grouped:
                out = eng.admit_group([eng.prepare_admission(i, s) for s, i in enumerate(ids)], pick=_pick_logits)
                return np.asarray(out.picked)
            return np.concatenate([np.asarray(eng.prefill_slot(i, s)) for s, i in enumerate(ids)])

    assert rel(admitted(True), admitted(False)) < 1e-4


def test_the_engine_serves_it_behind_the_batcher_at_both_chunk_widths(monkeypatch, prompts):
    """The normal path: admissions behind the chunked head; chunks at the
    compacted and the full width; the routed counters, the attention
    row-blocks and the window's walk published; the same plan at either width."""
    from tpu_voice_agent.serve import ContinuousBatcher
    from tpu_voice_agent.utils import tracing

    fresh = tracing.Metrics()
    monkeypatch.setattr(tracing, "_GLOBAL_METRICS", fresh)
    eng = _engine(kernels="pallas")
    assert eng.cfg.moe_impl == "grouped" and eng.compact_rows == 2 and eng.family.name == "plain"
    assert eng.set_prompt_prefix(*prompts[:2]) == 1024
    chunks, decode_chunk = [], eng.decode_chunk
    monkeypatch.setattr(eng, "decode_chunk", lambda *a, **k: chunks.append(decode_chunk(*a, **k)) or chunks[-1])
    batcher = ContinuousBatcher(eng, chunk_steps=4, max_new_tokens=12)
    solo = batcher.generate_many(prompts[:1])
    many = batcher.generate_many(prompts)
    assert all(r.error is None for r in solo + many)
    assert {c.rows for c in chunks} == {2, 8}
    assert all(set(c.counts) >= {"moe", "attn", "window"} and c.counts["window"].shape == (3,) for c in chunks)
    assert many[0].token_ids == solo[0].token_ids
    counters = fresh.snapshot()["counters"]
    walked, held = counters["attn.window_blocks_walked"], counters["attn.window_blocks_held"]
    assert 0 < walked < 0.4 * held  # a window of 129 behind ~1060 positions: 2-3 blocks of 9
    # (a block and one position: no block lies WHOLE inside every rider's window, so no common range)
    assert counters.get("attn.window_common_row_blocks", 0) == 0
    assert counters["moe.assigned_rows"] > 0 and counters["attn.common_row_blocks"] > 0
