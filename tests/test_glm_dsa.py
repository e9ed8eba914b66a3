"""GLM-5.2 (``glm_moe_dsa``; the benchmark's ``glm-5.2-int8``) at test widths
on the CPU: latent attention under a SELECTION that the layers
``indexer_types`` names "full" make with an indexer of their own and the
"shared" layers behind them reuse (IndexShare) — ``models/dots3.py``'s forward
with the selection carried from layer to layer — against its plain reference
(``benchmark/reference/glm_dsa_decoder.py``, which computes the mask in full
layers and REUSES it in shared ones), with selection binding, a nonzero router
bias, held experts from a nonzero ``first_expert`` and a value head wider than
the key's: prefill, T = 1 steps, a 1 + W block, a ragged packed block; a shared
layer on exactly its full layer's set (every other reading, and every planted
fault, is another model); the 16 shares of a layer; what the pool holds; the
configuration's rules; what the engine asks of the model (the chunked head,
grouped admission, both chunk widths, the counters). The AOT compile for the
TPU at the published widths is ``tests/test_glm_dsa_compile_tpu.py``'s.
"""

import dataclasses
import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import glm_dsa_stack, parse_stack
from benchmark.reference import glm_dsa_decoder as ref
from tpu_voice_agent.models import dots3, llama, mla
from tpu_voice_agent.models.family import family
from tpu_voice_agent.models.llama import forward_paged, init_params, quantize_params

F32 = jnp.float32
CONF = json.loads((Path(__file__).parents[1] / "benchmark/configs/glm-5.2-int8.json").read_text())
# the file's rehearsal widths (F S S F S: a leading dense full layer, two shared
# layers on its set, a routed full layer and a shared one on ITS set; 4 heads of
# 32 + 16 with values of 40), with an ``index_topk`` small enough to BIND inside
# 50 tokens
MODEL, SERVING = parse_stack.as_run(CONF, True)
MODEL = {**MODEL, "index_topk": 16}
CFG = dataclasses.replace(glm_dsa_stack.llama_config(MODEL, {**SERVING, "site_context_tokens": 0}),
                          max_seq_len=256)
BS, N = 8, 12
TABLE = jnp.asarray([[1, 2, 3, 4, 5, 6, 7]], jnp.int32)
TOKS = jax.random.randint(jax.random.key(1), (1, 50), 0, CFG.vocab_size)
SAMPLE = {"tokens": [int(t) for t in TOKS[0]], "rows": 50}
STATS = mla.LATENT_STATS + dots3.SPARSE_STATS + dots3.CARRY_STATS


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float((np.abs(got - want).max(-1) / np.abs(want).max(-1)).max())


def pools(cfg, dtype, n=N, bs=BS):
    planes = dots3.cache_spec(cfg)["planes"]
    return tuple({name: jnp.zeros((L, n, bs, w), dtype) for name, (L, w) in planes[p].items()}
                 for p in ("k", "v"))


def through_the_pool(params, cfg, impl, dtype, steps=(37, 1, 1, 1, 9, 1), toks=TOKS, **kw):
    """50 tokens as the engine feeds them: a prefill of 37, three T = 1 steps,
    one 1 + 8 block, one more step — through the paged planes. -> (50, V)."""
    kp, vp = pools(cfg, dtype)
    rows, pos = [], 0
    for T in steps:
        out = forward_paged(params, cfg, toks[:, pos:pos + T], (pos + jnp.arange(T))[None], kp, vp,
                            TABLE, attn_impl=impl, **kw)
        rows.append(np.asarray(out[0][0]))
        kp, vp, pos = out[1], out[2], pos + T
    return np.concatenate(rows)


def test_the_configuration_keeps_the_published_widths_and_names_its_cut():
    """The file's top level is the catalog's ``config`` but for the four
    ``reduced`` keys; the program's configuration reads every size from it; the
    pool gives an index plane to the layers that run an indexer alone."""
    published = {"hidden_size": 6144, "intermediate_size": 12288, "moe_intermediate_size": 2048,
                 "num_attention_heads": 64, "kv_lora_rank": 512, "q_lora_rank": 2048,
                 "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "qk_head_dim": 256, "v_head_dim": 256,
                 "index_n_heads": 32, "index_head_dim": 128, "index_topk": 2048, "index_topk_freq": 4,
                 "index_skip_topk_offset": 3, "num_experts_per_tok": 8, "n_shared_experts": 1,
                 "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
                 "n_group": 1, "topk_group": 1, "norm_topk_prob": True, "num_nextn_predict_layers": 1,
                 "rms_norm_eps": 1e-05, "max_position_embeddings": 1048576, "model_type": "glm_moe_dsa"}
    assert {k: CONF[k] for k in published} == published
    assert CONF["rope_parameters"] == {"rope_theta": 8000000, "rope_type": "default"}
    assert CONF["rope_theta"] == CONF["rope_parameters"]["rope_theta"]
    cut = ("num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size")
    assert [(CONF[k], CONF[k + "_published"]) for k in cut] == [(8, 78), (1, 3), (16, 256), (19360, 154880)]
    assert sorted(CONF["reduced"]) == sorted(CONF["reduced_why"]) == sorted(cut)
    assert CONF["vocab_size"] * 8 == CONF["vocab_size_published"]
    assert CONF["n_routed_experts"] * CONF["chips_sharing_a_layer"] == CONF["n_routed_experts_published"]
    # the published pattern under the reading of index_topk_freq / index_skip_topk_offset, and the slice served
    types = CONF["indexer_types"]
    assert len(types) == len(CONF["mlp_layer_types"]) == 78
    assert types == ["full" if i < 3 or (i - 3) % CONF["index_topk_freq"] == CONF["index_skip_topk_offset"]
                     else "shared" for i in range(78)]
    assert CONF["served_layers"] == "2-9"
    assert CONF["indexer_kinds"] == "".join({"full": "F", "shared": "S"}[t] for t in types[2:10]) == "FSSSFSSS"
    assert CONF["mlp_layer_types"][2:10] == ["dense"] + ["sparse"] * 7
    assert "multi-token" in CONF["left_out"] and "ABSENT" in CONF["deployment"]
    s = CONF["serving"]
    dots3_serving = json.loads((Path(__file__).parents[1]
                                / "benchmark/configs/dots3-note-prev-int8.json").read_text())["serving"]
    assert {k: v for k, v in s.items() if k != "weights_seed"} == \
        {k: v for k, v in dots3_serving.items() if k != "weights_seed"}
    m, s = parse_stack.as_run(CONF, False)
    full = glm_dsa_stack.llama_config(m, {**s, "site_context_tokens": 0})
    assert (full.dim, full.n_heads, full.kv_lora_rank, full.q_lora_rank, full.qk_nope_dim, full.qk_rope_dim,
            full.v_head_dim, full.head_dim, full.index_n_heads, full.index_head_dim, full.index_topk) == \
        (6144, 64, 512, 2048, 192, 64, 256, 256, 32, 128, 2048)
    assert (full.n_experts, full.n_held, full.first_expert, full.top_k, full.first_dense_layers,
            full.dense_ffn_dim, full.ffn_dim, full.vocab_size, full.router_scale, full.rope_theta) == \
        (256, 16, 0, 8, 1, 12288, 2048, 19360, 2.5, 8e6)
    assert full.router_bias and full.shared_sum and not (full.attn_gate or full.lora_rescale)
    assert full.layer_types == ("full",) * 8
    assert full.indexer_types == ("full", "shared", "shared", "shared") * 2
    fam = family(full)  # the record the serving side reads
    assert (fam.name, fam.module, fam.scratch_prefix, fam.prefix_whole_blocks) == ("sparse", dots3, True, True)
    # ONE row of 512 + 64 a token in all 8 layers, an index key in the TWO that run an indexer
    assert fam.cache["planes"] == {"k": {"kv": (2, 576), "idx": (2, 128), "shared": (6, 576)}, "v": {}}
    assert fam.token_bytes == 8 * 1152 + 2 * 256 == 9728
    assert dots3.layer_plan(full)[:5] == (("full", 0), ("shared", 0), ("shared", 1), ("shared", 2), ("full", 1))
    assert fam.count("latent").metrics == tuple(f"attn.{n}" for n in STATS)
    # the rehearsal: every mechanism present, selection binding behind its head
    assert (CFG.indexer_types, CFG.first_dense_layers) == (("full", "shared", "shared", "full", "shared"), 1)
    assert (CFG.n_heads, CFG.qk_nope_dim, CFG.v_head_dim, CFG.kv_lora_rank) == (4, 32, 40, 48)
    assert (CFG.n_experts, CFG.n_held, CFG.first_expert, CFG.top_k) == (16, 4, 4, 3)
    assert parse_stack.as_run(CONF, True)[0]["index_topk"] < 1024


def test_the_parameter_tree_stacks_indexer_leaves_for_the_indexer_layers_alone():
    params = init_params(CFG, jax.random.key(0), F32)
    index = {"w_iq", "w_ik", "w_iw", "ik_norm"}
    assert index <= set(params["attn_full"]) and not index & set(params["attn_shared"])
    assert set(params["attn_full"]) - index == set(params["attn_shared"])
    assert params["attn_full"]["w_iq"].shape[0] == 2 and params["attn_shared"]["w_qa"].shape[0] == 3
    assert params["attn_full"]["w_kvb"].shape == (2, 48, 4 * (32 + 40)) and params["attn_full"]["wo"].shape == (2, 160, 128)
    assert float(jnp.abs(params["layers"]["router_bias"]).min()) > 0
    served = jax.eval_shape(lambda: glm_dsa_stack.make_params(CFG, 1))
    assert jax.tree.structure(served) == jax.tree.structure(quantize_params(params))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prefill_then_decode_through_the_pool_is_the_reference_full_forward(impl):
    """Float32: a prefill of 37 (past ``index_topk`` 16: selection binds INSIDE
    it), T = 1 steps and a 1 + 8 block — a shared layer gathering ITS OWN rows at
    the coordinates its full layer chose — against the reference's ONE full
    forward, which masks. Under "pallas" the indexer and the gathered kernel run
    (interpreted), and the grouped kernel the experts."""
    params = init_params(CFG, jax.random.key(0), F32)
    cfg = dataclasses.replace(CFG, moe_impl="grouped" if impl == "pallas" else "dense")
    want = ref.logits(params, MODEL, SAMPLE)
    with jax.default_matmul_precision("highest"):
        assert rel(through_the_pool(params, cfg, impl, F32), want) < 1e-4


def test_a_ragged_block_of_two_rows_behind_chunks_is_the_reference():
    """Two rows on tables of their own: the head in two chunks of 16, a step,
    then a 1 + 2 block where one row holds 3 real positions and the other 2
    (``n_real``: the real positions go first through the tiles, and the
    selection is carried in THAT order) — each real position's logits are the
    reference's, and the counts say who selected and who was handed a set."""
    params = init_params(CFG, jax.random.key(0), F32)
    toks = jax.random.randint(jax.random.key(3), (2, 36), 0, CFG.vocab_size)
    tables = jnp.asarray([[1, 2, 3, 4, 5], [6, 7, 8, 9, 10]], jnp.int32)
    kp, vp = pools(CFG, F32)
    got = []
    with jax.default_matmul_precision("highest"):
        for at, T, kw in ((0, 16, {}), (16, 16, {}), (32, 1, {}),
                          (33, 3, {"n_real": jnp.asarray([3, 2]), "latent_stats": True})):
            out = forward_paged(params, CFG, toks[:, at:at + T], jnp.tile(at + jnp.arange(T)[None], (2, 1)),
                                kp, vp, tables, attn_impl="xla", **kw)
            kp, vp = out[1], out[2]
            got.append(np.asarray(out[0]))
        stats = dict(zip(STATS, np.asarray(out[-1]).tolist()))
    got = np.concatenate(got, axis=1)
    for b, real in ((0, 36), (1, 35)):
        want = ref.forward(params, [int(t) for t in toks[b]], MODEL, last=36)
        assert rel(got[b, :real], np.asarray(want)[:real]) < 1e-4
    # 5 real positions at 33, 34, 35 | 33, 34: all FIVE layers see pos + 1 keys and attend 16 ...
    assert stats["keys_visible"] == 5 * (34 + 35 + 36 + 34 + 35) and stats["keys_selected"] == 5 * 5 * 16
    # ... chosen in TWO: the indexer scores two planes, three layers are handed a set
    assert stats["index_keys_scored"] == 2 * 6 * N * BS
    assert (stats["selections_made"], stats["selections_carried"]) == (2 * 5, 3 * 5)
    # one tile pass a selected layer, and the rule walks 40 keys of table behind top-16 at 4 heads
    assert (stats["selected_tiles"], stats["selected_tiles_walked"]) == (5, 5)
    assert stats["window_keys_read"] == 0 and stats["latent_query_rows"] == 2 * 3 * 5 * CFG.n_heads


@functools.lru_cache(maxsize=None)
def _rows_behind_a_head(B: int):
    params = init_params(CFG, jax.random.key(0), F32)
    toks = jax.random.randint(jax.random.key(5), (B, 29), 0, CFG.vocab_size)
    tables = jnp.arange(1, 4 * B + 1, dtype=jnp.int32).reshape(B, 4)
    kp, vp = pools(CFG, F32, n=4 * B + 2)
    with jax.default_matmul_precision("highest"):
        out = forward_paged(params, CFG, toks[:, :20], jnp.tile(jnp.arange(20)[None], (B, 1)), kp, vp,
                            tables, attn_impl="xla")
    return params, toks, tables, out[1], out[2]


# (n_real a row, the packed tile, a parked row or None): ``tests/test_dots3.py``'s cases that
# differ in what the carried selection has to survive — several attention tiles, a row with no
# real position, a parked row, filler slots behind the last real position
PACKED = {
    "one_tile": ([2, 1, 3, 2], 12, None),
    "several_tiles": ([9, 5, 7, 4], 12, None),
    "a_row_with_none": ([3, 0, 2, 1], 12, None),
    "a_parked_row": ([3, 2, 2, 1], 12, 1),
    "filler_slots_in_the_last_of_two_tiles": ([5, 4, 6, 3, 5, 4, 6, 4], 32, None),
}


@pytest.mark.parametrize("case", sorted(PACKED))
def test_the_packed_walk_is_the_whole_block(case):
    """A 1 + 8 block of four or eight rows as the chunk loop builds it, float32:
    walked in tiles of packed rows, the residual AND the selection packed, the
    logits of every real position and every plane at every index a real
    position writes are the whole block's; nothing else in the pool moves."""
    n, tile, parked = PACKED[case]
    B = len(n)
    params, toks, tables, kp, vp = _rows_behind_a_head(B)
    trash = 4 * B + 1
    n = jnp.asarray(n, jnp.int32)
    t = jnp.minimum(jnp.arange(9)[None], jnp.maximum(n[:, None] - 1, 0))  # (B, 9)
    kw = {"n_real": n, "attn_impl": "xla", "latent_stats": True}
    if parked is not None:
        kw.update(write_mask=jnp.arange(B) != parked, trash_idx=jnp.full((B,), trash * BS, jnp.int32))
    block = lambda: (jnp.take_along_axis(toks[:, 20:], t, axis=1), 20 + t,  # the pools are donated
                     *jax.tree.map(jnp.copy, (kp, vp)), tables)
    with jax.default_matmul_precision("highest"):
        whole = forward_paged(params, CFG, *block(), **kw)
        packed = forward_paged(params, CFG, *block(), ffn_pack=tile, **kw)
    assert len(packed) == len(whole) + 1
    assert np.asarray(packed[5]).tolist() == np.asarray(whole[5]).tolist()
    live = np.asarray(n) * (np.arange(B) != parked)
    made, carried = np.asarray(packed[5]).tolist()[-2:]
    assert (made, carried) == (2 * int(live.sum()), 3 * int(live.sum()))
    for b in range(B):
        if live[b]:
            assert rel(packed[0][b, :int(live[b])], whole[0][b, :int(live[b])]) < 1e-4, b
    written = np.zeros((trash + 1, BS), bool)
    for b in range(B):
        for p in range(20, 20 + int(live[b])):
            written[int(tables[b, p // BS]), p % BS] = True
    for before, got, want in zip(jax.tree.leaves((kp, vp)), jax.tree.leaves(packed[1:3]),
                                 jax.tree.leaves(whole[1:3])):
        before, got, want = (np.asarray(a) for a in (before, got, want))
        for layer in range(want.shape[0]):
            assert (np.abs(got[layer][written] - want[layer][written]).max()
                    < 1e-4 * np.abs(want[layer]).max()), layer
        still = ~written
        still[trash] = False  # the trash block: a parked row's writes
        assert np.array_equal(got[:, still], before[:, still])


@pytest.fixture(scope="module")
def served_f32():
    params = init_params(CFG, jax.random.key(0), F32)
    with jax.default_matmul_precision("highest"):
        return params, through_the_pool(params, CFG, "xla", F32)


FLIPS = {
    "the_first_full_layer_s_set_everywhere": ({"shared": "first"}, {}),
    "a_shared_layer_that_scores_again": ({"shared": "rescored"}, {}),
    "a_shared_layer_over_every_key": ({"shared": "all"}, {}),
    "every_layer_with_an_indexer_of_its_own_pattern": ({}, {"indexer_kinds": "FSFSS"}),
    "fifteen_keys": ({}, {"index_topk": 15}),
    "every_key": ({}, {"index_topk": 64}),
    "latent_norm_eps": ({}, {"latent_norm_eps": 1e-2}),
    "another_theta": ({}, {"rope_theta": 50000.0}),
    "top_2_experts": ({}, {"num_experts_per_tok": 2}),
    "held_from_expert_0": ({}, {"first_expert": 0}),
    "gates_that_sum_to_one": ({}, {"routed_scaling_factor": 1.0}),
}


@pytest.mark.parametrize("flip", sorted(FLIPS))
def test_each_other_reading_is_another_model(served_f32, flip):
    """A shared layer attends EXACTLY its full layer's set: the reference under
    one other reading — the first full layer's set everywhere, a re-score of
    the shared layer's own input, every key, another pattern, another
    ``index_topk``, eps, theta, expert rule or share — is not what is served."""
    params, got = served_f32
    departures, keys = FLIPS[flip]
    want = ref.forward(params, SAMPLE["tokens"], {**MODEL, **keys}, last=50, **departures)
    assert rel(got, want) > 1e-3, flip


def test_the_bias_selects(served_f32):
    params, got = served_f32
    zero = {**params, "layers": {**params["layers"],
                                 "router_bias": jnp.zeros_like(params["layers"]["router_bias"])}}
    assert rel(got, ref.logits(zero, MODEL, SAMPLE)) > 1e-3


FETCHES = ("walked", "gathered")


@pytest.fixture
def fetch(request, monkeypatch):
    """The rule bound to one of its answers: a selected layer's keys WALKED under the mask, or
    GATHERED — for a forward traced anew under it (a jit of the test's own: the module's hands
    back what it traced first)."""
    from tpu_voice_agent.ops import sparse_latent as sl

    monkeypatch.setattr(sl, "walks", lambda keys, topk, heads: request.param == "walked")
    return request.param


def _prefill(params, cfg, impl="xla", fault=None, **kw):
    kp, vp = pools(cfg, F32)
    return jax.jit(functools.partial(dots3.forward_paged, attn_impl=impl, fault=fault, **kw),
                   static_argnums=1)(params, cfg, TOKS, jnp.arange(50)[None], kp, vp, TABLE)


@pytest.mark.parametrize("fetch", FETCHES, indirect=True)
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_served_forward_is_the_same_walked_and_gathered(served_f32, impl, fetch):
    """ISSUE 62: one softmax over one key set, two ways to fetch it — a prefill
    of 50 under either answer of the rule, kernels and twins, is the forward the
    module serves; the selection is CARRIED to the shared layers as the fetch
    takes it (the members' mask, or the chosen keys' positions and blocks), and
    every count but the walked tile passes is the same."""
    params, sound = served_f32
    with jax.default_matmul_precision("highest"):
        out = _prefill(params, CFG, impl, latent_stats=True)
    assert rel(out[0][0], sound) < 1e-4
    stats = dict(zip(STATS, np.asarray(out[-1]).tolist()))
    tiles = 5 * 5  # five selected layers, 50 positions in tiles of 10
    assert (stats["selected_tiles"], stats["selected_tiles_walked"]) == (tiles, tiles * (fetch == "walked"))
    assert stats["keys_selected"] == 5 * sum(min(t + 1, 16) for t in range(50))
    assert (stats["selections_made"], stats["selections_carried"]) == (2 * 50, 3 * 50)
    # (ISSUE 63) two indexed layers' tiles select by threshold where they walk with the kernels on
    assert stats["selections_thresholded"] == 2 * 5 * (fetch == "walked" and impl == "pallas")


def test_a_walked_forward_through_the_threshold_select_is_the_one_through_top_k_bit_for_bit(served_f32, monkeypatch):
    """ISSUE 63: the SAME set by another way to it. A walked prefill of 50 with the kernels on,
    its indexed layers' tiles selecting through ``threshold_members`` (no sorted row), against the
    parent's path — ``lax.top_k`` + ``top_k_members`` bound in its place: every mask a full layer
    hands on (what the three shared layers behind it attend: ``layer/attn/carry``) is the same mask,
    and the logits and every cached row are the same BITS."""
    from tpu_voice_agent.ops import sparse_latent as sl

    params, _ = served_f32
    monkeypatch.setattr(sl, "walks", lambda keys, topk, heads: True)

    def prefill_through(members):
        masks = []

        def recorded(mine, k):
            made = members(mine, k)
            jax.debug.callback(lambda m: masks.append(np.asarray(m)), made, ordered=True)
            return made

        monkeypatch.setattr(sl, "threshold_members", recorded)
        with jax.default_matmul_precision("highest"):
            out = jax.block_until_ready(_prefill(params, CFG, "pallas"))
        jax.effects_barrier()
        return out, masks

    (new, new_masks), (old, old_masks) = prefill_through(sl.threshold_members), prefill_through(sl.chosen_mask)
    assert len(new_masks) == len(old_masks) == 2 * 5  # two indexed layers, 50 positions in tiles of 10
    assert all(m.dtype == bool and m.shape == (10, TABLE.shape[1] * BS) and 0 < m.sum() < m.size for m in new_masks)
    assert all(np.array_equal(a, b) for a, b in zip(new_masks, old_masks))
    assert np.array_equal(new[0], old[0])
    assert all(np.array_equal(a, b) for a, b in zip(jax.tree.leaves(new[1:3]), jax.tree.leaves(old[1:3])))


@pytest.mark.parametrize("fetch", FETCHES, indirect=True)
@pytest.mark.parametrize("fault", ("no_selection", "first_keys") + dots3.CARRY_FAULTS)
def test_each_planted_fault_moves_the_served_logits(served_f32, fault, fetch):
    """``dots3.forward_paged(fault=...)``: what the comparison's limit is set
    against on the chip (``benchmark/tools/indexshare_check.py``) — planted in
    the SERVED program, each departs from the sound one at float32 by far more
    than rounding, whichever way the selected keys are fetched (``other_row``
    rolls the members' mask where it rolled the chosen positions);
    ``first_selection`` and ``shared_all_keys`` land where the reference's
    matching departure does."""
    params, sound = served_f32
    prefill = lambda fault: _prefill(params, CFG, fault=fault)
    with jax.default_matmul_precision("highest"):
        out = prefill(fault)
        if fault == "no_selection":
            assert rel(prefill(None)[0][0], sound) < 1e-4  # one prefill of 50 is the six steps
        twin = {"first_selection": "first", "shared_all_keys": "all"}.get(fault)
        if twin:
            assert rel(out[0][0], ref.forward(params, SAMPLE["tokens"], MODEL, last=50, shared=twin)) < 1e-4
    assert rel(out[0][0], sound) > 1e-3


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """The model-configs guide's section 4: the routed parts the SIXTEEN shares
    of the rehearsal's 16 experts give (one held each, ids 0..15), with the
    shared expert counted ONCE, are the uncut layer — in the reference and in
    the served ``_ffn``, gates x 2.5 and all."""
    from benchmark.reference import decoder as dense_ref
    from benchmark.reference.moonlight_decoder import shared_part

    uncut_cfg = dataclasses.replace(CFG, experts_held=0, first_expert=0)
    up = init_params(uncut_cfg, jax.random.key(4), F32)
    layer = jax.tree.map(lambda a: a[0], up["layers"])
    u = jax.random.normal(jax.random.key(5), (1, 12, CFG.dim), F32)
    kw = dict(top_k=CFG.top_k, scale=CFG.router_scale)
    assert CFG.router_scale == 2.5
    with jax.default_matmul_precision("highest"):
        whole = ref.routed_part(u[0], layer, dense_ref.dense, first=0, **kw)
        served_whole, _ = llama._ffn(layer, u, uncut_cfg)
        parts, served_parts = [], []
        for first in range(16):
            share = {**layer, **{k: layer[k][first:first + 1] for k in ("moe_gate", "moe_up", "moe_down")}}
            parts.append(ref.routed_part(u[0], share, dense_ref.dense, first=first, **kw))
            cfg = dataclasses.replace(CFG, experts_held=1, first_expert=first)
            served_parts.append(llama._ffn(share, u, cfg)[0][0])
        shared = shared_part(u[0], layer, dense_ref.dense, n_shared=1)
    assert rel(sum(parts), whole) < 1e-5
    assert sum(float(jnp.abs(p).max()) > 1e-3 for p in parts) >= 3  # a share is not nothing
    # each served share carries the shared expert: counted once, fifteen of them come off
    assert rel(sum(served_parts) - 15 * shared, served_whole[0]) < 1e-4
    assert rel(served_whole[0], whole + shared) < 1e-4


def test_the_served_precision_reads_inside_the_limit_and_int4_outside():
    """int8 weights, bf16 activations and bf16 planes against the float32
    reference on the same weights, and the int4 control; the chip's limit at
    published widths is the reference module's own. With the selection VOID
    (``index_topk`` past the context): sixteen keys of fifty chosen in bfloat16
    are other keys than float32 chooses, and at these widths one key is a
    sixteenth of a softmax — the rehearsal's 256 of 1060 and the cell's 2048 of
    8.3 k are held by the comparison itself."""
    model = {**MODEL, "index_topk": 64}
    cfg = dataclasses.replace(CFG, index_topk=64)
    params = quantize_params(init_params(cfg, jax.random.key(0)))
    assert params["attn_full"]["w_kvb"]["q"].dtype == params["attn_shared"]["w_qb"]["q"].dtype == jnp.int8
    assert params["attn_full"]["ik_norm"].dtype == jnp.bfloat16
    want = np.asarray(ref.logits(params, model, SAMPLE))
    rows = lambda got: np.abs(np.asarray(got, np.float32) - want).max(-1) / np.abs(want).max(-1)
    served = rows(through_the_pool(params, cfg, "xla", jnp.bfloat16))
    control = rows(ref.logits(params, model, SAMPLE, control=True))
    assert 1e-3 < np.median(served) < 0.03 and np.mean(served < 0.08) > 0.85
    assert np.median(control) > 0.08 > np.quantile(served, 0.85)


def test_the_pool_holds_an_index_plane_for_the_indexer_layers_alone_at_published_widths():
    """Eight layers of ONE row of 512 + 64, a 128-wide index key in the two
    that run an indexer, no V pool at all: read from the pool's own shapes, the
    engine's gauge and the byte plan — through ``serve/paged.py`` and
    ``utils/hbmledger.py`` as they stand (PR 46's contract)."""
    from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
    from tpu_voice_agent.serve import PagedDecodeEngine
    from tpu_voice_agent.utils import hbmledger

    m, s = parse_stack.as_run(CONF, False)
    full = glm_dsa_stack.llama_config(m, {**s, "site_context_tokens": 0})
    eng = PagedDecodeEngine(cfg=full, tokenizer=default_tokenizer(), quant="int8", batch_slots=2,
                            block_size=128, pool_blocks=4, max_len=256, prefill_buckets=(128,),
                            init_weights=False)
    assert eng.sparse and eng.latent and not eng.hybrid
    assert {n: a.shape for n, a in eng.k_pool.items()} == {
        "kv": (2, 4, 128, 576), "idx": (2, 4, 128, 128), "shared": (6, 4, 128, 576)}
    assert dict(eng.v_pool) == {}
    pool_bytes = sum(a.nbytes for a in eng.k_pool.values())
    assert pool_bytes == 4 * eng.kv_bytes_per_block == 4 * 128 * 9728
    assert hbmledger.engine_hbm_plan(eng)["kv_pool_bytes"] == pool_bytes


def test_the_configuration_states_who_runs_an_indexer_and_refuses_what_it_cannot_mean():
    base = dict(kv_lora_rank=48, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=12, n_layers=3, n_heads=2,
                n_kv_heads=2, dim=32, head_size=24, q_lora_rank=16, index_topk=8, index_n_heads=2,
                index_head_dim=16, layer_types=("full",) * 3)
    ok = llama.LlamaConfig(**base, indexer_types=("full", "shared", "full"))
    assert dots3.layer_kinds(ok) == ("full", "shared", "full") and set(dots3.kinds(ok)) == {"full", "shared"}
    assert dots3.layer_kinds(llama.LlamaConfig(**base)) == ("full",) * 3  # every full layer its own
    with pytest.raises(ValueError, match="indexer_types"):
        llama.LlamaConfig(**base, indexer_types=("full", "shared"))
    with pytest.raises(ValueError, match="indexer_types"):
        llama.LlamaConfig(**base, indexer_types=("full", "shared", "none"))
    with pytest.raises(ValueError, match="the first layer runs an indexer"):
        llama.LlamaConfig(**base, indexer_types=("shared", "full", "shared"))
    with pytest.raises(ValueError, match="indexer_types"):
        llama.LlamaConfig(**{**base, "index_topk": 0, "layer_types": (), "q_lora_rank": 0},
                          indexer_types=("full", "shared", "full"))
    swa = dict(swa_n_heads=2, swa_kv_lora_rank=16, swa_qk_nope_dim=8, swa_qk_rope_dim=8, swa_v_head_dim=8,
               swa_rope_theta=1e4, sliding_window=4)
    with pytest.raises(NotImplementedError, match="past a sliding layer"):
        llama.LlamaConfig(**{**base, "layer_types": ("full", "sliding", "full")}, **swa,
                          indexer_types=("full", "shared", "shared"))


REHEARSAL = parse_stack.as_run(CONF, True)


def _float32(tree):
    return jax.tree.map(lambda a: a.astype(F32) if a.dtype == jnp.bfloat16 else a, tree)


def _engine(float32=False, **kw):
    from tpu_voice_agent.serve import PagedDecodeEngine

    # the rehearsal's OWN selection (256: it binds behind its head of 1024 tokens), buckets the
    # head is LONGER than
    cfg = dataclasses.replace(glm_dsa_stack.llama_config(*REHEARSAL), max_seq_len=1536)
    args = dict(cfg=cfg, max_len=1536, batch_slots=8, prefill_buckets=(128, 256),
                fast_forward=8, block_size=128, pool_blocks=80, quant=None)
    eng = PagedDecodeEngine(**{**args, **kw})
    if float32:  # weights and planes: no rounding for a selection to turn on
        eng.params, eng.k_pool, eng.v_pool = _float32(eng.params), _float32(eng.k_pool), _float32(eng.v_pool)
    return eng


@pytest.fixture(scope="module")
def prompts():
    """Prompts behind the rehearsal's SITE CONTEXT (``llama_config`` puts its
    145 tokens into the prompt head: 879 + 145 = 1024, eight whole blocks);
    taken away again behind the module's tests."""
    from tpu_voice_agent.services import prompts as P

    glm_dsa_stack.llama_config(*REHEARSAL)
    assert P.site_context()
    yield [P.render_prompt(t, {}) for t in ("go back", "scroll down to the bottom of the page",
                                            "open the settings page", "search for red shoes")]
    P.set_site_context("")


def test_the_engine_serves_it_behind_the_batcher_at_both_chunk_widths(monkeypatch, prompts):
    """The normal path: the prompt head — LONGER than the largest bucket —
    prefilled in chunks through ONE scratch pool, whole blocks of it cached;
    admissions behind it; chunks at the compacted and the full width; the
    routed counters, the latent reads, the selection's counters and who made
    and who carried a selection published through the family record's counts."""
    from tpu_voice_agent.serve import ContinuousBatcher
    from tpu_voice_agent.utils import tracing

    fresh = tracing.Metrics()
    monkeypatch.setattr(tracing, "_GLOBAL_METRICS", fresh)
    eng = _engine(kernels="pallas")
    assert eng.cfg.moe_impl == "grouped" and eng.compact_rows == 2 and eng.sparse
    P = eng.set_prompt_prefix(*prompts[:2])
    assert P == 1024 > eng.prefill_buckets[-1] and not eng._prefix_tail and len(eng._prefix_blocks[0]) == 8
    held = np.asarray(eng._prefix_blocks[0])  # every plane holds the head
    assert all(float(jnp.abs(a[:, held]).min(axis=(0, 2, 3)).max()) > 0 for a in eng.k_pool.values())
    chunks, decode_chunk = [], eng.decode_chunk
    monkeypatch.setattr(eng, "decode_chunk", lambda *a, **k: chunks.append(decode_chunk(*a, **k)) or chunks[-1])
    batcher = ContinuousBatcher(eng, chunk_steps=4, max_new_tokens=12)
    solo = batcher.generate_many(prompts[:1])
    many = batcher.generate_many(prompts)
    assert all(r.error is None for r in solo + many)
    assert {c.rows for c in chunks} == {2, 8}
    assert all(c.counts["latent"].shape == (len(STATS),) for c in chunks)
    assert many[0].token_ids == solo[0].token_ids  # the same plan at either width
    counters = fresh.snapshot()["counters"]
    assert counters["moe.assigned_rows"] > counters["moe.local_rows"] > 0
    visible, chosen = counters["attn.keys_visible"], counters["attn.keys_selected"]
    assert 0 < chosen < 0.3 * visible  # 256 of ~1050 keys a position
    made, carried = counters["attn.selections_made"], counters["attn.selections_carried"]
    assert made > 0 and carried * 2 == made * 3  # F S S F S: three layers in five are handed a set
    # what says the walk engaged: every tile pass took the path the rule picks at these shapes
    from tpu_voice_agent.ops import sparse_latent as sl

    walked = sl.walks(eng.block_tables.shape[1] * eng.block_size, eng.cfg.index_topk, eng.cfg.n_heads)
    assert counters["attn.selected_tiles"] > 0
    assert counters["attn.selected_tiles_walked"] == walked * counters["attn.selected_tiles"]
    # ... and the threshold select in the two layers in five that select (ISSUE 63)
    assert counters["attn.selections_thresholded"] * 5 == counters["attn.selected_tiles_walked"] * 2
    # the indexer scores TWO planes a forward: scored / (made / 2 positions) stays the pool's size
    assert counters["attn.index_keys_scored"] >= visible * 2 / 5 and counters["attn.window_keys_read"] == 0


def test_the_chunk_loop_gives_the_same_plans_walked_and_whole(monkeypatch, prompts):
    """Behind the pool the model's OWN prefill wrote, float32: four requests on
    eight slots with the block walked in tiles of 24 packed rows end in the
    plans the whole block gives and leave its cache, and the counters say it
    walked."""
    from tpu_voice_agent.serve import ContinuousBatcher
    from tpu_voice_agent.utils import tracing

    plans = {}
    for name, width in (("whole", 0), ("walked", 24)):
        fresh = tracing.Metrics()
        monkeypatch.setattr(tracing, "_GLOBAL_METRICS", fresh)
        eng = _engine(float32=True)  # an engine each: the same blocks to the same requests
        eng.set_prompt_prefix(*prompts[:2])
        eng.ffn_pack_rows = width
        with jax.default_matmul_precision("highest"):
            outs = ContinuousBatcher(eng, chunk_steps=4, max_new_tokens=16).generate_many(prompts)
        assert all(r.error is None for r in outs)
        planes = [np.asarray(a[:, 1:]) for a in jax.tree.leaves((eng.k_pool, eng.v_pool))]  # (0: trash)
        plans[name] = ([r.token_ids for r in outs], fresh.snapshot()["counters"], planes)
    assert plans["walked"][0] == plans["whole"][0]
    for got, want in zip(plans["walked"][2], plans["whole"][2]):
        assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()
    assert plans["whole"][1].get("ffn.forwards_packed", 0) == 0
    walked = plans["walked"][1]
    assert 0 < walked["ffn.forwards_packed"] <= walked["scheduler.forwards"]
    # (a block that packs nothing is not told its real positions: it counts, and computes, all nine a row)
    assert 0 < walked["attn.selections_carried"] < plans["whole"][1]["attn.selections_carried"]


def test_the_chunked_head_and_a_suffix_behind_it_are_the_reference(prompts):
    """What the cell's comparison holds at published widths, here in float32:
    the head through the scratch pool in four chunks of 256 (selection from
    position 256 on, carried inside every chunk), a suffix admitted behind it —
    the reference's one full forward over the same tokens."""
    eng = _engine(float32=True)
    assert eng.set_prompt_prefix(*prompts[:2]) == 1024
    ids = eng.tokenizer.encode(prompts[1], bos=True)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(eng.prefill_slot(ids, 0))
        want = ref.forward(eng.params, ids, REHEARSAL[0], last=1)
    assert len(ids) > 1024 + 20 and rel(got.reshape(1, -1), want) < 2e-4


def _pick_logits(logits, state, slots, ns):
    return logits[:, 0, :]


def test_a_group_s_admission_is_the_per_slot_admissions(prompts):
    """Grouped admission (16 slots: ``admit_rows`` 2) behind the cached head
    writes every plane and picks the logits the per-slot path does."""
    def admitted(grouped: bool):
        eng = _engine(float32=True, batch_slots=16, pool_blocks=140)
        eng.set_prompt_prefix(*prompts[:2])
        ids = [eng.tokenizer.encode(p, bos=True) for p in prompts[:2]]
        assert eng.admit_rows == 2
        if grouped:
            out = eng.admit_group([eng.prepare_admission(i, s) for s, i in enumerate(ids)], pick=_pick_logits)
            logits = np.asarray(out.picked)
        else:
            logits = np.concatenate([np.asarray(eng.prefill_slot(i, s)) for s, i in enumerate(ids)])
        owned = [eng._slot_owned[s][0] for s in range(2)]
        planes = [[np.asarray(a[:, b], np.float32) for a in eng.k_pool.values()] for b in owned]
        return logits, planes, [len(i) for i in ids], eng

    one, planes_one, lens, eng = admitted(False)
    grp, planes_grp, _, _ = admitted(True)
    assert rel(grp, one) < 1e-4
    P = len(eng.prefix_ids)
    assert P == 1024
    for a, b, n in zip(planes_one, planes_grp, lens):  # the suffix's cache, position by position
        assert len(a) == 3  # kv (a full layer's rows [c | r]), idx, shared
        for x, y in zip(a, b):
            assert float(np.abs(x[:, :n - P] - y[:, :n - P]).max()) < 1e-4


@pytest.mark.parametrize("what", ["radix", "kv_quant", "handoff", "dense_cache", "dense_forward"])
def test_what_moves_k_and_v_planes_refuses_the_planes_by_kind_by_type(what):
    from tpu_voice_agent.serve import DecodeEngine

    if what == "dense_forward":
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
        else:
            DecodeEngine(cfg=CFG, max_len=256, batch_slots=2, quant=None)
