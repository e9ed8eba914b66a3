"""dots3-note-prev (``dots3_note``; the benchmark's ``dots3-note-prev-int8``) at
test widths on the CPU: the served path — LEARNED SPARSE attention over a
latent cache in the full layers (an indexer choosing ``index_topk`` keys),
WINDOWED latent attention of its own sizes in the sliding ones, a compressed
query, a gate a head, planes by layer KIND on one block table — against its
plain reference (``benchmark/reference/dots3_decoder.py``, which decompresses
keys and values a head and applies selection and window as masks), with
selection AND window binding; each ``assumed`` reading flipped; the kernels
against their twins; the share test of the model-configs guide's section 4;
what the pool holds; every refusal by type; and what the engine asks of the
model (a prefix longer than the largest bucket through the scratch pool in
chunks, grouped admission, both chunk widths, the counters). The AOT compile
for the TPU at the published widths is ``tests/test_kernels_compile_tpu.py``'s.
"""

import dataclasses
import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import dots3_stack, parse_stack
from benchmark.reference import dots3_decoder as ref
from tpu_voice_agent.models import dots3, llama, mla
from tpu_voice_agent.models.family import family
from tpu_voice_agent.models.llama import forward_paged, init_params, quantize_params
from tpu_voice_agent.ops import sparse_latent as sl

F32 = jnp.float32
CONF = json.loads((Path(__file__).parents[1] / "benchmark/configs/dots3-note-prev-int8.json").read_text())
# the file's rehearsal widths (F S F S S: two leading dense layers of different
# kinds, three routed ones; 4 full heads, 2 sliding ones; ranks 48 / 40), with
# an ``index_topk`` and a window small enough to BIND inside 50 tokens
MODEL, SERVING = parse_stack.as_run(CONF, True)
MODEL = {**MODEL, "index_topk": 16, "sliding_window_size": 9}
CFG = dataclasses.replace(dots3_stack.llama_config(MODEL, {**SERVING, "site_context_tokens": 0}),
                          max_seq_len=256)
BS, N = 8, 12
TABLE = jnp.asarray([[1, 2, 3, 4, 5, 6, 7]], jnp.int32)
TOKS = jax.random.randint(jax.random.key(1), (1, 50), 0, CFG.vocab_size)
SAMPLE = {"tokens": [int(t) for t in TOKS[0]], "rows": 50}


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float((np.abs(got - want).max(-1) / np.abs(want).max(-1)).max())


def pools(cfg, dtype, n=N, bs=BS):
    planes = dots3.cache_spec(cfg)["planes"]
    return tuple({name: jnp.zeros((L, n, bs, w), dtype) for name, (L, w) in planes[p].items()}
                 for p in ("k", "v"))


def through_the_pool(params, cfg, impl, dtype, steps=(37, 1, 1, 1, 9, 1), toks=TOKS, **kw):
    """50 tokens as the engine feeds them: a prefill of 37, three T = 1 steps,
    one 1 + 8 block, one more step — through the paged planes of both kinds.
    -> (50, V) logits."""
    kp, vp = pools(cfg, dtype)
    rows, pos = [], 0
    for T in steps:
        out = forward_paged(params, cfg, toks[:, pos:pos + T], (pos + jnp.arange(T))[None], kp, vp,
                            TABLE, attn_impl=impl, **kw)
        rows.append(np.asarray(out[0][0]))
        kp, vp, pos = out[1], out[2], pos + T
    return np.concatenate(rows)


def test_the_configuration_keeps_the_published_widths_and_names_its_cut():
    """The file's top level is the catalog's ``config`` but for depth, experts
    HELD and vocabulary rows; the program's configuration reads every size
    from it, and the pool answers by layer kind."""
    published = {"hidden_size": 5120, "intermediate_size": 13824, "moe_intermediate_size": 1536,
                 "num_attention_heads": 128, "kv_lora_rank": 512, "q_lora_rank": 1024,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
                 "index_n_heads": 64, "index_head_dim": 128, "index_topk": 2048,
                 "swa_num_attention_heads": 64, "swa_kv_lora_rank": 1024, "swa_q_lora_rank": 1024,
                 "swa_qk_nope_head_dim": 192, "swa_qk_rope_head_dim": 64, "swa_v_head_dim": 128,
                 "swa_rope_theta": 50000, "rope_theta": 80000000, "sliding_window_size": 513,
                 "num_experts_per_tok": 8, "n_shared_experts": 1, "first_k_dense_replace": 1,
                 "routed_scaling_factor": 1, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
                 "norm_topk_prob": True, "apply_mla_qkv_lora_rescale": True,
                 "attention_gate_type": "headwise", "max_position_embeddings": 524288}
    assert {k: CONF[k] for k in published} == published
    assert [(CONF[k], CONF[k + "_published"]) for k in ("num_hidden_layers", "n_routed_experts", "vocab_size")] \
        == [(9, 46), (32, 256), (19008, 152064)]
    assert sorted(CONF["reduced_why"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert CONF["vocab_size"] * CONF["chips_sharing_a_layer"] == CONF["vocab_size_published"]
    assert CONF["n_routed_experts"] * CONF["chips_sharing_a_layer"] == CONF["n_routed_experts_published"]
    # the letters builder and reference read are the published list's first nine
    assert len(CONF["layer_types"]) == 46 and CONF["layer_types"].count("full_attention") == 13
    assert CONF["layer_kinds"] == "".join(t[0].upper() for t in CONF["layer_types"][:9]) == "FFSSSFSSS"
    for part in ("vision", "audio", "multi-token"):
        assert part in CONF["left_out"]
    s = CONF["serving"]
    assert s["max_len"] == 8192 + 128 + 512 and s["pool_blocks"] == 64 + 32 * 6 + 8
    assert 879 + s["site_context_tokens"] == 8192 == 64 * s["block_size"]
    m, s = parse_stack.as_run(CONF, False)
    full = dots3_stack.llama_config(m, {**s, "site_context_tokens": 0})
    assert (full.n_heads, full.kv_lora_rank, full.q_lora_rank, full.qk_nope_dim, full.qk_rope_dim,
            full.v_head_dim, full.index_n_heads, full.index_head_dim, full.index_topk) == \
        (128, 512, 1024, 128, 64, 128, 64, 128, 2048)
    assert (full.swa_n_heads, full.swa_kv_lora_rank, full.swa_q_lora_rank, full.swa_qk_nope_dim,
            full.swa_qk_rope_dim, full.swa_v_head_dim, full.swa_rope_theta, full.sliding_window) == \
        (64, 1024, 1024, 192, 64, 128, 50000.0, 513)
    assert (full.n_experts, full.n_held, full.first_expert, full.top_k, full.first_dense_layers,
            full.dense_ffn_dim, full.ffn_dim, full.vocab_size) == (256, 32, 0, 8, 1, 13824, 1536, 19008)
    assert full.attn_gate and full.lora_rescale and full.router_bias and full.shared_sum
    assert full.layer_types == ("full", "full") + ("sliding",) * 3 + ("full",) + ("sliding",) * 3
    fam = family(full)  # the record the serving side reads
    assert (fam.name, fam.module, fam.scratch_prefix, fam.prefix_whole_blocks) == ("sparse", dots3, True, True)
    spec = fam.cache
    # a full layer's latent and rotated key share ONE row of 512 + 64; no plane of full-layer r's beside it
    assert spec["planes"] == {"k": {"kv": (3, 576), "idx": (3, 128), "swa": (6, 1024)}, "v": {"swa": (6, 64)}}
    assert spec["by_name"] and not spec["state_column"] and spec["slot_planes"] == {"k": {}, "v": {}}
    assert (full.kv_lora_rank, full.qk_rope_dim, full.layer_types.count("full")) == (512, 64, 3)  # as published
    # 1408 B a token a full layer, 2176 B a sliding one (the file's deployment)
    assert fam.token_bytes == 3 * 1408 + 6 * 2176 == 17280
    assert dots3.layer_plan(full)[:3] == (("full", 0), ("full", 1), ("sliding", 0))
    assert fam.count("latent").metrics == tuple(f"attn.{n}" for n in mla.LATENT_STATS + dots3.SPARSE_STATS)
    assert [c.name for c in family(llama.PRESETS["test-tiny"]).counts] == ["attn", "kv"]
    # the rehearsal: every mechanism present, selection and window binding
    assert (CFG.layer_types, CFG.first_dense_layers) == (("full", "sliding", "full", "sliding", "sliding"), 2)
    assert (CFG.n_heads, CFG.swa_n_heads, CFG.kv_lora_rank, CFG.swa_kv_lora_rank) == (4, 2, 48, 40)
    assert (CFG.n_experts, CFG.n_held, CFG.first_expert, CFG.top_k) == (16, 4, 4, 3)
    reh = parse_stack.as_run(CONF, True)[0]
    assert reh["index_topk"] < 1024 and reh["sliding_window_size"] < 1024  # both bind behind the rehearsal's head


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prefill_then_decode_through_the_pool_is_the_reference_full_forward(impl):
    """Float32 weights and activations: a prefill of 37 (past ``index_topk``
    16 and the window of 9, so selection and window bind INSIDE it), T = 1
    steps and a 1 + 8 block through the planes of both kinds — absorbed
    attention over gathered keys everywhere — against the reference's ONE
    full forward, which decompresses keys and values a head and masks. Under
    "pallas" the indexer and the gathered kernel run (interpreted), and the
    grouped kernel the experts."""
    params = init_params(CFG, jax.random.key(0), F32)
    assert float(jnp.abs(params["layers"]["router_bias"]).min()) > 0
    cfg = dataclasses.replace(CFG, moe_impl="grouped" if impl == "pallas" else "dense")
    want = ref.logits(params, MODEL, SAMPLE)
    with jax.default_matmul_precision("highest"):
        assert rel(through_the_pool(params, cfg, impl, F32), want) < 1e-4


def test_a_ragged_block_of_two_rows_behind_chunks_is_the_reference():
    """Two rows on tables of their own: the head in two chunks of 16 (the
    second attends the first through the pool, selected and windowed), then a
    1 + 2 block where one row holds 3 real positions and the other 2
    (``n_real``: the real positions go first through the full layers'
    tiles) — each real position's logits are the reference's."""
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
        stats = dict(zip(mla.LATENT_STATS + dots3.SPARSE_STATS, np.asarray(out[-1]).tolist()))
    got = np.concatenate(got, axis=1)
    for b, real in ((0, 36), (1, 35)):
        want = ref.forward(params, [int(t) for t in toks[b]], MODEL, last=36)
        assert rel(got[b, :real], np.asarray(want)[:real]) < 1e-4
    # 5 real positions at 33, 34, 35 | 33, 34: two full layers see pos + 1 keys, attend 16
    assert stats["keys_visible"] == 2 * (34 + 35 + 36 + 34 + 35) and stats["keys_selected"] == 2 * 5 * 16
    assert stats["index_keys_scored"] == 2 * 6 * N * BS  # one tile of the block's 6 slots, the whole plane
    assert stats["window_keys_read"] > 0
    # one tile pass a full layer, and the rule walks 40 keys of table behind top-16 at 4 heads
    assert (stats["selected_tiles"], stats["selected_tiles_walked"]) == (2, 2)
    assert stats["selections_thresholded"] == 0  # the twins' ``chosen_mask`` sorts: no kernel ran


@functools.lru_cache(maxsize=None)
def _rows_behind_a_head(B: int):
    """B rows on tables of their own behind 20 cached positions each
    (selection and window bind), the pool they left, float32."""
    params = init_params(CFG, jax.random.key(0), F32)
    toks = jax.random.randint(jax.random.key(5), (B, 29), 0, CFG.vocab_size)
    tables = jnp.arange(1, 4 * B + 1, dtype=jnp.int32).reshape(B, 4)
    kp, vp = pools(CFG, F32, n=4 * B + 2)
    with jax.default_matmul_precision("highest"):
        out = forward_paged(params, CFG, toks[:, :20], jnp.tile(jnp.arange(20)[None], (B, 1)), kp, vp,
                            tables, attn_impl="xla")
    return params, toks, tables, out[1], out[2]


# (n_real a row, the packed tile, a parked row or None): the tiles the real positions take. Four rows'
# 36 positions go through the full layers' attention in tiles of 12, eight rows' 72 in tiles of 16
# (``_position_tile``): in the ``filler`` cases a packed tile holds slots past the last real position
# that NO attention tile filled — they are the last real position again, and write its cache index
PACKED = {
    "one_tile": ([2, 1, 3, 2], 12, None),
    "several_tiles": ([9, 5, 7, 4], 12, None),
    "a_row_with_none": ([3, 0, 2, 1], 12, None),
    "a_parked_row": ([3, 2, 2, 1], 12, 1),
    "every_position_real": ([9, 9, 9, 9], 12, None),
    "a_last_tile_that_overlaps": ([9, 9, 9, 9], 16, None),
    "filler_slots_no_attention_tile_fills": ([2, 1, 0, 3, 1, 2, 0, 1], 32, None),
    "filler_slots_in_the_last_of_two_tiles": ([5, 4, 6, 3, 5, 4, 6, 4], 32, None),
    "filler_slots_a_parked_row_and_an_overlap": ([3, 2, 9, 1, 4, 0, 2, 3], 40, 2),
    "filler_slots_in_a_block_of_four_rows": ([2, 1, 3, 2], 30, None),
}


@pytest.mark.parametrize("one_head", [False, True], ids=["every_position", "one_head"])
@pytest.mark.parametrize("case", sorted(PACKED))
def test_the_packed_walk_is_the_whole_block(case, one_head):
    """A 1 + 8 block of four or eight rows as the chunk loop builds it (a
    padded position is a copy of its row's last real one), float32: with
    ``ffn_pack`` under the block's positions everything position-wise runs on
    the real positions in tiles of packed rows — the logits of every real
    position, BOTH pools' planes and the index keys of EVERY layer at every
    index a real position writes are the whole block's, nothing else in the
    pool moves, and the counts keep their meaning (``FFN_STATS``: one tile or
    not, rows computed)."""
    n, tile, parked = PACKED[case]
    B = len(n)
    params, toks, tables, kp, vp = _rows_behind_a_head(B)
    trash = 4 * B + 1
    n = jnp.asarray(n, jnp.int32)
    t = jnp.minimum(jnp.arange(9)[None], jnp.maximum(n[:, None] - 1, 0))  # (B, 9)
    kw = {"n_real": n, "attn_impl": "xla", "moe_stats": True, "attn_stats": True, "latent_stats": True}
    if parked is not None:
        kw.update(write_mask=jnp.arange(B) != parked, trash_idx=jnp.full((B,), trash * BS, jnp.int32))
    if one_head:
        kw["logit_pos"] = jnp.maximum(n - 1, 0)
    block = lambda: (jnp.take_along_axis(toks[:, 20:], t, axis=1), 20 + t,  # the pools are donated
                     *jax.tree.map(jnp.copy, (kp, vp)), tables)
    with jax.default_matmul_precision("highest"):
        whole = forward_paged(params, CFG, *block(), **kw)
        packed = forward_paged(params, CFG, *block(), ffn_pack=tile, **kw)
    assert len(packed) == len(whole) + 1
    live = np.asarray(n) * (np.arange(B) != parked)
    n_pos = int(live.sum())
    n_tiles = max(-(-n_pos // tile), 1)
    assert np.asarray(packed[-1]).tolist() == [int(n_tiles == 1), n_tiles * tile]
    # the routed layers' assignments, summed over the tiles; the attention's and the selection's counts
    assert int(packed[5][0]) == 3 * n_tiles * tile * CFG.top_k and int(whole[5][0]) == 3 * B * 9 * CFG.top_k
    for a, b in zip(packed[6:8], whole[6:8]):
        assert np.asarray(a).tolist() == np.asarray(b).tolist()
    for b in range(B):
        if live[b]:
            rows = slice(0, 1) if one_head else slice(0, int(live[b]))
            assert rel(packed[0][b, rows], whole[0][b, rows]) < 1e-4, b
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
        assert np.abs(want[:, written] - before[:, written]).max(axis=-1).min() > 0  # they WERE written
        still = ~written
        still[trash] = False  # the trash block: a parked row's writes
        assert np.array_equal(got[:, still], before[:, still])


FLIPS = {
    "no_rescale": ({"rescale": False}, {}),
    "no_gate": ({"gated": False}, {}),
    "a_window_of_8": ({}, {"sliding_window_size": 8}),
    "a_window_of_10": ({}, {"sliding_window_size": 10}),
    "fifteen_keys": ({}, {"index_topk": 15}),
    "every_key": ({}, {"index_topk": 64}),
    "sixteen_index_heads_of_8": ({}, {"index_n_heads": 8, "index_head_dim": 16}),
    "the_sliding_theta_everywhere": ({}, {"rope_theta": 50000.0}),
    "one_theta_for_both": ({}, {"swa_rope_theta": 8e7}),
    "latent_norm_eps": ({}, {"latent_norm_eps": 1e-2}),
    "top_2_experts": ({}, {"num_experts_per_tok": 2}),
    "held_from_expert_0": ({}, {"first_expert": 0}),
    "scaled_gates": ({}, {"routed_scaling_factor": 2.5}),
    "layer_kinds_in_another_order": ({}, {"layer_kinds": "FSSFS"}),
}


@pytest.fixture(scope="module")
def served_f32():
    params = init_params(CFG, jax.random.key(0), F32)
    with jax.default_matmul_precision("highest"):
        return params, through_the_pool(params, CFG, "xla", F32)


@pytest.mark.parametrize("flip", sorted(FLIPS))
def test_each_assumed_reading_flipped_is_another_model(served_f32, flip):
    """The reference under ONE other reading of the block — no rank rescale,
    no gate, a window one wider or narrower, another ``index_topk``, the
    indexer's heads cut otherwise, one theta for both kinds, another latent
    eps, another expert rule, another share — is no longer what is served."""
    params, got = served_f32
    departures, keys = FLIPS[flip]
    if flip == "sixteen_index_heads_of_8":  # the same W_qI read as other heads
        keys = {"index_n_heads": MODEL["index_n_heads"] * 2, "index_head_dim": MODEL["index_head_dim"] // 2}
        with pytest.raises(Exception):  # one key of 32 values a token: 16-wide heads cannot read it
            ref.forward(params, SAMPLE["tokens"], {**MODEL, **keys}, last=50)
        return
    want = ref.forward(params, SAMPLE["tokens"], {**MODEL, **keys}, last=50, **departures)
    assert rel(got, want) > 1e-3, flip


def test_the_bias_selects_and_the_index_key_is_layer_normed(served_f32):
    params, got = served_f32
    zero = {**params, "layers": {**params["layers"],
                                 "router_bias": jnp.zeros_like(params["layers"]["router_bias"])}}
    assert rel(got, ref.logits(zero, MODEL, SAMPLE)) > 1e-3
    # the index key's LayerNorm gain: scaled, the chosen sets stay (a score is
    # linear in kI up to the relu) — but shifted keys choose otherwise
    full = params["attn_full"]
    moved = {**params, "attn_full": {**full, "w_ik": full["w_ik"][:, :, ::-1]}}
    assert rel(got, ref.logits(moved, MODEL, SAMPLE)) > 1e-3


FETCHES = ("walked", "gathered")


@pytest.fixture
def fetch(request, monkeypatch):
    """The rule bound to one of its answers: a selected layer's keys WALKED under the mask, or
    GATHERED — for a forward traced anew under it (a jit of the test's own: the module's hands
    back what it traced first)."""
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
    of 50 (selection and window binding) under either answer of the rule, kernels
    and twins, is the forward the module serves (whichever the rule picked for
    it), and counts its tile passes as walked or not."""
    params, sound = served_f32
    with jax.default_matmul_precision("highest"):
        out = _prefill(params, CFG, impl, latent_stats=True)
    assert rel(out[0][0], sound) < 1e-4
    stats = dict(zip(mla.LATENT_STATS + dots3.SPARSE_STATS, np.asarray(out[-1]).tolist()))
    tiles = 2 * 5  # two full layers, 50 positions in tiles of 10
    assert (stats["selected_tiles"], stats["selected_tiles_walked"]) == (tiles, tiles * (fetch == "walked"))
    # (ISSUE 63) the threshold select makes a walked tile's set where the kernels are on, ``lax.top_k`` elsewhere
    assert stats["selections_thresholded"] == tiles * (fetch == "walked" and impl == "pallas")
    assert stats["keys_selected"] == 2 * sum(min(t + 1, 16) for t in range(50))


@pytest.mark.parametrize("fetch", FETCHES, indirect=True)
@pytest.mark.parametrize("fault", dots3.FAULTS)
def test_each_planted_fault_moves_the_served_logits(served_f32, fault, fetch):
    """``dots3.forward_paged(fault=...)``: what the comparison's limit is set
    against on the chip — planted in the SERVED program, each departs from
    the sound one at float32 by far more than rounding, whichever way the
    selected keys are fetched."""
    params, sound = served_f32
    prefill = lambda fault: _prefill(params, CFG, fault=fault)
    with jax.default_matmul_precision("highest"):
        out = prefill(fault)
        if fault == dots3.FAULTS[0]:
            assert rel(prefill(None)[0][0], sound) < 1e-4  # one prefill of 50 is the six steps
    assert rel(out[0][0], sound) > 1e-3
    with pytest.raises(ValueError, match="one of"):
        prefill("none")


def test_the_indexer_kernel_matches_its_twin_and_scores_by_hand():
    """Interpret mode against the jnp twin, and one score by hand: sum over
    index heads of w relu(q . k)."""
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (11, 4, 32), F32)  # 11 positions: padded to 16 inside
    w = jax.random.normal(ks[1], (11, 4), F32)
    plane = jax.random.normal(ks[2], (3, 12, 8, 32), F32)
    with jax.default_matmul_precision("highest"):
        got = sl.indexer_scores(q, w, plane, jnp.int32(2))
        want = sl.indexer_scores_reference(q, w, plane, 2)
    assert got.shape == (11, 96) and rel(got, want) < 1e-5
    k = np.asarray(plane[2]).reshape(96, 32)
    by_hand = sum(float(w[5, j]) * max(float(np.asarray(q[5, j]) @ k[70]), 0.0) for j in range(4))
    assert abs(float(got[5, 70]) - by_hand) < 1e-4


def _gathered_case(Q: int, K: int, reach: int, seed: int):
    """G = 3 groups of Q query rows over K keys (C = 48, R = 16): each query row
    its own [lo, hi] (``reach`` wide), keys out of order, the last three at -1
    (padding: never seen, lo >= 0). -> (q_c, q_r, c, r, kpos, lo, hi)."""
    G, C, R = 3, 48, 16
    ks = jax.random.split(jax.random.key(seed), 5)
    q_c, q_r = jax.random.normal(ks[0], (G, Q, C), F32), jax.random.normal(ks[1], (G, Q, R), F32)
    c, r = jax.random.normal(ks[2], (G, K, C), F32), jax.random.normal(ks[3], (G, K, R), F32)
    kpos = jnp.stack([jax.random.permutation(k_, K) for k_ in jax.random.split(ks[4], G)]).astype(jnp.int32)
    kpos = kpos.at[:, -3:].set(-1)
    hi = jnp.tile(jnp.arange(Q, dtype=jnp.int32)[None] + reach - 1, (G, 1))
    return q_c, q_r, c, r, kpos, jnp.maximum(hi - reach, 0), hi


@pytest.mark.parametrize("Q,K", [(8, 24), (18, 40)])
def test_the_gathered_kernel_matches_its_twin_with_bounds_a_query_and_padding_keys(Q, K):
    """Each query row its own [lo, hi]; keys out of order, some at -1 (padding:
    never seen, lo >= 0); Q and K no multiple of the kernel's tiles."""
    q_c, q_r, c, r, kpos, lo, hi = case = _gathered_case(Q, K, 6, seed=8)
    with jax.default_matmul_precision("highest"):
        got = sl.window_latent_attention(*case, scale=0.125)
        want = sl.gathered_latent_attention_reference(*case, scale=0.125)
    assert got.shape == (3, Q, 48) and rel(got, want) < 1e-4
    # by hand, one query: softmax over the keys inside its bounds alone
    g, qi = 1, 4
    seen = (np.asarray(kpos[g]) >= int(lo[g, qi])) & (np.asarray(kpos[g]) <= int(hi[g, qi]))
    s = (np.asarray(q_c[g, qi]) @ np.asarray(c[g]).T + np.asarray(q_r[g, qi]) @ np.asarray(r[g]).T) * 0.125
    p = np.where(seen, np.exp(s - s[seen].max()), 0.0)
    np.testing.assert_allclose(got[g, qi], (p / p.sum()) @ np.asarray(c[g]), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("Q,K", [(8, 24), (18, 40), (4, 130)])
def test_the_gathered_kernel_on_one_key_tile_matches_its_twin_on_the_split_rows(Q, K):
    """A full layer's form of the same body: the keys as ONE tile of rows [c |
    r] as the plane holds them, against the twin on the split (c, r) — the
    cases above again, and K one past a lane tile. A tile of any other width —
    filler columns, a column short — is refused: the plane holds none, so
    nothing is ever read against a query value it was not written for."""
    q_c, q_r, c, r, kpos, lo, hi = case = _gathered_case(Q, K, K // 3 + 1, seed=9)
    rows = sl.key_row(c, r)
    assert rows.shape == (3, K, 48 + 16)
    assert np.array_equal(rows[..., :48], c) and np.array_equal(rows[..., 48:], r)
    with jax.default_matmul_precision("highest"):
        want = sl.gathered_latent_attention_reference(*case, scale=0.125)
        got = sl.sparse_latent_attention(q_c, q_r, rows, kpos, lo, hi, scale=0.125)
        assert rel(sl.sparse_latent_attention_reference(q_c, q_r, rows, kpos, lo, hi, scale=0.125), want) < 1e-6
        # the two names' one body: the window's entry on the same keys handed as (c, r)
        assert rel(got, sl.window_latent_attention(*case, scale=0.125)) < 1e-5
    assert got.shape == (3, Q, 48) and rel(got, want) < 1e-4
    for other in (rows[..., :-1], jnp.pad(rows, ((0, 0), (0, 0), (0, 8)))):
        with pytest.raises(ValueError, match="no rows"):
            sl.sparse_latent_attention(q_c, q_r, other, kpos, lo, hi, scale=0.125)


def _walked_case(H: int, nb: int, common: int, seed: int, filler: int = 0, tile: int = 4, K: int = 12):
    """A tile of ``tile`` slots (C = 48, R = 16, blocks of 8) whose tables hold the
    same ``common`` leading blocks and blocks of their own behind them; slot 0 sees
    FEWER keys than K, the others stand anywhere behind the common blocks; the
    last ``filler`` slots repeat the last real one (query, table, position), as
    ``llama.ffn_pack_index`` fills a tile. -> (q_c, q_r, plane stack, layer,
    tables, positions, the scores the selection is made from)."""
    C, R, bs, n = 48, 16, 8, 60
    ks = jax.random.split(jax.random.key(seed), 5)
    q_c, q_r = jax.random.normal(ks[0], (tile, H, C), F32), jax.random.normal(ks[1], (tile, H, R), F32)
    plane = jax.random.normal(ks[2], (3, n, bs, C + R), F32)
    tables = np.zeros((tile, nb), np.int32)
    tables[:, :common] = 7 + 2 * np.arange(common)[None, :]  # no run of ids: a walk reads them by name
    tables[:, common:] = (30 + np.arange(tile * (nb - common)).reshape(tile, nb - common)) % n
    pos = np.array(jax.random.randint(ks[3], (tile,), min(common * bs, nb * bs - 1), nb * bs), np.int32)
    pos[0] = K - 5
    real = tile - filler
    take = np.minimum(np.arange(tile), real - 1)
    q_c, q_r, tables, pos = q_c[take], q_r[take], jnp.asarray(tables[take]), jnp.asarray(pos[take])
    mine = jax.random.normal(ks[4], (tile, nb * bs), F32)[take]
    mine = jnp.where(jnp.arange(nb * bs)[None, :] <= pos[:, None], mine, -jnp.inf)
    return q_c, q_r, plane, jnp.int32(1), tables, pos, mine


# (heads, table columns, columns every slot holds): none, some, all in common; a common
# run that fills no whole item (``_WALK_COLS`` = 4: 4 + 2); heads past a sublane tile
WALKED = [(4, 5, 0), (4, 5, 3), (4, 5, 5), (6, 9, 6), (20, 3, 2)]


@pytest.mark.parametrize("filler", [0, 2])
@pytest.mark.parametrize("H,nb,common", WALKED)
def test_the_walked_kernel_matches_its_twin_and_the_gathered_one(H, nb, common, filler):
    """ISSUE 62: a tile's blocks walked straight out of the pool under the
    selection as a mask — interpret mode against the jnp twin, against the
    GATHERED kernel over ``lax.top_k``'s rows (the same softmax over the same
    set, fetched the other way) and one (slot, head) by hand; a slot that sees
    fewer keys than K; filler slots return what the slot they repeat returns."""
    K, bs = 12, 8
    q_c, q_r, plane, li, tables, pos, mine = _walked_case(H, nb, common, seed=11 + common, filler=filler)
    seq = jnp.arange(nb * bs)[None, :]
    chosen = sl.chosen_mask(mine, K) & (seq <= pos[:, None])
    assert int(chosen[0].sum()) == K - 4 and all(int(n) == K for n in chosen[1:].sum(axis=1))
    split = sl.walk_split(tables, pos, 4, bs)
    with jax.default_matmul_precision("highest"):
        got = sl.walked_latent_attention(q_c, q_r, plane, li, chosen, tables,
                                         jax.tree.map(lambda a: a[0], split), scale=0.125)
        want = sl.walked_latent_attention_reference(q_c, q_r, plane, li, chosen, tables, scale=0.125)
        _, sel = jax.lax.top_k(mine, K)
        rows = plane[1, jnp.take_along_axis(tables, sel // bs, axis=1), sel % bs]
        gathered = sl.sparse_latent_attention(q_c, q_r, rows, sel, jnp.zeros((4, H), jnp.int32),
                                              jnp.broadcast_to(pos[:, None], (4, H)), scale=0.125)
    assert got.shape == (4, H, 48) and rel(got, want) < 1e-4 and rel(got, gathered) < 1e-4
    p, h = 2, H - 1
    keys = np.asarray(plane[1][tables[p]]).reshape(nb * bs, 64)
    s = np.concatenate([np.asarray(q_c[p, h]), np.asarray(q_r[p, h])]) @ keys.T * 0.125
    w = np.where(np.asarray(chosen[p]), np.exp(s - s[np.asarray(chosen[p])].max()), 0.0)
    np.testing.assert_allclose(got[p, h], (w / w.sum()) @ keys[:, :48], rtol=2e-4, atol=2e-5)
    if filler:
        assert np.array_equal(got[-1], got[4 - filler - 1]) and np.array_equal(got[-2], got[4 - filler - 1])
    with pytest.raises(ValueError, match="no rows"):
        sl.walked_latent_attention(q_c, q_r, plane[..., :-1], li, chosen, tables,
                                   jax.tree.map(lambda a: a[0], split), scale=0.125)


@pytest.mark.parametrize("H,nb,common", WALKED)
def test_a_tile_s_items_read_every_block_a_slot_sees_once(H, nb, common):
    """``walk_split``: a (slot, column) a REAL slot's position reaches is read by
    exactly ONE item — a common item's where every slot that sees the column
    names the same block there, else an own item of that slot — under the block
    id the slot's table names; tiles are split apart (two here: the second one's
    slots all see little); a slot that is not real (the second tile's last: an
    idle row's table of zeros, a position past everything) has no item and parts
    no column the others hold in common."""
    bs, tile, NK = 8, 4, sl._WALK_COLS
    _, _, _, _, tables, pos, _ = _walked_case(H, nb, common, seed=5)
    tables = jnp.concatenate([tables, tables.at[-1].set(0)])
    pos = jnp.concatenate([pos, jnp.minimum(pos, 9).at[-1].set(nb * bs - 1)])
    real = jnp.arange(2 * tile) < 2 * tile - 1
    split = sl.walk_split(tables, pos, tile, bs, real)
    for t in range(2):
        at = slice(t * tile, (t + 1) * tile)
        tb, ps, live = np.asarray(tables[at]), np.asarray(pos[at]), np.asarray(real[at])
        groups, n_c, keys = int(split.n_common[t]), int(split.n_columns[t]), np.asarray(split.keys[t])
        read = np.zeros((tile, nb), int)
        for w in range(int(split.n_items[t])):
            held = min(n_c - w * NK, NK) if w < groups else 1
            for j in range(NK):
                slot, col = (int(v) for v in sl.walk_entry(w, j, groups, n_c, keys, tile, nb))
                assert col * bs <= ps[slot] and live[slot]  # the slot whose table names the block sees it
                if j < held:
                    read[np.flatnonzero(live) if w < groups else [slot], col] += 1
                else:  # a column the item does not hold repeats one it does: the same block again, masked
                    assert (slot, col) == tuple(int(v) for v in sl.walk_entry(w, held - 1, groups, n_c, keys, tile, nb))
        sees = (np.arange(nb)[None, :] * bs <= ps[:, None]) & live[:, None]
        same = np.array([len({tb[p, c] for p in range(tile) if sees[p, c]}) <= 1 for c in range(nb)])
        # (a common column is read for the real slots that do not reach it too: their mask is empty there)
        assert np.array_equal(read, np.where(same[None, :] & sees.any(axis=0)[None, :], live[:, None], sees))
        assert groups == -(-int((same & sees.any(axis=0)).sum()) // NK)
        if common >= 2 and t == 1:  # two columns, in common again
            assert int(split.n_items[t]) == groups == 1


_ROUNDED = lambda k, shape=(6, 40): jnp.round(jax.random.normal(k, shape, F32))
_ENDS = lambda ends, n: jnp.arange(n)[None, :] <= jnp.asarray(ends)[:, None]
# name -> (scores of a key, k)
TOP_K_ROWS = {
    "distinct": (lambda k: jax.random.normal(k, (6, 40), F32), 12),
    "ties_at_the_kth": (lambda k: jnp.round(jax.random.normal(k, (6, 40), F32) * 1.5) / 2, 12),
    "all_equal": (lambda k: jnp.zeros((6, 40), F32), 12),
    "fewer_finite_than_k": (lambda k: jnp.where(_ENDS([0, 3, 10, 11, 12, 39], 40), _ROUNDED(k), -jnp.inf), 12),
    # ISSUE 63: what the threshold select must take as ``top_k`` does
    "k_is_every_key": (_ROUNDED, 40),
    "k_is_one": (_ROUNDED, 1),
    "nans_of_both_signs": (lambda k: _ROUNDED(k).at[jnp.arange(6), jnp.arange(6) * 3].set(jnp.nan)
                           .at[2, 7::4].set(-jnp.nan).at[4, :30].set(jnp.nan), 12),
    "a_row_all_minus_inf": (lambda k: _ROUNDED(k).at[1].set(-jnp.inf).at[4].set(-jnp.inf), 12),
    "the_cells_8832_keys": (lambda k: jnp.where(_ENDS([5, 2047, 2048, 8300, 8831, 4000], 8832),
                                                 _ROUNDED(k, (6, 8832)) / 4, -jnp.inf), 2048),
}


@pytest.mark.parametrize("rows", sorted(TOP_K_ROWS))
def test_the_membership_mask_is_top_k_s_index_set_ties_included(rows):
    """``top_k_members`` (compares alone) and ``threshold_members`` (ISSUE 63: no sorted row — the
    kernel in interpret mode, and its steps as XLA alone): EXACTLY the set ``lax.top_k``
    returns — where values tie at the k-th (it takes the lower indices; 0.0 before
    -0.0, which is no tie to it), where a whole row ties, where fewer than k are finite (it takes -inf keys, lowest
    index first: the caller's ``s <= position`` cuts them again), where k is every key or one, where
    a row holds NaNs (above +inf, or below -inf by their sign) and at a width that is no power of two."""
    make, k = TOP_K_ROWS[rows]
    mine = make(jax.random.key(3))
    vals, sel = jax.lax.top_k(mine, k)
    if rows in ("ties_at_the_kth", "all_equal", "fewer_finite_than_k", "the_cells_8832_keys"):
        # the k-th value IS tied with one left out, in some row
        assert bool(((mine == vals[:, -1:]).sum(axis=1) > (vals == vals[:, -1:]).sum(axis=1)).any())
    if rows == "ties_at_the_kth":
        assert bool(jnp.signbit(jnp.where(mine == 0, mine, 1.0)).any())  # zeros of both signs among them
    want = np.zeros(mine.shape, bool)
    np.put_along_axis(want, np.asarray(sel), True, axis=1)
    assert np.array_equal(sl.top_k_members(mine, vals, sel), want)
    assert np.array_equal(sl.chosen_mask(mine, k), want)
    assert np.array_equal(sl.threshold_members(mine, k), want)
    if k < mine.shape[1]:
        steps = jax.jit(lambda m: sl._select_members(lambda: sl._total_order(m), lambda v: lambda: v, k, unroll=True))
        assert np.array_equal(np.asarray(steps(mine)) != 0, want)


def test_the_rule_reads_shapes_alone_and_walks_a_few_times_index_topk():
    """``walks(keys, topk, heads)``: the cells' 8832 positions of table behind
    top-2048 walk at 64 heads, a 128 k context gathers (60 x the FLOPs) at either
    head count — and nothing but its three arguments and the readings beside it
    decides: no flag, no environment variable, no name."""
    import inspect

    assert sl.walks(8832, 2048, 64) and not sl.walks(131072, 2048, 64) and not sl.walks(131072, 2048, 128)
    assert sl.walks(2048, 2048, 128) and sl.walks(56, 16, 4)  # nothing to leave out: every key is chosen
    assert list(inspect.signature(sl.walks).parameters) == ["keys", "topk", "heads"]
    assert set(sl.walks.__code__.co_names) <= {"_WALKED_KEY_HEAD_NS", "_GATHER_ROW_NS", "_GATHERED_KEY_HEAD_NS"}
    # the cost of the walk grows with the table, the gather's does not
    flips = [k for k in range(2048, 200000, 128) if sl.walks(k, 2048, 64) != sl.walks(k + 128, 2048, 64)]
    assert len(flips) == 1


def test_the_check_tool_holds_the_two_fetches_together_at_toy_shapes_on_the_cpu(tmp_path):
    """``tools/selected_attn_check.py`` (the stop rule's instrument, the source of
    the rule's constants) in interpret mode: gather + gathered kernel against the
    walk and its twin, the members' mask against ``top_k``'s scatter — exit code
    0, a line a head count, the rule's verdict, and NO time from a CPU."""
    import os
    import subprocess
    import sys

    root = Path(__file__).parents[1]
    done = subprocess.run([sys.executable, str(root / "tools/selected_attn_check.py"), "--heads", "4", "8",
                           "--keys", "640", "--common", "3", "--topk", "200", "--tile", "4"],
                          capture_output=True, text=True, cwd=tmp_path, timeout=600,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(ln) for ln in done.stdout.splitlines() if ln.startswith("{")]
    assert [ln["heads"] for ln in lines] == [4, 8]
    for ln in lines:
        assert ln["members_are_top_k_s"] and ln["rule_walks"] and ln["largest_difference"] < 2e-2
        assert (ln["keys"], ln["topk"], ln["common_items"]) == (640, 200, 1)
        assert not [k for k in ln if k.endswith("_us") or k == "readings_ns"]
    assert (tmp_path / "chiprun_out/selected_attn_check.jsonl").read_text().count("\n") == 2


def test_the_check_tool_s_select_reading_holds_each_form_to_top_k_s_set_on_the_cpu(tmp_path):
    """``tools/selected_attn_check.py --select`` (ISSUE 63's stop rule) in interpret mode: ``lax.top_k`` +
    ``top_k_members``, the threshold select as XLA alone and as the kernel — each mask a scatter of
    ``top_k``'s indices on seeded planes and the planted rows, exit code 0, ONE line, no time from a CPU."""
    import os
    import subprocess
    import sys

    tool = Path(__file__).parents[1] / "tools/selected_attn_check.py"
    done = subprocess.run([sys.executable, str(tool), "--select", "--keys", "640", "--topk", "200", "--tile", "4",
                           "--passes", "2"], capture_output=True, text=True, cwd=tmp_path, timeout=600,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    line, = [json.loads(ln) for ln in done.stdout.splitlines() if ln.startswith("{")]
    assert line["select"] and (line["tile"], line["keys"], line["topk"]) == (4, 640, 200)
    assert all(line[f"{form}_is_top_k_s"] for form in ("top_k", "select_xla", "select_kernel"))
    assert not [k for k in line if k.endswith("_us") or "_over_" in k]
    assert (tmp_path / "chiprun_out/selected_attn_check.jsonl").read_text().count("\n") == 1


def test_a_full_layer_s_row_in_the_pool_is_the_latent_beside_its_rotated_key():
    """Float32, a prefill of 37 then a T = 1 step and a 1 + 8 block: at every
    position the full layers' plane ``kv`` reads ``latent_qkv``'s c in its first
    C columns and its r in the next dr — nothing behind —, the sliding layers'
    two planes c and r apart; no plane holds a rotated key of a full layer
    anywhere else."""
    params = init_params(CFG, jax.random.key(0), F32)
    kd = dots3.kinds(CFG)
    seen = {"full": [], "sliding": []}
    front = dots3.latent_qkv

    def recorded(p, x, cfg, k, cos, sin, hold=False):
        out = front(p, x, cfg, k, cos, sin, hold)
        seen["full" if k.indexed else "sliding"].append((np.asarray(out[2][0]), np.asarray(out[3][0])))
        return out

    kp, vp = pools(CFG, F32)
    assert set(kp) == {"kv", "idx", "swa"} and set(vp) == {"swa"}
    pos = 0
    try:
        dots3.latent_qkv = recorded
        with jax.default_matmul_precision("highest"):
            for T in (37, 1, 9):  # eagerly: a block-shaped layer calls the front half outside any loop
                out = dots3.forward_paged(params, CFG, TOKS[:, pos:pos + T], (pos + jnp.arange(T))[None], kp, vp,
                                          TABLE, attn_impl="xla")
                kp, vp, pos = out[1], out[2], pos + T
    finally:
        dots3.latent_qkv = front
    at = (np.asarray(TABLE[0])[np.arange(pos) // BS], np.arange(pos) % BS)
    for kind, n_layers in (("full", 2), ("sliding", 3)):
        k = kd[kind]
        calls = seen[kind]
        assert len(calls) == 3 * n_layers
        for layer in range(n_layers):
            c = np.concatenate([calls[step * n_layers + layer][0] for step in range(3)])
            r = np.concatenate([calls[step * n_layers + layer][1] for step in range(3)])
            assert c.shape == (pos, k.C) and r.shape == (pos, k.dr) and np.abs(r).min(axis=-1).max() > 0
            if kind == "full":
                row = np.asarray(kp["kv"][layer])[at]
                assert row.shape == (pos, k.C + k.dr)
                assert np.array_equal(row[:, :k.C], c) and np.array_equal(row[:, k.C:], r)
            else:
                assert np.array_equal(np.asarray(kp["swa"][layer])[at], c)
                assert np.array_equal(np.asarray(vp["swa"][layer])[at], r)


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """The model-configs guide's section 4: the routed parts the FOUR shares
    of the rehearsal's 16 experts give (4 held from ids 0, 4, 8, 12), with
    the shared expert and attention counted ONCE, are the uncut layer — in
    the reference and in the served ``_ffn``."""
    from benchmark.reference import decoder as dense_ref

    uncut_cfg = dataclasses.replace(CFG, experts_held=0, first_expert=0)
    up = init_params(uncut_cfg, jax.random.key(4), F32)
    layer = jax.tree.map(lambda a: a[0], up["layers"])
    u = jax.random.normal(jax.random.key(5), (1, 12, CFG.dim), F32)
    kw = dict(top_k=CFG.top_k, scale=1.0)
    with jax.default_matmul_precision("highest"):
        whole = ref.routed_part(u[0], layer, dense_ref.dense, first=0, **kw)
        served_whole, _ = llama._ffn(layer, u, uncut_cfg)
        parts, served_parts = [], []
        for first in (0, 4, 8, 12):
            share = {**layer, **{k: layer[k][first:first + 4] for k in ("moe_gate", "moe_up", "moe_down")}}
            parts.append(ref.routed_part(u[0], share, dense_ref.dense, first=first, **kw))
            cfg = dataclasses.replace(CFG, experts_held=4, first_expert=first)
            served_parts.append(llama._ffn(share, u, cfg)[0][0])
        shared = ref.shared_part(u[0], layer, dense_ref.dense, n_shared=1)
    assert rel(sum(parts), whole) < 1e-5
    assert float(jnp.abs(parts[1]).max()) > 1e-3  # a share is not nothing
    # each served share carries the shared expert: counted once, three of them come off
    assert rel(sum(served_parts) - 3 * shared, served_whole[0]) < 1e-4
    assert rel(served_whole[0], whole + shared) < 1e-4


def test_the_served_precision_reads_inside_the_limit_and_int4_outside():
    """int8 weights, bf16 activations and bf16 planes against the float32
    reference on the same weights, and the int4 control; the chip's limit at
    published widths is the reference module's own. With the selection VOID
    (``index_topk`` past the context; the window of 9 binds): sixteen keys of
    fifty chosen in bfloat16 are other keys than float32 chooses, and at
    these widths one key is a sixteenth of a softmax — the rehearsal's 256 of
    1060 and the cell's 2048 of 8.3 k are held by the comparison itself
    (``benchmark/tests/test_dots3_reference.py``, the chip)."""
    model = {**MODEL, "index_topk": 64}
    cfg = dataclasses.replace(CFG, index_topk=64)
    params = quantize_params(init_params(cfg, jax.random.key(0)))
    assert params["attn_full"]["w_kvb"]["q"].dtype == jnp.int8 and params["attn_swa"]["w_qb"]["q"].dtype == jnp.int8
    assert params["attn_full"]["ik_norm"].dtype == jnp.bfloat16
    assert params["layers"]["router_bias"].dtype == F32 and params["layers"]["router"].dtype == jnp.bfloat16
    want = np.asarray(ref.logits(params, model, SAMPLE))
    rows = lambda got: np.abs(np.asarray(got, np.float32) - want).max(-1) / np.abs(want).max(-1)
    served = rows(through_the_pool(params, cfg, "xla", jnp.bfloat16))
    control = rows(ref.logits(params, model, SAMPLE, control=True))
    # by the typical row: at these widths one of a token's three experts that
    # flips on a near tie (4 of 16 held) moves a row by a fifth of its range
    assert 1e-3 < np.median(served) < 0.03 and np.mean(served < 0.08) > 0.85
    assert np.median(control) > 0.08 > np.quantile(served, 0.85)


def test_the_pool_holds_planes_by_layer_kind_at_the_published_widths():
    """Three full layers of ONE row of 512 + 64 and a 128-wide index key, six
    sliding ones of 1024 + 64 in two planes: read from the pool's own shapes
    (no plane where the full layers' rotated keys lived), the engine's gauge
    and the byte plan."""
    from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
    from tpu_voice_agent.serve import PagedDecodeEngine, paged
    from tpu_voice_agent.utils import hbmledger, tracing

    m, s = parse_stack.as_run(CONF, False)
    full = dots3_stack.llama_config(m, {**s, "site_context_tokens": 0})
    eng = PagedDecodeEngine(cfg=full, tokenizer=default_tokenizer(), quant="int8", batch_slots=2,
                            block_size=128, pool_blocks=4, max_len=256, prefill_buckets=(128,),
                            init_weights=False)
    assert eng.sparse and eng.latent and not eng.hybrid
    assert {n: a.shape for n, a in eng.k_pool.items()} == {
        "kv": (3, 4, 128, 576), "idx": (3, 4, 128, 128), "swa": (6, 4, 128, 1024)}
    assert {n: a.shape for n, a in eng.v_pool.items()} == {"swa": (6, 4, 128, 64)}
    pool_bytes = sum(a.nbytes for pool in (eng.k_pool, eng.v_pool) for a in pool.values())
    assert pool_bytes == 4 * eng.kv_bytes_per_block == 4 * 128 * 17280
    assert hbmledger.engine_hbm_plan(eng)["kv_pool_bytes"] == pool_bytes
    fresh = tracing.Metrics()
    orig, tracing._GLOBAL_METRICS = tracing._GLOBAL_METRICS, fresh
    try:
        paged.record_pool_gauges(eng.allocator, engine=eng)
    finally:
        tracing._GLOBAL_METRICS = orig
    assert fresh.snapshot()["gauges"]["paged.kv_bytes_per_token"] == 17280


def test_the_configuration_refuses_what_only_this_forward_has_without_it():
    base = dict(kv_lora_rank=48, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=12, n_layers=2,
                n_heads=2, n_kv_heads=2, dim=32)
    with pytest.raises(NotImplementedError, match="compressed query"):
        llama.LlamaConfig(**base, q_lora_rank=16)
    with pytest.raises(NotImplementedError, match="compressed query"):
        llama.LlamaConfig(**base, attn_gate=True)
    with pytest.raises(NotImplementedError, match="layer kinds inside a latent model"):
        llama.LlamaConfig(**base, layer_types=("full", "sliding"), sliding_window=4)
    with pytest.raises(ValueError, match="over a latent cache"):
        llama.LlamaConfig(n_layers=2, index_topk=8, index_n_heads=2, index_head_dim=16)
    sparse = dict(base, layer_types=("full", "sliding"), sliding_window=4, index_topk=8,
                  index_n_heads=2, index_head_dim=16)
    with pytest.raises(NotImplementedError, match="q_lora_rank"):
        llama.LlamaConfig(**sparse)
    with pytest.raises(ValueError, match="swa_"):
        llama.LlamaConfig(**sparse, q_lora_rank=16)


REHEARSAL = parse_stack.as_run(CONF, True)


def _float32(tree):
    return jax.tree.map(lambda a: a.astype(F32) if a.dtype == jnp.bfloat16 else a, tree)


def _engine(float32=False, **kw):
    from tpu_voice_agent.serve import PagedDecodeEngine

    # the rehearsal's OWN selection and window (256 and 129: both bind behind
    # its head of 1024 tokens), buckets the head is LONGER than
    cfg = dataclasses.replace(dots3_stack.llama_config(*REHEARSAL), max_seq_len=1536)
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

    dots3_stack.llama_config(*REHEARSAL)
    assert P.site_context()
    yield [P.render_prompt(t, {}) for t in ("go back", "scroll down to the bottom of the page",
                                            "open the settings page", "search for red shoes")]
    P.set_site_context("")


def test_the_engine_serves_it_behind_the_batcher_at_both_chunk_widths(monkeypatch, prompts):
    """The normal path: the prompt head — LONGER than the largest bucket —
    prefilled in chunks through ONE scratch pool, whole blocks of it cached;
    admissions behind it; chunks at the compacted and the full width; the
    routed counters, the latent reads and the selection's counters published."""
    from tpu_voice_agent.serve import ContinuousBatcher
    from tpu_voice_agent.utils import tracing

    fresh = tracing.Metrics()
    monkeypatch.setattr(tracing, "_GLOBAL_METRICS", fresh)
    eng = _engine(kernels="pallas")
    assert eng.cfg.moe_impl == "grouped" and eng.compact_rows == 2 and eng.sparse
    P = eng.set_prompt_prefix(*prompts[:2])
    assert P == 1024 > eng.prefill_buckets[-1] and not eng._prefix_tail and len(eng._prefix_blocks[0]) == 8
    held = np.asarray(eng._prefix_blocks[0])  # every plane of both kinds holds the head
    assert all(float(jnp.abs(a[:, held]).min(axis=(0, 2, 3)).max()) > 0
               for pool in (eng.k_pool, eng.v_pool) for a in pool.values())
    chunks, decode_chunk = [], eng.decode_chunk
    monkeypatch.setattr(eng, "decode_chunk", lambda *a, **k: chunks.append(decode_chunk(*a, **k)) or chunks[-1])
    batcher = ContinuousBatcher(eng, chunk_steps=4, max_new_tokens=12)
    solo = batcher.generate_many(prompts[:1])
    many = batcher.generate_many(prompts)
    assert all(r.error is None for r in solo + many)
    assert {c.rows for c in chunks} == {2, 8}
    assert all(c.counts["moe"].shape == (len(llama.moe_stat_names(eng.cfg)),) and c.counts["latent"].shape == (9,) for c in chunks)
    assert many[0].token_ids == solo[0].token_ids  # the same plan at either width
    counters = fresh.snapshot()["counters"]
    assert counters["moe.assigned_rows"] > counters["moe.local_rows"] > 0
    visible, chosen = counters["attn.keys_visible"], counters["attn.keys_selected"]
    assert 0 < chosen < 0.3 * visible  # 256 of ~1050 keys a position
    assert counters["attn.index_keys_scored"] >= visible and counters["attn.window_keys_read"] > 0
    assert counters["attn.latent_query_rows"] > 0 and counters["attn.latent_keys_read"] > 0
    # what says the walk engaged: every tile pass took the path the rule picks at these shapes
    walked = sl.walks(eng.block_tables.shape[1] * eng.block_size, eng.cfg.index_topk, eng.cfg.n_heads)
    assert counters["attn.selected_tiles"] > 0
    assert counters["attn.selected_tiles_walked"] == walked * counters["attn.selected_tiles"]
    # ... and the threshold select with it (ISSUE 63): every tile pass of this model selects for itself
    assert counters["attn.selections_thresholded"] == counters["attn.selected_tiles_walked"]


def test_the_chunk_loop_gives_the_same_plans_walked_and_whole(monkeypatch, prompts):
    """Behind the pool the model's OWN prefill wrote (keys that weigh: the
    selection and the window bind), float32: four requests on eight slots
    with the block walked in tiles of 24 packed rows — a tile's slots behind
    the last real position are ones no attention tile (12 here) fills — end
    in the plans the whole block gives and leave its cache, and the counters
    say it walked."""
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
    # what the requests left in the cache, every layer's planes of both kinds and the index keys
    for got, want in zip(plans["walked"][2], plans["whole"][2]):
        assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()
    assert plans["whole"][1].get("ffn.forwards_packed", 0) == 0
    walked = plans["walked"][1]
    assert 0 < walked["ffn.forwards_packed"] <= walked["scheduler.forwards"]
    assert walked["ffn.rows"] < plans["whole"][1]["ffn.rows"]


def test_the_chunked_head_and_a_suffix_behind_it_are_the_reference(prompts):
    """What the cell's comparison holds at published widths, here in float32:
    the head through the scratch pool in four chunks of 256 (selection from
    position 256 on, the window from 129), a suffix admitted behind it — the
    reference's one full forward over the same tokens."""
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
    writes the planes of every kind and picks the logits the per-slot path does."""
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
        planes = [[np.asarray(a[:, b], np.float32) for pool in (eng.k_pool, eng.v_pool) for a in pool.values()]
                  for b in owned]
        return logits, planes, [len(i) for i in ids], eng

    one, planes_one, lens, eng = admitted(False)
    grp, planes_grp, _, _ = admitted(True)
    assert rel(grp, one) < 1e-4
    P = len(eng.prefix_ids)
    assert P == 1024
    for a, b, n in zip(planes_one, planes_grp, lens):  # the suffix's cache, position by position
        assert len(a) == 4  # kv (a full layer's rows [c | r]), idx, swa | swa
        for x, y in zip(a, b):
            assert float(np.abs(x[:, :n - P] - y[:, :n - P]).max()) < 1e-4


# this model's programs at the rehearsal widths and 32 slots as ISSUE 45's tree lowers them
# (``tests/test_older_programs_pinned._texts``, ``tests/test_admit_group._chunk_program_shas``).
# ISSUE 44 changed the FULL-width chunk program alone and pinned the other three to ITS parent
# (ee06ed6); ISSUE 45 moved all four — every program of the model writes a full layer's ONE row
# [c | r] and gathers it once, straight out of the pool — and re-derived them: what a later PR
# that leaves this model's programs alone must reproduce. ISSUE 48 re-derived the COMPACTED chunk
# program alone: its carry holds a third attention count (``ops.ATTN_STATS``), 0 for this model.
# ISSUE 62 moved all four and re-derived them ONCE: at these widths the rule (``sl.walks``: twelve
# columns of table behind top-256 at 4 heads) WALKS — the members' mask, ``walk_split`` once a
# forward and the walked kernel where the gather stood — and every program carries two more
# counts (``selected_tiles`` / ``selected_tiles_walked``). ISSUE 63 moved all four again, ONCE: a
# walked tile's set is made by ``threshold_members`` (in interpret mode here: its compare-and-count
# steps stand in the text) where ``lax.top_k`` sorted, and the carry holds a ninth count
# (``selections_thresholded``)
PARENT_SHA256 = {
    "group": "351a01c88efc4b25e69e04feea74736679677f7edf43c4ea10e90b49ad0a460d",
    "block": "887b0527f8ce358515c76e7e0dc25f3dac42101db3514a25670b418819f48e90",
    "chunk": ["618e45882ec4eae9ca761493215e18620c8b58d93886d0786b3b57841bb5b0f9",
              "ee74fc10a2668cba133f05d64121aa9b8717a391df69884308e3f55931463213"],
}


@pytest.fixture(scope="module")
def engine_of_32_slots(prompts):
    eng = _engine(batch_slots=32, pool_blocks=240)
    eng.set_prompt_prefix(*prompts[:2])
    assert eng.admit_rows == 4 and eng.compact_rows * 9 <= eng.ffn_pack_rows < 32 * 9
    return eng


@pytest.fixture(scope="module")
def lowered_at_32_slots(engine_of_32_slots):
    from test_admit_group import _chunk_program_shas
    from test_older_programs_pinned import _sha, _texts

    eng = engine_of_32_slots
    return {**{k: _sha(v) for k, v in _texts(eng).items()}, "chunk": _chunk_program_shas(eng)}


@pytest.mark.parametrize("program", ["group", "block", "compact"])
def test_the_programs_that_pack_nothing_are_the_parents(lowered_at_32_slots, program):
    """The grouped admission ((4, 64) with ``n_real`` and no ``ffn_pack``: the
    real positions first through the full layers' tiles, block-shaped
    everywhere else), the comparison's one-row 1 + 8 block and the compacted
    chunk width (72 positions <= 96) lower to the pinned text (ISSUE 45's: the
    merged plane moved them all): a change that means to leave them alone
    loads the parent's executables on the chip."""
    got = lowered_at_32_slots
    if program == "compact":
        assert got["chunk"][1] == PARENT_SHA256["chunk"][1]
    else:
        assert got[program] == PARENT_SHA256[program]


def test_the_full_width_chunk_program_walks_tiles_and_branches_nowhere(engine_of_32_slots):
    """ISSUE 44: at 32 rows x 9 positions the chunk program packs — with NO
    conditional from the packing (as many as the same program lowered without
    ``ffn_pack``; the parent's held 11 more at these widths, ``llama.packed_ffn``'s
    one a layer and what its two branches each repeat) and two tile walks a layer."""
    from tpu_voice_agent.serve import paged

    eng = engine_of_32_slots
    z = lambda dt=jnp.int32: jnp.zeros((eng.batch_slots,), dt)
    lowered = lambda **kw: paged.paged_chunk_decode_loop.__wrapped__.lower(
        eng.params, eng.cfg, eng.k_pool, eng.v_pool, eng.block_tables, z(), z(), z(), z(jnp.bool_), z(), z(),
        eng.tables_ff, eng.byte_len_table, jax.random.PRNGKey(0), jnp.float32(0), jnp.int32(0),
        trash_idx=z(), rules=None, logit_mask=eng.logit_mask, chunk_steps=4, greedy=True, constrained=True,
        kernels=eng.kernels, eos_id=eng.eos_id, pad_id=eng.pad_id, max_len=eng.max_len, kv_quant=None,
        quality_lanes=eng.quality_lanes, **kw).as_text()
    packed, whole = lowered(ffn_pack=eng.ffn_pack_rows), lowered()
    count = lambda text, op: text.count(f"stablehlo.{op}")
    assert count(packed, "case") + count(packed, "if") == count(whole, "case") + count(whole, "if")
    assert count(packed, "while") == count(whole, "while") + 2 * eng.cfg.n_layers


@pytest.mark.parametrize("what", ["radix", "kv_quant", "handoff", "mesh", "dense_engine", "dense_forward"])
def test_what_moves_k_and_v_planes_refuses_the_planes_by_kind_by_type(what):
    """ONE typed error, where each is built or called — its message names the
    index plane."""
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
        elif what == "mesh":
            from tpu_voice_agent.parallel import make_mesh

            _engine(mesh=make_mesh(dp=2, tp=1, devices=jax.devices()[:2]))
        else:
            DecodeEngine(cfg=CFG, max_len=256, batch_slots=2, quant=None)
    assert "index key" in mla.LatentCacheOnly.__doc__
