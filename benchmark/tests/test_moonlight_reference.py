"""The Moonlight reference, reached as the harness reaches it: by the name the
configuration gives, through the protocol's ``logits`` with the
configuration's own keys — each of the model's rules read from them — and
through ``lib/refcheck.compare`` on the rehearsal's served stack (two leading
dense layers, a latent rank that is no head width, a nonzero bias), where its
int4 control has to land above its tolerance; then the latent cache's and the
routed block's roofline arithmetic against hand counts at the PUBLISHED
widths, and the cell among the manifest's per-layer lists."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import manifest as mf
from benchmark.lib import refcheck

CONF = mf.load_json("benchmark/configs/moonlight-16b-a3b-int8.json")


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def test_the_file_holds_the_catalog_s_numbers_but_for_the_depth():
    manifest = mf.load_manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == "moonlight-16b-a3b-int8")
    assert entry["reduced"] == ["num_hidden_layers"] == sorted(CONF["reduced_why"])
    assert (CONF["num_hidden_layers"], CONF["num_hidden_layers_published"]) == (17, 27)
    assert CONF["num_hidden_layers"] - CONF["first_k_dense_replace"] >= 4  # the floor behind the dense one
    published = {"hidden_size": 2048, "intermediate_size": 11264, "moe_intermediate_size": 1408,
                 "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
                 "q_lora_rank": None, "n_routed_experts": 64, "num_experts_per_tok": 6,
                 "n_shared_experts": 2, "num_attention_heads": 16, "vocab_size": 163840,
                 "first_k_dense_replace": 1, "routed_scaling_factor": 2.446, "rope_theta": 50000,
                 "rms_norm_eps": 1e-05, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
                 "n_group": 1, "norm_topk_prob": True, "max_position_embeddings": 8192}
    assert {k: CONF[k] for k in published} == published
    cell = next(w for w in manifest["workloads"] if w["name"] == "moonlight_flood")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("moonlight-16b-a3b-int8", "parse_flood", 1)


def test_the_manifest_is_valid_with_room_left_and_the_cell_reads_what_its_siblings_read():
    """Until PR 42 the manifest was full (128 of 128) and this cell had four
    entries; folded to one entry a (metric, metric moved) it reads its
    siblings' lists, its own floors through its own files, and four of its own."""
    manifest = mf.load_manifest()
    assert mf.validate(manifest) == [] and len(manifest["per_layer"]) <= 80
    cell = mf.load_cell(manifest, "moonlight_flood")
    assert [m["name"] for m in cell["end_to_end"]] == ["setup_s", "out_tokens_per_s"]
    names = [m["name"] for m in cell["per_layer"]]
    assert len(names) == 35 and {
        "expert_matmul_device_ms_per_forward.floods", "grouped_matmul_roofline.floods", "tokens_per_forward.floods",
        "step_ms.floods", "device_idle_share.floods", "decode_program_roofline.floods", "step_mfu.floods"} <= set(names)
    own = [m["name"] for m in cell["per_layer"] if m["workloads"] == ["moonlight_flood"]]
    assert own == ["latent_attn_roofline.moonlight_flood", "latent_write_device_ms_per_forward.moonlight_flood",
                   "latent_absorb_device_ms_per_forward.moonlight_flood", "dense_ffn_device_ms_per_forward.moonlight_flood"]
    assert all(m["moves"] == "out_tokens_per_s" for m in cell["per_layer"])
    floors = {n: mf.load_layer_metric(n, "moonlight_flood") for n in names if n.endswith("_roofline.floods")}
    assert {n: (s["reader"], s["args"]["what"]) for n, s in floors.items()} == {
        "decode_program_roofline.floods": ("roofline_mla_moe", "program_roofline"),
        "grouped_matmul_roofline.floods": ("roofline_mla_moe", "grouped_matmul_roofline")}
    assert mf.code_problems(cell) == []
    rate = next(m for m in manifest["end_to_end"] if m["name"] == "out_tokens_per_s")
    assert rate["workloads"][-1] == "moonlight_flood" and rate["bound"] == 0.015


def test_the_reference_reads_each_rule_of_the_model_from_the_configuration_s_keys():
    from benchmark.builders import moonlight_stack, parse_stack
    from tpu_voice_agent.models.llama import forward_paged, init_params

    ref = mf.load_code("reference", CONF["reference"])
    model, serving = parse_stack.as_run(CONF, rehearsal=True)
    cfg = dataclasses.replace(moonlight_stack.llama_config(model, serving), max_seq_len=256)
    assert (cfg.first_dense_layers, cfg.kv_lora_rank, cfg.qk_rope_dim, cfg.n_experts, cfg.top_k) == (2, 48, 16, 8, 3)
    params = init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 48), 0, cfg.vocab_size)
    sample = {"tokens": [int(t) for t in toks[0]], "rows": 48}
    planes = lambda w: jnp.zeros((cfg.n_layers, 5, 16, w), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = forward_paged(params, cfg, toks, jnp.arange(48, dtype=jnp.int32)[None],
                             planes(cfg.kv_lora_rank), planes(cfg.qk_rope_dim),
                             jnp.asarray([[1, 2, 3, 4]], jnp.int32), attn_impl="xla", fresh_block=True)[0][0]
    assert _rel(ref.logits(params, model, sample), want) < 2e-4
    for change in ({"routed_scaling_factor": 1.0}, {"num_experts_per_tok": 2}, {"rope_theta": 10000},
                   {"latent_norm_eps": 1e-2}, {"rms_norm_eps": 1e-1}):
        assert _rel(ref.logits(params, dict(model, **change), sample), want) > 1e-3, change
    # the shared experts are ADDED: taken apart as one or as two they are the same sum
    assert _rel(ref.logits(params, dict(model, n_shared_experts=1), sample), want) < 2e-4
    # the bias selects: without it the chosen sets differ and so do the logits
    no_bias = jax.tree.map(lambda a: a, params)
    no_bias["layers"] = {**params["layers"], "router_bias": jnp.zeros_like(params["layers"]["router_bias"])}
    assert _rel(ref.logits(no_bias, model, sample), want) > 1e-3
    assert _rel(ref.logits(params, model, sample, control=True), want) > ref.TOLERANCE
    assert ref.CONTROL == "int4" and ref.SAMPLE == "paged_decoder"


def test_the_rehearsal_stack_passes_the_comparison_with_its_control_above():
    said = []
    served = mf.load_code("builders", CONF["builder"]).build(CONF, True, said.append)
    try:
        eng = served.engine
        assert eng.latent and eng.cfg.first_dense_layers == 2 and eng.cfg.router_bias
        assert eng.k_pool.shape[-1] == 48 and eng.v_pool.shape[-1] == 16 and eng.k_pool.ndim == 4
        assert float(jnp.abs(eng.params["layers"]["router_bias"]).min()) > 0
        seen = refcheck.compare(served, CONF, 3, said.append)
    finally:
        served.close()
    ref = mf.load_code("reference", CONF["reference"])
    assert [c["reference"] for c in seen] == ["moonlight_decoder"] and seen[0]["ok"]
    assert seen[0]["rel_err"] <= ref.TOLERANCE < seen[0]["control"]
    assert any("reference moonlight_decoder:" in line and line.endswith("-> ok") for line in said)


def test_a_program_without_the_model_s_fields_is_refused_before_anything_is_built(monkeypatch):
    """What the PARENT of PR 38 does with this cell: the builder's typed exit."""
    import pytest

    from benchmark.builders import moonlight_stack

    monkeypatch.setattr(moonlight_stack, "NEEDS", moonlight_stack.NEEDS + ("a_field_no_program_has",))
    with pytest.raises(SystemExit, match="REFUSED: this program's LlamaConfig has no"):
        moonlight_stack.build(CONF, True, lambda line: None)


# ---- the roofline arithmetic (lib/peaks_mla_moe.py, readers/roofline_mla_moe.py)

MODEL = {k: v for k, v in CONF.items() if not isinstance(v, (dict, list))}
V5E = {"bytes_per_s": 819e9, "flops_per_s": 197e12}
EXPERT = 3 * 2048 * 1408  # one routed expert's three planes, int8 bytes


def test_the_hand_counts_at_the_published_widths():
    from benchmark.lib import peaks_mla_moe as pkm

    attn = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    assert pkm.attention_params(MODEL) == attn == 13_762_560  # 6.29 + 1.18 + 2.10 + 4.19 M
    assert pkm.expert_params(MODEL) == EXPERT == 8_650_752
    # the issue's 584.97 MB routed layer and 82.97 MB dense one, at int8
    assert pkm.layer_bytes(MODEL, 1, routed=True) == attn + 64 * EXPERT + 2 * EXPERT + 2048 * 64 * 2
    assert round(pkm.layer_bytes(MODEL, 1, routed=True) / 1e6, 2) == 584.97
    assert round(pkm.layer_bytes(MODEL, 1, routed=False) / 1e6, 2) == 82.97
    # 1152 B a token a layer, 19584 over the 17 layers; 200 blocks of 128 are 0.50 GB
    assert pkm.cache_bytes_per_token(MODEL) == 17 * (512 + 64) * 2 == 19584
    assert round(200 * 128 * pkm.cache_bytes_per_token(MODEL) / 1e9, 2) == 0.50
    quant, plain = pkm.streamed_params(MODEL)
    assert quant == 17 * attn + 3 * 2048 * 11264 + 16 * 2 * EXPERT + 163840 * 2048
    assert plain == 16 * 2048 * 64


def test_the_floor_counts_experts_touched_rows_assigned_and_the_cache_as_it_was_read():
    from benchmark.lib import peaks_mla_moe as pkm

    L = 16
    assert pkm.expert_bytes(MODEL, 1, touched=L * 64) == L * 64 * EXPERT  # 8.86 GB
    assert pkm.expert_flops(MODEL, assigned_rows=L * 288 * 6) == L * 288 * 6 * 2 * EXPERT
    assert pkm.cache_read_bytes(MODEL, keys_read=17 * 40 * 128) == 17 * 40 * 128 * 1152
    assert pkm.attention_flops(MODEL, query_rows=17 * 288 * 16, ctx=950) == 17 * 288 * 16 * 950 * 2 * (576 + 512)
    few = pkm.forward_bytes(MODEL, 1, touched=L * 10, keys_read=0)
    all_ = pkm.forward_bytes(MODEL, 1, touched=L * 64, keys_read=0)
    assert all_ - few == L * 54 * EXPERT
    # the head's FLOPs on ONE position a row
    base = pkm.forward_flops(MODEL, rows=32, positions=288, ctx=950, assigned_rows=0)
    more = pkm.forward_flops(MODEL, rows=33, positions=288, ctx=950, assigned_rows=0)
    assert more - base == 2 * 163840 * 2048
    # were all 288 positions of a 1 + 8 block of 32 rows real, the kernel's dots would bound it, not its bytes
    floor, roof = pkm.latent_attention_floor_s(MODEL, V5E, keys_read=17 * 48 * 128, positions=288, ctx=950)
    assert roof == "flops" and floor == 17 * 4608 * 950 * 2 * 1088 / 197e12
    # 29 of 64 experts a layer touched at 576 assignments: the planes bound the grouped matmul
    floor, roof = pkm.grouped_matmul_floor_s(MODEL, V5E, 1, touched=L * 29, assigned_rows=L * 576)
    assert roof == "bytes" and floor == L * 29 * EXPERT / 819e9


def test_a_perfect_kernel_reads_100_percent_and_a_program_without_the_counters_reads_nothing(monkeypatch):
    from benchmark.readers import roofline_mla_moe as rm

    from benchmark.readers import roofline

    fwds, keys, qrows = 16, 17 * 48 * 128, 17 * 4608
    perfect_ns = 17 * 48 * 128 * 1152 / 819e9 * 1e9 * fwds  # 45 real positions: the cache's read bounds the kernel
    experts_ns = 16 * 50 * EXPERT / 819e9 * 1e9 * fwds
    monkeypatch.setattr(roofline, "run_trace", lambda ctx: object())
    monkeypatch.setattr(rm, "needed", lambda ctx: {"steps": [], "rows": 32.0, "context": 950.0, "positions": 45.0,
                                                 "common_row_blocks": 192.0, "block_size": 128, "live": 32.0, "common": 768.0})
    monkeypatch.setattr(roofline, "scope_ns", lambda plane, scopes, program: {
        "ns": {rm.KERNEL: perfect_ns, "grouped_matmul": experts_ns}.get((scopes or [None])[0], 0),
        "program_ns": 400 * perfect_ns, "forwards": fwds})
    counters = {"scheduler.forwards": 100.0, "moe.experts_touched": 100.0 * 16 * 50,
                "moe.assigned_rows": 100.0 * 16 * 576, "attn.latent_keys_read": 100.0 * keys,
                "attn.latent_query_rows": 100.0 * qrows}
    ctx = {"counters": counters, "peaks": V5E, "model": MODEL,
           "serving": {"quant": "int8", "fast_forward": 8}}
    assert abs(rm.read(ctx, "kernel_roofline") - 100.0) < 1e-9
    assert abs(rm.read(ctx, "grouped_matmul_roofline") - 100.0) < 1e-9  # the planes of the 50 touched, at the roof
    assert 0 < rm.read(ctx, "program_roofline") < 100.0
    # the parent of PR 38, every model whose cache is K and V, a CPU rehearsal: nothing, and no raise
    for lacking in ("attn.latent_keys_read", "moe.experts_touched"):
        parent = dict(ctx, counters={k: v for k, v in counters.items() if k != lacking})
        assert [rm.read(parent, w) for w in ("kernel_roofline", "program_roofline", "grouped_matmul_roofline")] \
            == [None] * 3
    assert rm.read(dict(ctx, peaks=None), "kernel_roofline") is None
    assert rm.read(dict(ctx, model={"hidden_size": 4096}), "program_roofline") is None
