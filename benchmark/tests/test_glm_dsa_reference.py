"""The GLM-5.2 reference and cell, reached as the harness reaches them: by the
names the configuration gives, through the protocol's ``logits`` with the
configuration's own keys and through ``lib/refcheck.compare`` on the
rehearsal's served stack (selection binding behind its cached head, carried
into the shared layers), where the int4 control has to land above the
tolerance; the file's byte arithmetic and the floors of ``lib/peaks_glm_dsa.py``
against hand counts at the PUBLISHED widths; the cell among the manifest's
per-layer lists; a program without the model's fields refused before anything
is built; and the proof that the cell came as NEW files and APPENDED entries
(``data/glm_dsa_addition.json`` holds the parent's hashes)."""

import dataclasses
import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import manifest as mf
from benchmark.lib import refcheck

ROOT = Path(__file__).resolve().parents[2]
CELL = "glm52_sitemap_flood"
CONF = mf.load_json("benchmark/configs/glm-5.2-int8.json")
MODEL = {k: v for k, v in CONF.items() if not isinstance(v, (dict, list))}
V5E = {"bytes_per_s": 819e9, "flops_per_s": 197e12}
EXPERT = 3 * 6144 * 2048  # one routed expert's three planes, int8 bytes
OWN = ["index_device_ms_per_forward", "sparse_attn_device_ms_per_forward", "selection_carry_device_ms_per_forward",
       "sparse_selected_share", "selection_carried_share", "sparse_attn_roofline", "indexer_roofline",
       "position_wise_device_ms_per_forward"]
NEW = ("glm", "benchmark/INDEXSHARE.md", "benchmark/tools/indexshare_check.py")


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def test_the_file_holds_the_catalog_s_numbers_but_for_the_four_reduced_keys():
    manifest = mf.load_manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == "glm-5.2-int8")
    assert entry["reduced"] == CONF["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                                                   "n_routed_experts", "vocab_size"]
    assert sorted(entry["reduced"]) == sorted(CONF["reduced_why"]) and entry["source"] == CONF["source"]
    # the guide's floors: two whole periods behind the dense layer, >= 8 experts, 1/8 of the rows
    assert CONF["num_hidden_layers"] - CONF["first_k_dense_replace"] == 7 and CONF["indexer_kinds"] == "FSSS" * 2
    assert CONF["n_routed_experts"] >= 8 and CONF["vocab_size"] * 8 == CONF["vocab_size_published"]
    assert len(CONF["assumed"]) >= 10 and "left_out" in CONF and "deployment" in CONF
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("glm-5.2-int8", "parse_flood", 1)
    assert len(cell["why"]) <= 200 and "16x" in cell["why"]


def test_the_manifest_is_valid_and_the_cell_reads_what_its_sibling_reads_and_eight_of_its_own():
    manifest = mf.load_manifest()
    assert mf.validate(manifest) == []
    cell = mf.load_cell(manifest, CELL)
    assert mf.code_problems(cell) == []
    assert [m["name"] for m in cell["end_to_end"]] == ["setup_s", "out_tokens_per_s"]
    names = [m["name"] for m in cell["per_layer"]]
    sibling = [m["name"] for m in mf.load_cell(manifest, "dots3note_sitemap_flood")["per_layer"]]
    shared = [n for n in sibling if not n.endswith(".dots3note_sitemap_flood")]
    own = [m["name"] for m in cell["per_layer"] if m["workloads"] == [CELL]]
    assert own == [f"{q}.{CELL}" for q in OWN] and names == shared + own
    assert all(m["moves"] == "out_tokens_per_s" for m in cell["per_layer"])
    assert all(m["workloads"][-1] == CELL for m in cell["per_layer"])  # every list joined at the END
    floors = {n: mf.load_layer_metric(n, CELL) for n in names if "roofline" in n or n.startswith("step_mfu")}
    assert {n: (s["reader"], s["args"]["what"]) for n, s in floors.items()} == {
        "decode_program_roofline.floods": ("roofline_glm_dsa", "program_roofline"),
        "grouped_matmul_roofline.floods": ("roofline_glm_dsa", "grouped_matmul_roofline"),
        "step_mfu.floods": ("roofline_glm_dsa", "step_mfu"),
        f"sparse_attn_roofline.{CELL}": ("roofline_glm_dsa", "sparse_attn_roofline"),
        f"indexer_roofline.{CELL}": ("roofline_glm_dsa", "indexer_roofline")}
    assert mf.load_layer_metric("moe_experts_touched_per_layer.floods", CELL)["args"]["scale"] == 1 / 7
    assert mf.load_layer_metric("moe_load_max_over_mean.floods", CELL)["args"]["scale"] == 16


def test_nothing_the_benchmark_had_was_edited_and_every_entry_was_appended():
    """``data/glm_dsa_addition.json``: sha256 of every file under ``benchmark/``
    and of the manifest as PR 61's parent (7f49a8f) held them. Each file is
    still that file; the manifest with this PR's cell, configuration and
    entries taken out again is the parent's, entry for entry and in order."""
    held = json.loads((Path(__file__).parent / "data" / "glm_dsa_addition.json").read_text())
    now = {p.relative_to(ROOT).as_posix(): hashlib.sha256(p.read_bytes()).hexdigest()
           for p in sorted((ROOT / "benchmark").rglob("*"))
           if p.is_file() and "__pycache__" not in p.parts and ".jax_cache" not in p.parts}
    assert {k: now.get(k) for k in held["files"]} == held["files"]
    added = sorted(set(now) - set(held["files"]))
    assert added and all(any(n in k for n in NEW) for k in added), added
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert manifest["configs"].pop()["name"] == "glm-5.2-int8"
    assert manifest["workloads"].pop()["name"] == CELL
    assert [m["name"] for m in manifest["per_layer"][-len(OWN):]] == [f"{q}.{CELL}" for q in OWN]
    del manifest["per_layer"][-len(OWN):]
    joined = 0
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m.get("workloads", [None])[-1] == CELL:
            m["workloads"].pop()
            joined += 1
    assert joined == held["lists_joined"]
    assert CELL not in json.dumps(manifest) and "glm" not in json.dumps(manifest)
    assert hashlib.sha256(json.dumps(manifest, sort_keys=True).encode()).hexdigest() == held["manifest_sha256"]


def test_the_reference_reads_each_rule_of_the_model_from_the_configuration_s_keys():
    from benchmark.builders import parse_stack
    from tpu_voice_agent.models import dots3
    from tpu_voice_agent.models.llama import forward_paged, init_params

    ref = mf.load_code("reference", CONF["reference"])
    builder = mf.load_code("builders", CONF["builder"])
    model, serving = parse_stack.as_run(CONF, rehearsal=True)
    model = {**model, "index_topk": 12}  # binds inside 40 tokens
    cfg = dataclasses.replace(builder.llama_config(model, {**serving, "site_context_tokens": 0}), max_seq_len=256)
    assert (cfg.first_dense_layers, cfg.kv_lora_rank, cfg.v_head_dim, cfg.n_experts, cfg.n_held) == (1, 48, 40, 16, 4)
    params = init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 40), 0, cfg.vocab_size)
    sample = {"tokens": [int(t) for t in toks[0]], "rows": 40}
    planes = dots3.cache_spec(cfg)["planes"]
    kp, vp = ({n: jnp.zeros((L, 6, 8, w), jnp.float32) for n, (L, w) in planes[p].items()} for p in "kv")
    with jax.default_matmul_precision("highest"):
        want = forward_paged(params, cfg, toks, jnp.arange(40, dtype=jnp.int32)[None], kp, vp,
                             jnp.asarray([[1, 2, 3, 4, 5]], jnp.int32), attn_impl="xla")[0][0]
    assert _rel(ref.logits(params, model, sample), want) < 2e-4
    for change in ({"index_topk": 11}, {"num_experts_per_tok": 2}, {"rope_theta": 10000},
                   {"latent_norm_eps": 1e-2}, {"rms_norm_eps": 1e-1}, {"first_expert": 0},
                   {"routed_scaling_factor": 1.0}, {"indexer_kinds": "FSFSS"}):
        assert _rel(ref.logits(params, dict(model, **change), sample), want) > 1e-3, change
    # a shared layer attends EXACTLY its full layer's set: every other reading of "shared" is another model
    for shared in ("first", "rescored", "all"):
        assert _rel(ref.forward(params, sample["tokens"], model, last=40, shared=shared), want) > 1e-3, shared
    assert _rel(ref.logits(params, model, sample, control=True), want) > ref.TOLERANCE
    assert ref.CONTROL == "int4" and ref.SAMPLE == "paged_decoder"


def test_the_rehearsal_stack_passes_the_comparison_with_its_control_above():
    said = []
    served = mf.load_code("builders", CONF["builder"]).build(CONF, True, said.append)
    try:
        eng = served.engine
        assert eng.sparse and eng.cfg.first_dense_layers == 1 and eng.cfg.router_bias
        assert set(eng.k_pool) == {"kv", "idx", "shared"} and not eng.v_pool
        assert (eng.k_pool["idx"].shape[0], eng.k_pool["shared"].shape[0]) == (2, 3)
        # the rehearsal's head: 879 + 145 = 1024 tokens, eight whole blocks; 256 keys bind
        assert len(eng.prefix_ids) == 1024 > eng.cfg.index_topk
        seen = refcheck.compare(served, CONF, 3, said.append)
    finally:
        served.close()
        from tpu_voice_agent.services import prompts

        prompts.set_site_context("")
    ref = mf.load_code("reference", CONF["reference"])
    assert [c["reference"] for c in seen] == ["glm_dsa_decoder"] and seen[0]["ok"]
    assert seen[0]["rel_err"] <= ref.TOLERANCE < seen[0]["control"]
    assert any("reference glm_dsa_decoder:" in line and line.endswith("-> ok") for line in said)


def test_a_program_without_the_model_s_fields_is_refused_before_anything_is_built(monkeypatch):
    """What the PARENT of PR 61 does with this cell: the builder's typed exit."""
    from benchmark.builders import glm_dsa_stack

    monkeypatch.setattr(glm_dsa_stack, "NEEDS", glm_dsa_stack.NEEDS + ("a_field_no_program_has",))
    with pytest.raises(SystemExit, match="REFUSED: this program's LlamaConfig has no"):
        glm_dsa_stack.build(CONF, True, lambda line: None)


# ---- the file's byte arithmetic and the floors (lib/peaks_glm_dsa.py, readers/roofline_glm_dsa.py)


def test_the_file_s_byte_arithmetic_is_the_yardstick_s():
    from benchmark.lib import peaks_glm_dsa as pkg

    attn = 6144 * 2048 + 2048 * 64 * 256 + 6144 * 576 + 512 * 64 * 448 + 64 * 256 * 6144
    assert pkg.attention_params(MODEL) == attn == 165_019_648
    assert pkg.indexer_params(MODEL) == 2048 * 32 * 128 + 6144 * 128 + 6144 * 32 == 9_371_648
    assert pkg.expert_params(MODEL) == EXPERT == 37_748_736 and pkg.dense_params(MODEL) == 226_492_416
    assert pkg.layers(MODEL) == (8, 2)
    shared_l, full_l, dense0 = (pkg.layer_bytes(MODEL, 1, False, True), pkg.layer_bytes(MODEL, 1, True, True),
                                pkg.layer_bytes(MODEL, 1, True, False))
    assert shared_l == attn + 17 * EXPERT + 6144 * 256 * 2 and full_l - shared_l == 9_371_648
    assert [round(b / 1e6) for b in (shared_l, full_l, dense0)] == [810, 819, 401]
    assert round((dense0 + full_l + 6 * shared_l) / 1e9, 2) == 6.08
    assert round((dense0 + 2 * full_l + 9 * shared_l) / 1e9, 2) == 9.33  # a third period
    for said in ("810 MB", "819 MB", "401 MB", "6.08 GB", "9.33 GB", "165.0 M", "9.37 M", "37.75 M"):
        assert said in CONF["reduced_why"]["num_hidden_layers"], said
    # 1152 B a token a layer, 256 B more where an indexer runs: 9728 B over the 8 layers, 0.33 GB of pool
    assert pkg.cache_bytes_per_token(MODEL) == 8 * 1152 + 2 * 256 == 9728
    s = CONF["serving"]
    assert round(s["pool_blocks"] * s["block_size"] * 9728 / 1e9, 2) == 0.33
    quant, plain = pkg.streamed_params(MODEL)
    assert quant == 8 * attn + 2 * 9_371_648 + 3 * 6144 * 12288 + 7 * EXPERT + 19360 * 6144
    assert plain == 7 * 6144 * 256


def test_the_floor_counts_what_is_needed_selected_rows_in_all_layers_index_keys_in_two():
    from benchmark.lib import peaks_glm_dsa as pkg

    sel = 8 * 45 * 2048  # 45 real positions, all eight layers
    assert pkg.selected_bytes(MODEL, sel) == sel * 576 * 2
    assert pkg.selected_flops(MODEL, sel) == sel * 64 * 2 * (576 + 512)
    vis = 8 * 45 * 8400  # counted over all eight; two score
    assert pkg.indexer_flops(MODEL, vis) == 2 * 45 * 8400 * 32 * 128 * 2
    assert pkg.indexer_bytes(MODEL, ctx=8400) == 2 * 8400 * 128 * 2
    assert pkg.expert_bytes(MODEL, 1, touched=7 * 4) == 7 * 4 * EXPERT
    assert pkg.expert_flops(MODEL, local_rows=7 * 22) == 7 * 22 * 2 * EXPERT
    few = pkg.forward_bytes(MODEL, 1, 8400, touched=7 * 3, keys_selected=sel)
    all_ = pkg.forward_bytes(MODEL, 1, 8400, touched=7 * 16, keys_selected=sel)
    assert all_ - few == 7 * 13 * EXPERT
    assert pkg.forward_bytes(MODEL, 1, 8400, 0, sel) - pkg.forward_bytes(MODEL, 1, 8400, 0, 0) == sel * 1152
    base = pkg.forward_flops(MODEL, 32, 45, 0, sel, vis)
    assert pkg.forward_flops(MODEL, 33, 45, 0, sel, vis) - base == 2 * 19360 * 6144  # the head: a position a row
    floor, roof = pkg.selected_attention_floor_s(MODEL, V5E, sel)
    # 64 heads share a key's 1152 bytes: 121 FLOPs a byte, half v5e's ridge — the rows' bytes
    assert roof == "bytes" and floor == sel * 1152 / 819e9
    floor, roof = pkg.indexer_floor_s(MODEL, V5E, 8400, vis)
    assert roof == "flops" and floor == 2 * 45 * 8400 * 32 * 128 * 2 / 197e12
    floor, roof = pkg.grouped_matmul_floor_s(MODEL, V5E, 1, touched=7 * 4, local_rows=7 * 22)
    assert roof == "bytes" and floor == 7 * 4 * EXPERT / 819e9


def test_a_perfect_kernel_reads_100_percent_and_a_program_without_the_counters_reads_nothing(monkeypatch):
    from benchmark.lib import peaks_glm_dsa as pkg
    from benchmark.readers import roofline
    from benchmark.readers import roofline_glm_dsa as rd

    fwds, sel, vis = 16, 8 * 45 * 2048, 8 * 45 * 8400
    n = {"steps": [], "rows": 32.0, "context": 8400.0, "positions": 45.0, "common_row_blocks": 0.0,
         "block_size": 128, "live": 32.0, "common": 8192.0}
    perfect = {"layer/attn/select": pkg.selected_attention_floor_s(MODEL, V5E, sel)[0],
               "indexer_scores": pkg.indexer_floor_s(MODEL, V5E, 8400.0, vis)[0],
               "grouped_matmul": pkg.grouped_matmul_floor_s(MODEL, V5E, 1, 7 * 4, 7 * 22)[0]}
    monkeypatch.setattr(roofline, "run_trace", lambda ctx: object())
    monkeypatch.setattr(rd, "needed", lambda ctx: n)
    under = lambda plane, scopes, program: {
        "ns": perfect.get((scopes or [None])[0], 0) * 1e9 * fwds, "program_ns": 0.030 * 1e9 * fwds, "forwards": fwds}
    monkeypatch.setattr(roofline, "scope_ns", under)
    monkeypatch.setattr(rd, "scope_ns", under)
    monkeypatch.setattr(rd, "run_trace", lambda ctx: object())
    assert rd.SELECTED == ["layer/attn/select", "layer/attn/full"]  # the gather AND the kernel
    counters = {"scheduler.forwards": 100.0, "moe.experts_touched": 100.0 * 7 * 4, "moe.local_rows": 100.0 * 7 * 22,
                "attn.keys_selected": 100.0 * sel, "attn.keys_visible": 100.0 * vis,
                "attn.selections_made": 100.0 * 2 * 45, "attn.selections_carried": 100.0 * 6 * 45}
    ctx = {"counters": counters, "peaks": V5E, "model": MODEL, "serving": {"quant": "int8", "fast_forward": 8}}
    for what in ("sparse_attn_roofline", "indexer_roofline", "grouped_matmul_roofline"):
        assert abs(rd.read(ctx, what) - 100.0) < 1e-9, what
    assert 0 < rd.read(ctx, "program_roofline") < 100.0
    assert rd.read(ctx, "carried_share") == 75.0
    # the parent of PR 61, a model whose layers each select for themselves, a CPU rehearsal: nothing, no raise
    for lacking in ("attn.selections_carried", "attn.keys_selected", "moe.local_rows"):
        parent = dict(ctx, counters={k: v for k, v in counters.items() if k != lacking})
        assert [rd.read(parent, w) for w in ("sparse_attn_roofline", "program_roofline", "step_mfu")] == [None] * 3
    assert rd.read(dict(ctx, counters={}), "carried_share") is None
    assert rd.read(dict(ctx, peaks=None), "indexer_roofline") is None
    assert rd.read(dict(ctx, model={"hidden_size": 4096}), "program_roofline") is None
    with pytest.raises(ValueError, match="unknown quantity"):
        rd.read(ctx, "no_such_share")
