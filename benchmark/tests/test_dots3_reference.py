"""The dots3-note-prev reference and cell, reached as the harness reaches them:
by the names the configuration gives, through the protocol's ``logits`` with
the configuration's own keys and through ``lib/refcheck.compare`` on the
rehearsal's served stack (selection and window binding behind its cached
head), where the int4 control has to land above the tolerance; the file's
byte arithmetic and the floors of ``lib/peaks_dots3.py`` against hand counts at
the PUBLISHED widths; the cell among the manifest's per-layer lists; a program
without the model's fields refused before anything is built; and the proof
that the cell came as NEW files and APPENDED entries (``data/dots3_addition.json``
holds the parent's hashes)."""

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
CELL = "dots3note_sitemap_flood"
CONF = mf.load_json("benchmark/configs/dots3-note-prev-int8.json")
MODEL = {k: v for k, v in CONF.items() if not isinstance(v, (dict, list))}
V5E = {"bytes_per_s": 819e9, "flops_per_s": 197e12}
EXPERT = 3 * 5120 * 1536  # one routed expert's three planes, int8 bytes
OWN = ["index_device_ms_per_forward", "sparse_attn_device_ms_per_forward", "window_attn_device_ms_per_forward",
       "sparse_selected_share", "sparse_attn_roofline", "indexer_roofline", "window_latent_attn_roofline"]


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def test_the_file_holds_the_catalog_s_numbers_but_for_depth_experts_held_and_vocabulary():
    manifest = mf.load_manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == "dots3-note-prev-int8")
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert sorted(entry["reduced"]) == sorted(CONF["reduced_why"]) and entry["source"] == CONF["source"]
    assert (CONF["num_hidden_layers"], CONF["n_routed_experts"], CONF["vocab_size"]) == (9, 32, 19008)
    # the guide's floors: a whole period and >= 4 layers behind the dense one, >= 8 experts, 1/8 of the rows
    assert CONF["num_hidden_layers"] - CONF["first_k_dense_replace"] == 8 and CONF["layer_kinds"][1:] == "FSSS" * 2
    assert CONF["n_routed_experts"] >= CONF["num_experts_per_tok"] == 8
    assert {k: CONF[k] for k in ("hidden_size", "q_lora_rank", "kv_lora_rank", "index_topk", "swa_kv_lora_rank",
                                 "sliding_window_size", "moe_intermediate_size", "intermediate_size")} == {
        "hidden_size": 5120, "q_lora_rank": 1024, "kv_lora_rank": 512, "index_topk": 2048,
        "swa_kv_lora_rank": 1024, "sliding_window_size": 513, "moe_intermediate_size": 1536,
        "intermediate_size": 13824}
    assert len(CONF["assumed"]) >= 10 and "left_out" in CONF and "deployment" in CONF
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("dots3-note-prev-int8", "parse_flood", 1)
    assert len(cell["why"]) <= 200


def test_the_manifest_is_valid_and_the_cell_reads_what_its_siblings_read_and_seven_of_its_own():
    manifest = mf.load_manifest()
    assert mf.validate(manifest) == []
    assert len(manifest["per_layer"]) <= 88 and len(manifest["workloads"]) == 7 == len(manifest["configs"]) + 1
    cell = mf.load_cell(manifest, CELL)
    assert [m["name"] for m in cell["end_to_end"]] == ["setup_s", "out_tokens_per_s"]
    names = [m["name"] for m in cell["per_layer"]]
    assert {"expert_matmul_device_ms_per_forward.floods", "grouped_matmul_roofline.floods", "step_ms.floods",
            "tokens_per_forward.floods", "device_idle_share.floods", "decode_program_roofline.floods",
            "step_mfu.floods", "shared_expert_device_ms_per_forward.floods"} <= set(names)
    own = [m["name"] for m in cell["per_layer"] if m["workloads"] == [CELL]]
    assert own == [f"{q}.{CELL}" for q in OWN] and own == [m["name"] for m in manifest["per_layer"][-7:]]
    assert all(m["moves"] == "out_tokens_per_s" for m in cell["per_layer"])
    # every list the cell joined, it joined at the END
    assert all(m["workloads"][-1] == CELL for m in cell["per_layer"])
    floors = {n: mf.load_layer_metric(n, CELL) for n in names if "roofline" in n or n.startswith("step_mfu")}
    assert {n: (s["reader"], s["args"]["what"]) for n, s in floors.items()} == {
        "decode_program_roofline.floods": ("roofline_dots3", "program_roofline"),
        "grouped_matmul_roofline.floods": ("roofline_dots3", "grouped_matmul_roofline"),
        "step_mfu.floods": ("roofline_dots3", "step_mfu"),
        f"sparse_attn_roofline.{CELL}": ("roofline_dots3", "sparse_attn_roofline"),
        f"indexer_roofline.{CELL}": ("roofline_dots3", "indexer_roofline"),
        f"window_latent_attn_roofline.{CELL}": ("roofline_dots3", "window_attn_roofline")}
    assert mf.load_layer_metric("moe_experts_touched_per_layer.floods", CELL)["args"]["scale"] == 1 / 8
    assert mf.code_problems(cell) == []
    rate = next(m for m in manifest["end_to_end"] if m["name"] == "out_tokens_per_s")
    assert rate["workloads"][-1] == CELL and rate["bound"] == 0.015


def test_nothing_the_benchmark_had_was_edited_and_every_entry_was_appended():
    """``data/dots3_addition.json``: sha256 of every file under ``benchmark/``
    and of the manifest as PR 43's parent (90c46fe) held them. Each file is
    still that file; the manifest with this PR's cell, configuration and
    entries taken out again is the parent's, entry for entry and in order. (A
    ``benchmark`` PR that edits a file on purpose re-derives the data.)"""
    held = json.loads((Path(__file__).parent / "data" / "dots3_addition.json").read_text())
    now = {p.relative_to(ROOT).as_posix(): hashlib.sha256(p.read_bytes()).hexdigest()
           for p in sorted((ROOT / "benchmark").rglob("*"))
           if p.is_file() and "__pycache__" not in p.parts and ".jax_cache" not in p.parts}
    assert {k: now.get(k) for k in held["files"]} == held["files"]
    added = sorted(set(now) - set(held["files"]))
    assert all("dots3" in k or k in ("benchmark/SPARSE.md", "benchmark/tools/sparse_check.py") for k in added), added
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert manifest["configs"].pop()["name"] == "dots3-note-prev-int8"
    assert manifest["workloads"].pop()["name"] == CELL
    assert [m["name"] for m in manifest["per_layer"][-7:]] == [f"{q}.{CELL}" for q in OWN]
    del manifest["per_layer"][-7:]
    joined = 0
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m.get("workloads", [None])[-1] == CELL:
            m["workloads"].pop()
            joined += 1
    assert joined == held["lists_joined"]
    assert CELL not in json.dumps(manifest) and "dots3" not in json.dumps(manifest)
    assert hashlib.sha256(json.dumps(manifest, sort_keys=True).encode()).hexdigest() == held["manifest_sha256"]


def test_the_reference_reads_each_rule_of_the_model_from_the_configuration_s_keys():
    from benchmark.builders import parse_stack
    from tpu_voice_agent.models import dots3
    from tpu_voice_agent.models.llama import forward_paged, init_params

    ref = mf.load_code("reference", CONF["reference"])
    builder = mf.load_code("builders", CONF["builder"])
    model, serving = parse_stack.as_run(CONF, rehearsal=True)
    model = {**model, "index_topk": 12, "sliding_window_size": 7}  # both bind inside 40 tokens
    cfg = dataclasses.replace(builder.llama_config(model, {**serving, "site_context_tokens": 0}), max_seq_len=256)
    assert (cfg.first_dense_layers, cfg.kv_lora_rank, cfg.swa_kv_lora_rank, cfg.n_experts, cfg.n_held) == (2, 48, 40, 16, 4)
    params = init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 40), 0, cfg.vocab_size)
    sample = {"tokens": [int(t) for t in toks[0]], "rows": 40}
    planes = dots3.cache_spec(cfg)["planes"]
    kp, vp = ({n: jnp.zeros((L, 6, 8, w), jnp.float32) for n, (L, w) in planes[p].items()} for p in "kv")
    with jax.default_matmul_precision("highest"):
        want = forward_paged(params, cfg, toks, jnp.arange(40, dtype=jnp.int32)[None], kp, vp,
                             jnp.asarray([[1, 2, 3, 4, 5]], jnp.int32), attn_impl="xla")[0][0]
    assert _rel(ref.logits(params, model, sample), want) < 2e-4
    for change in ({"index_topk": 11}, {"sliding_window_size": 8}, {"num_experts_per_tok": 2},
                   {"rope_theta": 10000}, {"swa_rope_theta": 10000}, {"latent_norm_eps": 1e-2},
                   {"rms_norm_eps": 1e-1}, {"first_expert": 0}, {"layer_kinds": "FSSFS"}):
        assert _rel(ref.logits(params, dict(model, **change), sample), want) > 1e-3, change
    assert _rel(ref.logits(params, model, sample, control=True), want) > ref.TOLERANCE
    assert ref.CONTROL == "int4" and ref.SAMPLE == "paged_decoder"


def test_the_rehearsal_stack_passes_the_comparison_with_its_control_above():
    said = []
    served = mf.load_code("builders", CONF["builder"]).build(CONF, True, said.append)
    try:
        eng = served.engine
        assert eng.sparse and eng.cfg.first_dense_layers == 2 and eng.cfg.router_bias
        assert set(eng.k_pool) == {"kv", "idx", "swa"} and eng.k_pool["idx"].shape[-1] == 32
        # the rehearsal's head: 879 + 145 = 1024 tokens, eight whole blocks; 256 keys and a window of 129 bind
        assert len(eng.prefix_ids) == 1024 > eng.cfg.index_topk > eng.cfg.sliding_window
        seen = refcheck.compare(served, CONF, 3, said.append)
    finally:
        served.close()
        from tpu_voice_agent.services import prompts

        prompts.set_site_context("")
    ref = mf.load_code("reference", CONF["reference"])
    assert [c["reference"] for c in seen] == ["dots3_decoder"] and seen[0]["ok"]
    assert seen[0]["rel_err"] <= ref.TOLERANCE < seen[0]["control"]
    assert any("reference dots3_decoder:" in line and line.endswith("-> ok") for line in said)


def test_a_program_without_the_model_s_fields_is_refused_before_anything_is_built(monkeypatch):
    """What the PARENT of PR 43 does with this cell: the builder's typed exit."""
    from benchmark.builders import dots3_stack

    monkeypatch.setattr(dots3_stack, "NEEDS", dots3_stack.NEEDS + ("a_field_no_program_has",))
    with pytest.raises(SystemExit, match="REFUSED: this program's LlamaConfig has no"):
        dots3_stack.build(CONF, True, lambda line: None)


# ---- the file's byte arithmetic and the floors (lib/peaks_dots3.py, readers/roofline_dots3.py)


def test_the_file_s_byte_arithmetic_is_the_yardstick_s():
    from benchmark.lib import peaks_dots3 as pkd

    full = (5120 * 1024 + 1024 * 128 * 192 + 5120 * 576 + 512 * 128 * 256 + 128 * 128 * 5120 + 5120 * 128
            + 1024 * 64 * 128 + 5120 * 128 + 5120 * 64)
    swa = 5120 * 1024 + 1024 * 64 * 256 + 5120 * 1088 + 1024 * 64 * 320 + 64 * 128 * 5120 + 5120 * 64
    assert pkd.attention_params(MODEL, "full") == full == 144_048_128
    assert pkd.attention_params(MODEL, "sliding") == swa == 90_832_896
    assert pkd.indexer_params(MODEL) == 9_371_648 and pkd.expert_params(MODEL) == EXPERT == 23_592_960
    # the issue's 925 / 872 / 356 MB layers and 7.44 GB of them, as ``reduced_why`` states
    routed_f, routed_s, dense0 = (pkd.layer_bytes(MODEL, 1, "full", True), pkd.layer_bytes(MODEL, 1, "sliding", True),
                                  pkd.layer_bytes(MODEL, 1, "full", False))
    assert routed_f == full + 33 * EXPERT + 5120 * 256 * 2 and routed_s - swa == routed_f - full
    assert [round(b / 1e6) for b in (routed_f, routed_s, dense0)] == [925, 872, 356]
    assert round((dense0 + 2 * routed_f + 6 * routed_s) / 1e9, 2) == 7.44
    for said in ("925 MB", "872 MB", "356 MB", "7.44 GB", "144.05 M", "90.83 M"):
        assert said in CONF["reduced_why"]["num_hidden_layers"]
    # 1408 B a token a full layer, 2176 B a sliding one; padded to lane tiles 1536 / 2304: 0.62 GB of pool
    assert pkd.cache_bytes_per_token(MODEL) == {"full": 1408, "sliding": 2176}
    s = CONF["serving"]
    assert round(s["pool_blocks"] * s["block_size"] * (3 * 1536 + 6 * 2304) / 1e9, 2) == 0.62
    quant, plain = pkd.streamed_params(MODEL)
    assert quant == 3 * full + 6 * swa + 3 * 5120 * 13824 + 8 * EXPERT + 19008 * 5120
    assert plain == 8 * 5120 * 256


def test_the_floor_counts_selected_keys_window_keys_and_held_experts_touched():
    from benchmark.lib import peaks_dots3 as pkd

    sel = 3 * 45 * 2048  # 45 real positions, three full layers
    assert pkd.selected_bytes(MODEL, sel) == sel * 576 * 2
    assert pkd.selected_flops(MODEL, sel) == sel * 128 * 2 * (576 + 512)
    vis = 3 * 45 * 8400
    assert pkd.indexer_flops(MODEL, vis) == vis * 64 * 128 * 2
    assert pkd.indexer_bytes(MODEL, ctx=8400) == 3 * 8400 * 128 * 2
    # a live row's window and its own positions, never its context
    assert pkd.window_keys(MODEL, rows=32, positions=45, ctx=8400) == 6 * 32 * (512 + 45 / 32)
    assert pkd.window_keys(MODEL, rows=32, positions=45, ctx=100) == 6 * 32 * 100
    assert pkd.window_bytes(MODEL, 32, 45, 8400) == pkd.window_keys(MODEL, 32, 45, 8400) * 1088 * 2
    assert pkd.window_flops(MODEL, positions=45, ctx=8400) == 6 * 45 * 64 * 513 * 2 * (1088 + 1024)
    assert pkd.expert_bytes(MODEL, 1, touched=8 * 24) == 8 * 24 * EXPERT
    assert pkd.expert_flops(MODEL, local_rows=8 * 45) == 8 * 45 * 2 * EXPERT
    few = pkd.forward_bytes(MODEL, 1, 32, 45, 8400, touched=8 * 10, keys_selected=sel)
    all_ = pkd.forward_bytes(MODEL, 1, 32, 45, 8400, touched=8 * 32, keys_selected=sel)
    assert all_ - few == 8 * 22 * EXPERT
    # the selection, not the context, sets attention's bytes: four times the keys visible, the same floor
    assert pkd.forward_bytes(MODEL, 1, 32, 45, 8400, 0, sel) - pkd.forward_bytes(MODEL, 1, 32, 45, 8400, 0, 0) \
        == sel * 1152
    # the head's FLOPs on ONE position a row
    base = pkd.forward_flops(MODEL, 32, 45, 8400, 0, sel, vis)
    assert pkd.forward_flops(MODEL, 33, 45, 8400, 0, sel, vis) - base == 2 * 19008 * 5120
    floor, roof = pkd.selected_attention_floor_s(MODEL, V5E, sel)
    # 128 heads share a key's 1152 bytes: 242 FLOPs a byte, v5e's ridge (240.5) — the dots, by half a percent
    assert roof == "flops" and floor == sel * 128 * 2 * 1088 / 197e12 > sel * 1152 / 819e9 > 0.99 * floor
    floor, roof = pkd.indexer_floor_s(MODEL, V5E, 8400, vis)
    assert roof == "flops" and floor == vis * 64 * 128 * 2 / 197e12
    floor, roof = pkd.grouped_matmul_floor_s(MODEL, V5E, 1, touched=8 * 24, local_rows=8 * 45)
    assert roof == "bytes" and floor == 8 * 24 * EXPERT / 819e9


def test_a_perfect_kernel_reads_100_percent_and_a_program_without_the_counters_reads_nothing(monkeypatch):
    from benchmark.lib import peaks_dots3 as pkd
    from benchmark.readers import roofline
    from benchmark.readers import roofline_dots3 as rd

    fwds, sel, vis = 16, 3 * 45 * 2048, 3 * 45 * 8400
    n = {"steps": [], "rows": 32.0, "context": 8400.0, "positions": 45.0, "common_row_blocks": 0.0,
         "block_size": 128, "live": 32.0, "common": 8192.0}
    perfect = {"sparse_latent_attention": pkd.selected_attention_floor_s(MODEL, V5E, sel)[0],
               "indexer_scores": pkd.indexer_floor_s(MODEL, V5E, 8400.0, vis)[0],
               "window_latent_attention": pkd.window_attention_floor_s(MODEL, V5E, 32.0, 45.0, 8400.0)[0],
               "grouped_matmul": pkd.grouped_matmul_floor_s(MODEL, V5E, 1, 8 * 24, 8 * 45)[0]}
    monkeypatch.setattr(roofline, "run_trace", lambda ctx: object())
    monkeypatch.setattr(rd, "needed", lambda ctx: n)
    monkeypatch.setattr(roofline, "scope_ns", lambda plane, scopes, program: {
        "ns": perfect.get((scopes or [None])[0], 0) * 1e9 * fwds, "program_ns": 0.030 * 1e9 * fwds, "forwards": fwds})
    counters = {"scheduler.forwards": 100.0, "moe.experts_touched": 100.0 * 8 * 24, "moe.local_rows": 100.0 * 8 * 45,
                "attn.keys_selected": 100.0 * sel, "attn.keys_visible": 100.0 * vis}
    ctx = {"counters": counters, "peaks": V5E, "model": MODEL, "serving": {"quant": "int8", "fast_forward": 8}}
    for what in ("sparse_attn_roofline", "indexer_roofline", "window_attn_roofline", "grouped_matmul_roofline"):
        assert abs(rd.read(ctx, what) - 100.0) < 1e-9, what
    assert 0 < rd.read(ctx, "program_roofline") < 100.0
    # the parent of PR 43, every model without an indexer, a CPU rehearsal: nothing, and no raise
    for lacking in ("attn.keys_selected", "attn.keys_visible", "moe.local_rows"):
        parent = dict(ctx, counters={k: v for k, v in counters.items() if k != lacking})
        assert [rd.read(parent, w) for w in ("sparse_attn_roofline", "program_roofline", "step_mfu")] == [None] * 3
    assert rd.read(dict(ctx, peaks=None), "indexer_roofline") is None
    assert rd.read(dict(ctx, model={"hidden_size": 4096}), "program_roofline") is None
    with pytest.raises(ValueError, match="unknown quantity"):
        rd.read(ctx, "no_such_share")
