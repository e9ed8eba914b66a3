"""The benchmark of record (``BENCHMARK.json`` + ``benchmark/``) holds
together — in tier-1, so that a PR that breaks a cell's files, a builder's or
a reference's protocol, or the new configuration's path through the program
is refused here and not on the chip (PERF.md section 7, "Left out of PR 26").
``benchmark/tests/`` has the harness's own tests; these are the three that
guard the PROGRAM's side of the contract."""

import hashlib
import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import manifest as mf
from benchmark.lib import refcheck

M = mf.load_manifest()
CONFIGS = sorted(p.stem for p in (mf.BENCH_DIR / "configs").glob("*.json"))


def test_the_manifest_meets_the_static_rules():
    assert mf.validate(M) == []
    assert len(json.dumps(M)) < 64 * 1024


@pytest.mark.parametrize("cell", [w["name"] for w in M["workloads"]])
def test_every_cell_names_code_that_is_there(cell):
    c = mf.load_cell(M, cell)
    assert mf.code_problems(c) == []
    names = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2 and c["per_layer"]
    for m in c["per_layer"]:
        spec = mf.load_layer_metric(m["name"])
        assert {k: spec[k] for k in m} == m, m["name"]


@pytest.mark.parametrize("name", CONFIGS)
def test_every_configuration_names_a_builder_and_references_that_keep_the_protocol(name):
    conf = mf.load_json(f"benchmark/configs/{name}.json")
    if "decoder_config" in conf:
        conf["decoder"] = mf.load_json(f"benchmark/configs/{conf['decoder_config']}.json")
    assert hasattr(mf.load_code("builders", conf["builder"]), "build")
    for r in mf.references_of(conf):
        mod = mf.load_code("reference", r)
        assert mod.SAMPLE in refcheck.SAMPLERS and 0 < mod.TOLERANCE < 1 and mod.CONTROL
        assert callable(mod.logits)
    assert set(conf["rehearsal"]) - {"note", "serving"} <= set(conf.get("decoder", conf)) | set(conf)


def test_the_olmoe_configuration_keeps_every_published_number():
    """The catalog's ``config`` for OLMoE-1B-7B-0125-Instruct, key for key;
    ``reduced`` is empty, so none may differ."""
    published = {
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 1024, "max_position_embeddings": 4096, "model_type": "olmoe",
        "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "tie_word_embeddings": False, "vocab_size": 50304}
    entry = next(c for c in M["configs"] if c["name"] == "olmoe-1b-7b-0125-int8")
    conf = mf.load_json(entry["file"])
    assert entry["reduced"] == [] and entry["source"] == conf["source"]
    assert {k: conf[k] for k in published} == published
    mistral = mf.load_json("benchmark/configs/mistral-7b-v0.1-int8.json")
    same = lambda c: {k: v for k, v in c["serving"].items() if k != "weights_seed"}
    assert same(conf) == same(mistral)  # the same pool, slots, buckets and knobs
    assert not any("MOE" in k for k in conf["serving"]["env"])  # nothing selects the expert path
    cell = next(w for w in M["workloads"] if w["name"] == "olmoe_flood")
    assert (cell["traffic"], cell["chips"]) == ("parse_flood", 1)
    traffic = (mf.BENCH_DIR / "traffic" / "parse_flood.json").read_bytes()
    assert hashlib.sha256(traffic).hexdigest() == \
        "b2a73b8466d07e9a8afd018eb5b88e4276a43abdf13e969b6a4efb276f28eacd"


def test_the_olmoe_cells_cpu_rehearsal_reaches_ok():
    """``benchmark/run.py`` on the CPU at the rehearsal's widths: the builder
    serves the routed decoder through ``brain._wrap_batched`` ->
    ``ContinuousBatcher`` -> ``PagedDecodeEngine`` with no environment
    variable choosing the expert path, every ``/parse`` is a plan, and the
    comparison with the plain reference ends ``-> ok``."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "olmoe_flood", "--seed", "2147483651",
         "--seconds", "8", "--trace", "0"],  # 3 until PR 32: beside five busy workers no plan ended inside 3 s
        cwd=mf.ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    ref_line = next(ln for ln in out.stdout.splitlines() if "reference olmoe_decoder:" in ln)
    assert ref_line.endswith("-> ok"), ref_line
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["correct"] is False and result["device"]["platform"] == "cpu"  # a rehearsal is never a result
    assert {"setup_s", "out_tokens_per_s"} <= set(result["metrics"])


def test_the_phi4flash_configuration_keeps_every_published_number():
    """The catalog's ``config`` for Phi-4-mini-flash-reasoning, key for key;
    ``reduced`` is empty, so none may differ; the sizes the source does not
    carry are keys of their own and listed under ``assumed``."""
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 10240,
        "layer_norm_eps": 1e-05, "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40, "num_hidden_layers": 32,
        "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False, "vocab_size": 200064}
    entry = next(c for c in M["configs"] if c["name"] == "phi-4-mini-flash-reasoning-int8")
    conf = mf.load_json(entry["file"])
    assert entry["reduced"] == [] and entry["source"] == conf["source"]
    assert {k: conf[k] for k in published} == published
    assumed = " ".join(conf["assumed"])
    assert all(k in assumed and k in conf for k in ("ssm_d_inner", "ssm_d_state", "ssm_d_conv", "ssm_dt_rank"))
    mistral = mf.load_json("benchmark/configs/mistral-7b-v0.1-int8.json")
    same = lambda c: {k: v for k, v in c["serving"].items() if k != "weights_seed"}
    assert same(conf) == same(mistral)  # the same pool, slots, buckets and knobs
    cell = next(w for w in M["workloads"] if w["name"] == "phi4flash_flood")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (entry["name"], "parse_flood", 1)
    rate = next(m for m in M["end_to_end"] if m["name"] == "out_tokens_per_s")
    assert "phi4flash_flood" in rate["workloads"] and rate["bound"] == 0.015  # later cells append


def test_the_phi4flash_cells_cpu_rehearsal_reaches_ok():
    """``benchmark/run.py`` on the CPU at the rehearsal's widths: the builder
    serves the hybrid decoder through ``brain._wrap_batched`` ->
    ``ContinuousBatcher`` -> ``PagedDecodeEngine`` with no entry point, knob
    or environment variable of its own, no ``/parse`` fails, and the
    comparison with the plain reference ends ``-> ok``."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "phi4flash_flood", "--seed", "3000000017",
         "--seconds", "4", "--trace", "0"],
        cwd=mf.ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    ref_line = next(ln for ln in out.stdout.splitlines() if "reference sambay_decoder:" in ln)
    assert ref_line.endswith("-> ok"), ref_line
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["failed"] == 0  # a plan of these seeded weights runs ~240 tokens: none need end inside 4 s
    assert result["correct"] is False and result["device"]["platform"] == "cpu"  # a rehearsal is never a result
    assert {"setup_s", "out_tokens_per_s"} <= set(result["metrics"])  # the layers' counters: tests/test_hybrid_decoder.py
