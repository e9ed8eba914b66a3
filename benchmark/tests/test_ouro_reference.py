"""The Ouro reference and yardstick, reached as the harness reaches them: the
configuration file against the catalog's numbers, ISSUE 57's arithmetic against
``lib/peaks_looped.py`` (the 2.77 GB, the 1 572 864 B a token, the 55-block
pool, a forward's needed bytes at 8 rows), the published selection by hand, the
reader on a program that has none of it, the manifest valid with the cell in
every list it joined, the reference's control above its tolerance at the
rehearsal's widths and the CPU rehearsal of the cell to ``-> ok``."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import manifest as mf
from benchmark.lib import peaks as pk
from benchmark.lib import peaks_looped as pkl
from benchmark.readers import roofline_looped as reader

NAME, CELL = "ouro-2.6b-int8", "ouro_flood"
CONF = mf.load_json(f"benchmark/configs/{NAME}.json")
PEAKS = {"bytes_per_s": 819e9, "flops_per_s": 197e12}
OWN = ("loop_passes_per_forward", "loop_exit_last_share", "kv_write_device_ms_per_forward",
       "loop_exit_device_ms_per_forward")


def test_the_file_holds_every_number_of_the_catalog_and_reduces_none():
    entry = next(c for c in mf.load_manifest()["configs"] if c["name"] == NAME)
    assert entry["reduced"] == [] and entry["source"] == CONF["source"]
    widths = {"hidden_size": 2048, "intermediate_size": 5632, "num_hidden_layers": 48,
              "num_attention_heads": 16, "num_key_value_heads": 16, "head_dim": 128,
              "vocab_size": 49152, "total_ut_steps": 4, "early_exit_threshold": 1,
              "rope_theta": 1000000, "rms_norm_eps": 1e-06, "tie_word_embeddings": False}
    assert {k: CONF[k] for k in widths} == widths and len(CONF["assumed"]) >= 8
    assert (CONF["builder"], CONF["reference"]) == ("ouro_stack", "ouro_decoder")
    try:  # where the catalog is beside the guides: every key of its row
        rows = [json.loads(line) for line in open("/opt/skills/guides/model-configs/architectures.jsonl")]
    except OSError:
        return
    row = next(r for r in rows if r["source_url"] == CONF["source"])
    assert {k for k, v in row["config"].items() if CONF.get(k, "absent") != v} == set()
    mistral = mf.load_json("benchmark/configs/mistral-7b-v0.1-int8.json")["serving"]
    own = ("weights_seed", "batch_slots", "pool_blocks")
    assert {k: v for k, v in CONF["serving"].items() if k not in own} == \
        {k: v for k, v in mistral.items() if k not in own}


def test_the_peaks_are_the_issues_arithmetic():
    s = pkl.dims(CONF)
    assert (s["U"], s["L"], s["planes"], s["nq"], s["nkv"], s["hd"]) == (4, 48, 192, 16, 16, 128)
    quant, plain = pkl.layer_params(CONF)
    assert quant == 48 * (16_777_216 + 34_603_008) and round(quant / 48 / 1e6, 2) == 51.38
    assert round(quant / 1e9, 3) == 2.466 and plain == 48 * 4 * 2048
    assert pkl.token_bytes(CONF) == 192 * 8192 == 1_572_864 == 12 * 131072  # twelve times Mistral's
    serving = CONF["serving"]
    # the FULL reservation, 55 blocks; the file stands at 47 = 6 + 8 x 5 + 1 (its ``assumed`` has why)
    assert 879 // 128 + serving["batch_slots"] * 6 + 1 == 55
    assert serving["pool_blocks"] == 879 // 128 + serving["batch_slots"] * 5 + 1 == 47
    assert round(pkl.held_bytes(CONF, 47, 128)["kv"] / 1e9, 2) == 9.46
    held = pkl.held_bytes(CONF, 55, serving["block_size"])
    assert round(held["embedding"] / 1e9, 3) == 0.201 and round(held["head"] / 1e9, 3) == 0.101
    assert round((held["layers"] + held["embedding"] + held["head"]) / 1e9, 2) == 2.77
    assert held["kv"] == 55 * 201_326_592 and round(held["kv"] / 1e9, 2) == 11.07
    assert round(sum(held.values()) / 1e9, 2) == 13.84  # 86 % of 16 GB; 12 slots (79 blocks) would be 15.9


def test_a_forwards_needed_bytes_and_flops_on_hand_made_counts():
    # the issue's forward: 8 live rows ~216 positions past the 6 common blocks, ~11 real positions
    rows, positions, ctx, common = 8.0, 11.0, 768.0 + 216.0, 768.0
    quant, plain = pkl.layer_params(CONF)
    weights = 4 * (quant + 2 * plain) + 49152 * 2048
    assert round(weights / 1e9, 2) == 9.97  # the layers four times, the head once
    kv = pkl.token_bytes(CONF) * (768 + 8 * 216)
    assert pkl.kv_positions(CONF, rows, ctx, common) == 192 * (768 + 8 * 216)
    assert round(pkl.token_bytes(CONF) * 768 / 1e9, 2) == 1.21 and round(kv / 1e9, 1) == 3.9
    assert pkl.forward_bytes(CONF, 1, rows, ctx, common=common) == weights + kv
    t, roof = pkl.forward_floor_s(CONF, PEAKS, 1, rows, positions, ctx, common)
    assert roof == "bytes" and abs(t - (weights + kv) / 819e9) < 1e-12 and 0.016 < t < 0.018
    flops = positions * (4 * 2 * quant + 4 * 16 * 128 * 192 * ctx) + rows * 2 * 49152 * 2048
    assert pkl.forward_flops(CONF, rows, positions, ctx) == flops
    assert pkl.forward_floor_s(CONF, PEAKS, 1, 1.0, 1e5, 8.0)[1] == "flops"
    # against the dense floor of lib/peaks.py on the same keys: the weights once, 48 planes
    dense = pk.forward_bytes(CONF, 1, rows, ctx, common=common)
    assert dense == quant + 49152 * 2048 + kv / 4 and pkl.forward_bytes(CONF, 1, rows, ctx, common=common) > 3.5 * dense


def test_the_references_selection_by_hand():
    ref = mf.load_code("reference", CONF["reference"])
    states = jnp.arange(3 * 2 * 1, dtype=jnp.float32).reshape(3, 2, 1)  # (U, T, d): the value IS 2 u + t
    lams = jnp.asarray([[0.5, 0.1], [0.5, 0.2], [0.9, 0.9]])
    # p = [.5, .25, .25] and [.1, .18, .72]; cumulated [.5, .75, 1.] and [.1, .28, 1.]
    for threshold, want in ((0.5, [0, 2]), (0.7, [1, 2]), (0.2, [0, 1]), (1.0, [2, 2]), (0.0, [0, 0])):
        s, t = ref.select(states, lams, threshold)
        assert np.asarray(t).tolist() == want
        assert np.asarray(s)[:, 0].tolist() == [2 * u + i for i, u in enumerate(want)]
    s, t = ref.select(states, lams * 0.0, 1.5)  # nothing reaches it: the last pass
    assert np.asarray(t).tolist() == [2, 2]
    assert {ref.SAMPLE, ref.CONTROL} == {"paged_decoder", "int4"} and 0 < ref.TOLERANCE < 0.2


def test_the_reader_is_silent_on_a_program_without_it_and_reads_one_that_has_it():
    ctx = {"counters": {"scheduler.forwards": 10.0}, "steps": [], "records": [], "peaks": dict(PEAKS),
           "model": dict(CONF), "serving": CONF["serving"], "window_s": 1.0}
    for what in ("program_roofline", "step_mfu"):
        assert reader.read(ctx, what) is None  # no step ledger
    steps = [{"forwards": 10, "occupancy": 8, "tokens": 110}]
    other = {**ctx, "steps": steps, "model": {"hidden_size": 4096}}
    assert reader.read(other, "step_mfu") is None  # another model's configuration
    assert reader.read({**ctx, "steps": steps, "peaks": None}, "step_mfu") is None  # a CPU rehearsal
    live = {**ctx, "steps": steps, "prefix_tokens": 879, "tokens_per_request": 100.0}
    got = reader.read(live, "step_mfu")
    want = 100.0 * pkl.forward_flops(CONF, 8.0, 11.0, 929.0) * 10 / 197e12
    assert abs(got - want) < 1e-9 and 0 < got < 100
    assert reader.read(live, "program_roofline") is None  # no trace: nothing to divide by


def test_the_manifest_is_valid_with_the_cell_in_every_list_it_joined():
    m = mf.load_manifest()
    assert mf.validate(m) == []
    cell = mf.load_cell(m, CELL)
    assert cell["config"]["builder"] == "ouro_stack" and cell["traffic"]["generator"] == "parse_clients"
    assert cell["cell"] == {"name": CELL, "config": NAME, "traffic": "parse_flood", "chips": 1,
                            "why": cell["cell"]["why"]}
    assert mf.code_problems(cell) == []
    rate = next(e for e in m["end_to_end"] if e["name"] == "out_tokens_per_s")
    assert CELL in rate["workloads"] and all(w["chips"] == 1 for w in m["workloads"])
    mine = [p["name"] for p in m["per_layer"] if CELL in p.get("workloads", [])]
    assert [n for n in mine if n.endswith("." + CELL)] == [f"{n}.{CELL}" for n in OWN]
    for name in ("step_mfu.floods", "decode_program_roofline.floods", "attn_device_ms_per_forward.floods",
                 "ffn_device_ms_per_forward.floods", "prefill_device_ms.floods", "device_idle_share.floods"):
        assert name in mine
    for name in ("grouped_matmul_roofline.floods", "moe_experts_touched_per_layer.floods",
                 "shared_expert_device_ms_per_forward.floods", "admit_batched_share.floods"):
        assert name not in mine  # no expert, and 8 slots group no admission: nothing to read
    assert len(m["per_layer"]) <= 128
    for name in mine:  # every one resolves to a reader that is there
        metric = mf.load_layer_metric(name, CELL)
        assert callable(mf.load_code("readers", metric["reader"]).read)
        if name.endswith("." + CELL):
            assert metric["moves"] == "out_tokens_per_s" and metric["workloads"] == [CELL]
    assert mf.load_layer_metric("decode_program_roofline.floods", CELL)["reader"] == "roofline_looped"
    assert mf.load_layer_metric("step_mfu.floods", CELL)["reader"] == "roofline_looped"
    assert mf.load_layer_metric("decode_program_roofline.floods", "parse_flood")["reader"] == "roofline"


def test_the_references_control_lands_above_its_tolerance_at_the_rehearsals_widths():
    """Seeded weights by the builder's own recipe at the rehearsal's widths,
    float32 against itself re-quantised to int4: the control is another model."""
    from benchmark.builders import ouro_stack, parse_stack

    ref = mf.load_code("reference", CONF["reference"])
    model, serving = parse_stack.as_run(CONF, True)
    params = ouro_stack.make_params(ouro_stack.llama_config(model, serving), 23)
    assert params["exit_gate"]["w"].dtype == jnp.float32 and "attn_post_norm" in params["layers"]
    toks = [int(t) for t in jax.random.randint(jax.random.key(2), (40,), 0, model["vocab_size"])]
    sample = {"tokens": toks, "rows": 13}
    want = np.asarray(ref.logits(params, model, sample))
    ctrl = np.asarray(ref.logits(params, model, sample, control=True))
    assert want.shape == (13, model["vocab_size"]) and np.isfinite(want).all()
    rel = (np.abs(ctrl - want).max(-1) / np.abs(want).max(-1)).max()
    assert rel > 2 * ref.TOLERANCE
    _, picked = ref.forward(params, toks, model, last=13, picked=True)
    assert np.asarray(picked).tolist() == [model["total_ut_steps"] - 1] * 13  # the published threshold: the last pass


def test_the_cells_cpu_rehearsal_runs_to_ok():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", str(2**31 + 57),
                        "--seconds", "3", "--trace", "0"], cwd=mf.ROOT, env=env, capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["device"]["platform"] == "cpu" and last["correct"] is False and last["failed"] == 0
    assert {"setup_s", "out_tokens_per_s"} <= set(last["metrics"])
    (line,) = [ln for ln in p.stdout.splitlines() if ln.startswith("[benchmark] reference ")]
    assert line.startswith("[benchmark] reference ouro_decoder: ") and line.endswith("-> ok"), line
