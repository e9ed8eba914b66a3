"""The SmallThinker reference and yardstick, reached as the harness reaches
them: the configuration file against the catalog's numbers, the arithmetic of
the cut (ISSUE 50's Motivation) against ``lib/peaks_smallthinker.py``, the
floors on hand-made counts, the router's gates by hand, the reader on a
program that has none of it, and the manifest valid with the cell in every
list it joined."""

import json

import jax.numpy as jnp
import numpy as np

from benchmark.lib import manifest as mf
from benchmark.lib import peaks_smallthinker as pks
from benchmark.readers import roofline_smallthinker as reader

NAME, CELL = "smallthinker-21b-a3b-int8", "smallthinker_pagemap_flood"
CONF = mf.load_json(f"benchmark/configs/{NAME}.json")
PEAKS = {"bytes_per_s": 819e9, "flops_per_s": 197e12}


def test_the_file_holds_the_catalogs_numbers_but_for_the_one_reduced_key():
    entry = next(c for c in mf.load_manifest()["configs"] if c["name"] == NAME)
    assert entry["reduced"] == ["num_hidden_layers"] == list(CONF["reduced_why"])
    assert entry["source"] == CONF["source"]
    assert (CONF["num_hidden_layers"], CONF["num_hidden_layers_published"], CONF["stage"], CONF["stages"]) == (24, 52, 0, 2)
    widths = {"hidden_size": 2560, "num_attention_heads": 28, "num_key_value_heads": 4, "head_dim": 128,
              "moe_ffn_hidden_size": 768, "moe_num_primary_experts": 64, "moe_num_active_primary_experts": 6,
              "sliding_window_size": 4096, "vocab_size": 151936, "rope_theta": 1500000, "rms_norm_eps": 1e-6}
    assert {k: CONF[k] for k in widths} == widths
    assert CONF["rope_layout"] == CONF["sliding_window_layout"] == [0, 1, 1, 1] * 13
    assert CONF["layer_kinds"] == "".join("S" if one else "F" for one in CONF["rope_layout"][:24])
    assert CONF["left_out"] == "" and len(CONF["assumed"]) >= 8
    try:  # where the catalog is beside the guides: every key of its row, but the one
        rows = [json.loads(line) for line in open("/opt/skills/guides/model-configs/architectures.jsonl")]
    except OSError:
        return
    row = next(r for r in rows if r["source_url"] == CONF["source"])
    assert {k for k, v in row["config"].items() if CONF.get(k, "absent") != v} == {"num_hidden_layers"}


def test_the_peaks_are_the_issues_arithmetic():
    s = pks.dims(CONF)
    assert (s["n_full"], s["n_sliding"], s["E"], s["K"], s["nq"] // s["nkv"]) == (6, 18, 64, 6, 7)
    quant, plain = pks.layer_params(CONF)
    assert quant == 24 * (2560 * 3584 * 2 + 2560 * 512 * 2) == 24 * 20_971_520  # attention 20.97 M a layer
    assert plain == 24 * 2560 * 64 and round(plain / 24 / 1e6, 2) == 0.16  # the router, unquantised
    assert pks.expert_params(CONF) == 64 * 3 * 2560 * 768 == 377_487_360  # experts 377.49 M a layer
    held = pks.held_bytes(CONF, CONF["serving"]["pool_blocks"], CONF["serving"]["block_size"])
    layer_gb = (held["layers"] + held["router"]) / 24 / 1e9
    assert round(layer_gb, 3) == 0.399 and round(52 * layer_gb, 1) == 20.7  # the whole model passes one chip
    assert round(held["layers"] / 1e9 + held["router"] / 1e9, 2) == 9.57
    assert round(held["embedding"] / 1e9, 3) == 0.778 and round(held["head"] / 1e9, 3) == 0.389
    assert held["kv"] == 264 * 128 * 24 * 2048 and round(held["kv"] / 1e9, 2) == 1.66
    assert round(sum(held.values()) / 1e9, 1) == 12.4
    assert str(round(layer_gb, 3)) in CONF["reduced_why"]["num_hidden_layers"]


def test_the_floors_on_hand_made_counts():
    # the issue's forward: 32 live rows ~150 positions past the 8192-token head, ~45 real positions,
    # ~50 of 64 experts touched a layer, every full layer's 64 head blocks in common
    rows, positions, ctx, head = 32.0, 45.0, 8192.0 + 150.0, 8192.0
    touched, assigned, common = 24 * 50.0, 45.0 * 6 * 24, 8192.0
    # a sliding layer NEEDS its windows' common part inside the head once and a row's own a row
    assert pks.window_positions(CONF, rows, ctx, head) == (4096 - 150) + 32 * 150
    assert pks.window_positions(CONF, rows, 3000.0, 0.0) == 32 * 3000  # no shared head: a row's context a row
    assert pks.window_positions(CONF, rows, 9000.0, 0.0) == 32 * 4096
    assert pks.kv_positions(CONF, rows, ctx, common, head) == 6 * (8192 + 32 * 150) + 18 * (3946 + 32 * 150)
    t, roof = pks.forward_floor_s(CONF, PEAKS, 1, rows, positions, ctx, touched, assigned, common, head)
    assert roof == "bytes" and 0.009 < t < 0.012  # ~0.9 GB shared + 7.1 GB of experts + 0.3 GB of K/V
    t_w, roof = pks.window_attention_floor_s(CONF, PEAKS, rows, positions, ctx, head)
    assert roof == "bytes" and abs(t_w - 2 * 18 * (3946 + 4800) * 4 * 128 * 2 / 819e9) < 1e-12
    walked = 2 * 18 * 32 * 33 * 128 * 4 * 128 * 2 / 819e9  # what the program reads: 33 blocks a row a layer
    assert 0.05 < t_w / walked < 0.08  # the windowed kernel cannot read over ~6.5 % of this floor's bytes
    t_g, roof = pks.grouped_matmul_floor_s(CONF, PEAKS, 1, touched, assigned)
    assert roof == "bytes" and abs(t_g - 1200 * 3 * 2560 * 768 / 819e9) < 1e-12
    assert pks.grouped_matmul_floor_s(CONF, PEAKS, 1, 1, 1e6)[1] == "flops"
    assert pks.window_attention_floor_s(CONF, PEAKS, 1, 1e4, ctx, head)[1] == "flops"


def test_the_references_gates_by_hand():
    ref = mf.load_code("reference", CONF["reference"])
    r = jnp.asarray([[2.0, 0.0, 1.0, -1.0], [0.0, 0.0, 3.0, 3.0]])
    got = np.asarray(ref.gates_of(r, 2))
    e = np.exp([2.0, 1.0])
    assert np.allclose(got[0], [e[0] / e.sum(), 0, e[1] / e.sum(), 0]) and np.allclose(got[1], [0, 0, 0.5, 0.5])
    loose = np.asarray(ref.gates_of(r, 2, renorm=False))
    z = np.exp([2.0, 0.0, 1.0, -1.0]).sum()
    assert np.allclose(loose[0], [np.exp(2.0) / z, 0, np.exp(1.0) / z, 0])
    assert {ref.SAMPLE, ref.CONTROL} == {"paged_decoder", "int4"} and 0 < ref.TOLERANCE < 0.2


def test_the_reader_is_silent_on_a_program_without_the_counters():
    ctx = {"counters": {"scheduler.forwards": 10.0, "moe.experts_touched": 5.0}, "steps": [], "records": [],
           "peaks": dict(PEAKS), "model": dict(CONF), "serving": CONF["serving"]}
    for what in ("program_roofline", "kernel_roofline", "window_attn_roofline", "step_mfu", "padding_share"):
        assert reader.read(ctx, what) is None
    other = {**ctx, "model": {"num_experts": 64}, "steps": [{"forwards": 1, "occupancy": 1, "tokens": 1}],
             "counters": {**ctx["counters"], "moe.assigned_rows": 1.0}}
    assert reader.read(other, "step_mfu") is None  # another model's configuration: nothing to read


def test_the_manifest_is_valid_with_the_cell_in_every_list_it_joined():
    m = mf.load_manifest()
    assert mf.validate(m) == []
    cell = mf.load_cell(m, CELL)
    assert cell["config"]["builder"] == "smallthinker_stack" and cell["traffic"]["generator"] == "parse_clients"
    assert len(m["workloads"]) == 9 and all(w["chips"] == 1 for w in m["workloads"])
    rate = next(e for e in m["end_to_end"] if e["name"] == "out_tokens_per_s")
    assert rate["workloads"][-1] == CELL
    mine = [p["name"] for p in m["per_layer"] if CELL in p.get("workloads", [])]
    assert len(mine) == 36 and sum(n.endswith("." + CELL) for n in mine) == 5
    for name in ("step_mfu.floods", "decode_program_roofline.floods", "grouped_matmul_roofline.floods",
                 "moe_experts_touched_per_layer.floods", "prefill_device_ms.floods",
                 f"window_attn_roofline.{CELL}", f"route_ahead_device_ms_per_forward.{CELL}",
                 f"attn_window_block_share.{CELL}", f"full_attn_device_ms_per_forward.{CELL}",
                 f"window_attn_device_ms_per_forward.{CELL}"):
        assert name in mine
    assert "shared_expert_device_ms_per_forward.floods" not in mine  # no shared expert: nothing to read
    assert len(m["per_layer"]) == 98 <= 128  # 93 + this cell's five
    for name in mine:  # every one resolves to a reader that is there
        metric = mf.load_layer_metric(name, CELL)
        assert mf.load_code("readers", metric["reader"]).read
