"""The OLMo hybrid reference, reached as the harness reaches it: a hand-written
two-layer case of its equations, the configuration file against the catalog's
numbers and its own ``arithmetic``, ``lib/peaks_olmo_hybrid.py`` against that
arithmetic and on hand-made counts, the reader on a program that has none of
it, and the manifest valid with the cell in every list it joined."""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import manifest as mf
from benchmark.lib import peaks_olmo_hybrid as pko
from benchmark.readers import roofline_olmo_hybrid as reader

NAME, CELL = "olmo-hybrid-7b-int8", "olmohybrid_flood"
CONF = mf.load_json(f"benchmark/configs/{NAME}.json")
F32 = np.float32


def test_the_file_holds_every_number_of_the_catalog_and_reduces_none():
    entry = next(c for c in mf.load_manifest()["configs"] if c["name"] == NAME)
    assert entry["reduced"] == [] and entry["source"] == CONF["source"]
    widths = {"hidden_size": 3840, "intermediate_size": 11008, "num_hidden_layers": 32,
              "num_attention_heads": 30, "num_key_value_heads": 30, "vocab_size": 100352,
              "linear_num_key_heads": 30, "linear_num_value_heads": 30, "linear_key_head_dim": 96,
              "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
              "rms_norm_eps": 1e-06, "tie_word_embeddings": False}
    assert {k: CONF[k] for k in widths} == widths
    assert CONF["rope_parameters"] == {"rope_theta": None}
    kinds = "".join({"linear_attention": "L", "full_attention": "F"}[k] for k in CONF["layer_types"])
    assert kinds == CONF["layer_kinds"] == "LLLF" * 8
    try:  # where the catalog is beside the guides: every number of its row
        rows = [json.loads(l) for l in open("/opt/skills/guides/model-configs/architectures.jsonl")]
    except OSError:
        return
    row = next(r for r in rows if r["source_url"] == CONF["source"])
    assert {k for k, v in row["config"].items() if CONF.get(k, "absent") != v} == set()
    # the serving block is mistral-7b-v0.1-int8's, key for key but the seed
    mistral = mf.load_json("benchmark/configs/mistral-7b-v0.1-int8.json")["serving"]
    assert {k: v for k, v in CONF["serving"].items() if k != "weights_seed"} == \
        {k: v for k, v in mistral.items() if k != "weights_seed"}


def test_the_reference_on_a_hand_written_case():
    """One Gated-DeltaNet layer (one head, d_k = d_v = 2, a convolution of width
    2 whose last tap is 1, an MLP of zeros), two positions, every number small
    enough to follow by hand; a full layer behind it runs to finite logits."""
    ref = mf.load_code("reference", CONF["reference"])
    d = 2
    eye, zero = np.eye(d, dtype=F32), np.zeros((d, d), F32)
    mlp = {"mixer_norm": np.ones((1, d), F32), "mlp_norm": np.ones((1, d), F32),
           "w_gate": zero[None], "w_up": zero[None], "w_down": zero[None]}
    gdn = {"in_proj": np.concatenate([eye, eye, eye, eye], axis=1)[None],  # q = k = v = g = x
           "ab": np.zeros((1, d, 2), F32), "conv_w": np.asarray([[[0.0] * 6, [1.0] * 6]], F32),
           "dt_bias": np.zeros((1, 1), F32), "A_log": np.zeros((1, 1), F32),
           "onorm": np.ones((1, d), F32), "wo": eye[None], **mlp}
    attn = {"wqkv": np.concatenate([eye, eye, eye], axis=1)[None], "q_norm": np.ones((1, d), F32),
            "k_norm": np.ones((1, d), F32), "wo": eye[None], **mlp}
    params = jax.tree.map(jnp.asarray, {"embed": np.asarray([[1.0, 0.0], [0.0, 2.0]], F32),
                                        "final_norm": np.ones((d,), F32), "lm_head": eye,
                                        "gdn": gdn, "attn": attn})
    model = {"num_hidden_layers": 1, "layer_kinds": "LF", "linear_num_value_heads": 1,
             "linear_key_head_dim": 2, "linear_value_head_dim": 2, "linear_allow_neg_eigval": True,
             "num_attention_heads": 1, "num_key_value_heads": 1, "rms_norm_eps": 1e-12}
    got = np.asarray(ref.forward(params, [0, 1], last=2, **ref.model_kw(model)))

    silu = lambda v: v / (1 + np.exp(-v))
    rms = lambda v: v / np.sqrt(np.mean(v * v))
    unit = lambda v: v / np.sqrt(np.sum(v * v) + 1e-6)
    s, want = np.zeros((2, 2)), []
    for x in (np.asarray([1.0, 0.0]), np.asarray([0.0, 2.0])):
        c = silu(x)  # the convolution's last tap is 1, the first 0: conv(x) = x
        q, k, v = unit(c) * 2 ** -0.5, unit(c), c
        beta, g = 2 * 0.5, -np.log(2.0)  # sigmoid(0) doubled; -exp(0) softplus(0)
        s = np.exp(g) * s
        s = s + np.outer(k, beta * (v - s.T @ k))
        o = rms(s.T @ q) * silu(x)  # the norm, THEN the gate
        h = x + rms(o)  # W_o = 1, the norm on the mixer's OUTPUT; the MLP adds norm(0) = 0
        want.append(rms(h))  # the final norm, the head = 1
    assert np.allclose(got, np.stack(want), atol=2e-5)
    both = np.asarray(ref.forward(params, [0, 1], last=2, **ref.model_kw({**model, "num_hidden_layers": 2})))
    assert both.shape == (2, 2) and np.isfinite(both).all() and not np.allclose(both, got)


def test_the_peaks_are_the_files_arithmetic():
    k = pko.kind_params(CONF)
    text = CONF["arithmetic"]
    said = lambda pat: float(re.search(pat, text).group(1))
    assert round(k["L"][0] / 1e6, 1) == said(r"L layer: ([\d.]+) M int8") == 215.3
    assert round(k["F"][0] / 1e6, 1) == said(r"F layer: ([\d.]+) M int8") == 185.8
    s = pko.dims(CONF)
    assert (s["nL"], s["nF"], s["hd"], s["H"], s["dk"], s["dv"]) == (24, 8, 128, 30, 96, 192)
    quant, _ = pko.layer_params(CONF)
    assert round(quant / 1e9, 2) == said(r"= ([\d.]+) GB int8 in the layers") == 6.65
    assert pko.state_bytes(CONF, 1) == 2 * 30 * 96 * 192 * 4 == 4423680
    assert round(32 * 24 * pko.state_bytes(CONF, 1) / 1e9, 2) == 3.40  # a third of the byte floor


def test_the_floors_on_hand_made_counts():
    peaks = {"bytes_per_s": 819e9, "flops_per_s": 197e12}
    # the issue's forward: 32 live rows, ~40 positions, the 879-token prefix with 768 positions in common
    moved = 32 * 24
    t, roof = pko.forward_floor_s(CONF, peaks, 1, 32, 40, 950, moved, 768)
    assert roof == "bytes" and 0.0130 < t < 0.0145  # ~11.3 GB over 819 GB/s
    by_hand = (6653214720 + 100352 * 3840 + 2 * 6948768 + moved * 4423680
               + 2 * 8 * (768 + 32 * (950 - 768)) * 30 * 128 * 2)
    assert pko.forward_bytes(CONF, 1, 32, 950, moved, common=768) == by_hand
    t_scan, roof = pko.scan_floor_s(CONF, peaks, moved, 40)
    assert roof == "bytes" and abs(t_scan - moved * 4423680 / 819e9) < 1e-9
    assert pko.scan_flops(CONF, 1) == 24 * 30 * 96 * 192 * 7
    # enough positions and the recurrence's FLOPs bound the scan
    assert pko.scan_floor_s(CONF, peaks, 24, 1e6)[1] == "flops"
    flops = pko.forward_flops(CONF, 32, 40, 950)
    assert flops == 40 * (2 * (6653214720 + 6948768) + 4 * 30 * 128 * 8 * 950) + pko.scan_flops(CONF, 40) \
        + 32 * 2 * 100352 * 3840


def test_the_reader_is_silent_on_a_program_without_the_counters():
    ctx = {"counters": {"scheduler.forwards": 10.0, "ssm.state_rows_moved": 5.0}, "steps": [], "records": [],
           "peaks": {"bytes_per_s": 1.0, "flops_per_s": 1.0}, "model": dict(CONF), "serving": CONF["serving"]}
    for what in ("program_roofline", "scan_roofline", "step_mfu"):
        assert reader.read(ctx, what) is None
    assert reader.read({**ctx, "counters": {}}, "scan_roofline") is None


def test_the_manifest_is_valid_with_the_cell_in_every_list_it_joined():
    m = mf.load_manifest()
    assert mf.validate(m) == []
    cell = mf.load_cell(m, CELL)
    assert cell["config"]["builder"] == "olmo_hybrid_stack" and cell["cell"]["traffic"] == "parse_flood"
    assert len(m["workloads"]) == 10 and all(w["chips"] == 1 for w in m["workloads"])
    rate = next(e for e in m["end_to_end"] if e["name"] == "out_tokens_per_s")
    assert rate["workloads"][-1] == CELL
    mine = [p["name"] for p in m["per_layer"] if CELL in p.get("workloads", [])]
    assert len(mine) == 30 and sum(n.endswith("." + CELL) for n in mine) == 5
    for name in ("step_mfu.floods", "decode_program_roofline.floods", "prefill_device_ms.floods",
                 f"gdn_scan_roofline.{CELL}", f"gdn_device_ms_per_forward.{CELL}",
                 f"gdn_proj_device_ms_per_forward.{CELL}", f"gdn_advance_share.{CELL}",
                 f"admit_state_restore_ms.{CELL}"):
        assert name in mine
    assert not any(n.startswith(("moe_", "expert_", "grouped_matmul_", "shared_expert_")) for n in mine)
    assert len(m["per_layer"]) == 107  # 102 + this cell's five
    assert mf.code_problems(cell) == []
