"""The Nemotron-H reference, reached as the harness reaches it: a hand-written
two-layer case of its equations, the configuration file against the catalog's
numbers and its own ``reduced_why`` arithmetic, ``lib/peaks_nemotron_h.py``
against that arithmetic and on hand-made counts, the reader on a program that
has none of it, and the manifest valid with the cell in every list it joined."""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import manifest as mf
from benchmark.lib import peaks_nemotron_h as pkn
from benchmark.readers import roofline_nemotron_h as reader

NAME, CELL = "nemotron-3-super-120b-a12b-int8", "nemotron3super_flood"
CONF = mf.load_json(f"benchmark/configs/{NAME}.json")
F32 = np.float32


def test_the_file_holds_the_catalogs_numbers_but_for_the_three_reduced_keys():
    entry = next(c for c in mf.load_manifest()["configs"] if c["name"] == NAME)
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert set(CONF["reduced_why"]) == set(entry["reduced"]) | {"arithmetic"}
    assert (CONF["num_hidden_layers_published"], CONF["n_routed_experts_published"],
            CONF["vocab_size_published"], CONF["chips_sharing_a_layer"], CONF["first_expert"]) == (88, 512, 131072, 4, 0)
    assert CONF["n_routed_experts"] * 4 == 512 and CONF["vocab_size"] * 4 == 131072
    # every published width, as the source has it
    widths = {"hidden_size": 4096, "mamba_num_heads": 128, "mamba_head_dim": 64, "n_groups": 8,
              "ssm_state_size": 128, "conv_kernel": 4, "num_attention_heads": 32, "num_key_value_heads": 2,
              "head_dim": 128, "num_experts_per_tok": 22, "routed_scaling_factor": 5, "moe_latent_size": 1024,
              "moe_intermediate_size": 2688, "moe_shared_expert_intermediate_size": 5376}
    assert {k: CONF[k] for k in widths} == widths
    pattern = CONF["hybrid_override_pattern"]
    assert len(pattern) == 88 and pattern[:22] == "MEMEMEM*EMEMEMEM*EMEME"
    try:  # where the catalog is beside the guides: every number of its row, but the three
        rows = [json.loads(l) for l in open("/opt/skills/guides/model-configs/architectures.jsonl")]
    except OSError:
        return
    row = next(r for r in rows if r["source_url"] == CONF["source"])
    differs = {k for k, v in row["config"].items() if CONF.get(k, "absent") != v}
    assert differs == set(entry["reduced"])


def test_the_reference_on_a_hand_written_two_layer_case():
    """One Mamba-2 layer (one head of two channels, one group, two states, a
    convolution of width 2) and one expert layer (two experts, one a token),
    two positions, every number small enough to follow by hand."""
    ref = mf.load_code("reference", CONF["reference"])
    d = 2
    eye = np.eye(d, dtype=F32)
    # in_proj columns [z 2 | x 2, B 2, C 2 | dt 1]: z = u, x = u, B = C = (1, 0) * u0, dt = 0
    w_in = np.zeros((d, 9), F32)
    w_in[:, 0:2] = eye
    w_in[:, 2:4] = eye
    w_in[0, 4] = w_in[0, 6] = 1.0
    mamba = {"norm": np.ones((1, d), F32), "in_proj": w_in[None], "conv_w": np.asarray([[[0.0] * 6, [1.0] * 6]], F32),
             "conv_b": np.zeros((1, 6), F32), "dt_bias": np.zeros((1, 1), F32), "A_log": np.zeros((1, 1), F32),
             "D": np.ones((1, 1), F32), "gnorm": np.ones((1, d), F32), "out_proj": eye[None]}
    experts = {"norm": np.ones((1, d), F32), "router": np.asarray([[[4.0, -4.0], [-4.0, 4.0]]], F32),
               "router_bias": np.zeros((1, 2), F32), "fc1": eye[None], "fc2": eye[None],
               "moe_up": np.stack([eye, 2 * eye])[None], "moe_down": np.stack([eye, eye])[None],
               "shared_up": np.zeros((1, d, d), F32), "shared_down": np.zeros((1, d, d), F32)}
    params = jax.tree.map(jnp.asarray, {"embed": np.asarray([[1.0, 0.0], [0.0, 2.0]], F32), "final_norm": np.ones((d,), F32),
                                        "lm_head": eye, "mamba": mamba, "experts": experts})
    model = {"num_hidden_layers": 2, "hybrid_override_pattern": "ME", "mamba_num_heads": 1, "n_groups": 1,
             "ssm_state_size": 2, "num_attention_heads": 1, "num_key_value_heads": 1, "norm_eps": 0.0,
             "layer_norm_epsilon": 0.0, "num_experts_per_tok": 1, "routed_scaling_factor": 5,
             "norm_topk_prob": True, "first_expert": 0}
    got = np.asarray(ref.forward(params, [0, 1], last=2, **ref.model_kw(model)))

    def by_hand(tokens):
        silu = lambda v: v / (1 + np.exp(-v))
        rms = lambda v: v / np.sqrt(np.mean(v * v))
        x_seq = [np.asarray([1.0, 0.0]), np.asarray([0.0, 2.0])]
        s, out = np.zeros((2, 2)), []
        for t in tokens:
            e = x_seq[t]
            u = rms(e)
            z, xc = u, silu(u)  # the convolution's last tap is 1, the first 0: conv(x) = x
            b = c = silu(np.asarray([u[0], 0.0]))
            dt = np.log(2.0)  # softplus(0)
            s = np.exp(-dt) * s + dt * np.outer(xc, b)
            y = s @ c + xc
            o = rms(y * silu(z))
            x1 = e + o
            h = rms(x1)
            pick = int(np.argmax(1 / (1 + np.exp(-(h @ np.asarray([[4.0, -4.0], [-4.0, 4.0]]))))))
            routed = 5.0 * np.square(np.maximum(h * (1 + pick), 0))  # one pick: its gate renormalises to 1, x 5
            out.append(rms(x1 + routed))
        return np.stack(out)

    assert np.allclose(got, by_hand([0, 1]), atol=2e-5)


def test_the_peaks_are_the_files_arithmetic():
    k = pkn.kind_params(CONF)
    text = CONF["reduced_why"]["arithmetic"] + CONF["reduced_why"]["n_routed_experts"]
    said = lambda pat: float(re.search(pat, text).group(1))
    assert round((k["M"][0] + k["M"][1]) / 1e6, 2) == said(r"10 x ([\d.]+) M \(M:") == 109.64
    assert round((k["E"][0] + k["E"][1]) / 1e6, 2) == 54.53 and round(k["expert"] / 1e6, 3) == 5.505
    assert round((k["E"][0] + k["E"][1] + 128 * k["expert"]) / 1e6, 2) == said(r"= ([\d.]+) M$") == 759.17
    assert round(k["*"][0] / 1e6, 2) == said(r"2 x ([\d.]+) M \(\*") == 35.65
    s = pkn.dims(CONF)
    assert (s["nM"], s["nE"], s["nA"], s["E"], s["held"]) == (10, 10, 2, 512, 128)
    quant, plain = pkn.layer_params(CONF)
    whole = quant + plain + 10 * 128 * k["expert"]
    assert round(whole / 1e9, 2) == said(r"= ([\d.]+) GB; bf16 embedding") == 8.76
    assert pkn.state_bytes(CONF, 1) == 2 * 128 * 64 * 128 * 4 == 8388608
    assert pkn.expert_bytes(CONF, 1, 1) == 2 * 1024 * 2688


def test_the_floors_on_hand_made_counts():
    peaks = {"bytes_per_s": 819e9, "flops_per_s": 197e12}
    # the issue's forward: ~110 of 128 held experts touched a layer, 32 live rows, ~45 positions
    touched, local, moved = 10 * 110, 10 * 45 * 22 / 4, 32 * 10
    t, roof = pkn.forward_floor_s(CONF, peaks, 1, 32, 45, 950, touched, local, moved)
    assert roof == "bytes" and 0.012 < t < 0.014  # ~10.5 GB over 819 GB/s
    t_scan, roof = pkn.scan_floor_s(CONF, peaks, moved, 45)
    assert roof == "bytes" and abs(t_scan - 320 * 8388608 / 819e9) < 1e-9
    t_gmm, roof = pkn.grouped_matmul_floor_s(CONF, peaks, 1, touched, local)
    assert roof == "bytes" and abs(t_gmm - 1100 * 5505024 / 819e9) < 1e-9
    # enough positions and the recurrence's FLOPs bound the scan, enough rows the experts' theirs
    assert pkn.scan_floor_s(CONF, peaks, 10, 1e6)[1] == "flops"
    assert pkn.grouped_matmul_floor_s(CONF, peaks, 1, 1, 1e5)[1] == "flops"


def test_the_reader_is_silent_on_a_program_without_the_counters():
    ctx = {"counters": {"scheduler.forwards": 10.0, "moe.experts_touched": 5.0}, "steps": [], "records": [],
           "peaks": {"bytes_per_s": 1.0, "flops_per_s": 1.0}, "model": dict(CONF), "serving": CONF["serving"]}
    for what in ("program_roofline", "kernel_roofline", "scan_roofline", "step_mfu", "padding_share"):
        assert reader.read(ctx, what) is None


def test_the_manifest_is_valid_with_the_cell_in_every_list_it_joined():
    m = mf.load_manifest()
    assert mf.validate(m) == []
    cell = mf.load_cell(m, CELL)
    assert cell["config"]["builder"] == "nemotron_h_stack"
    assert len(m["workloads"]) == 8 and all(w["chips"] == 1 for w in m["workloads"])
    rate = next(e for e in m["end_to_end"] if e["name"] == "out_tokens_per_s")
    assert CELL in rate["workloads"]
    mine = [p["name"] for p in m["per_layer"] if CELL in p.get("workloads", [])]
    assert len(mine) == 37 and sum(n.endswith("." + CELL) for n in mine) == 6
    for name in ("step_mfu.floods", "decode_program_roofline.floods", "grouped_matmul_roofline.floods",
                 "moe_padding_share.floods", "shared_expert_device_ms_per_forward.floods",
                 f"ssd_scan_roofline.{CELL}", f"admit_state_restore_ms.{CELL}"):
        assert name in mine
    assert len(m["per_layer"]) == 92  # 86 + this cell's six
