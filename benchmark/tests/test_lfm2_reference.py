"""The LFM2 reference and cell, reached as the harness reaches them: the
reference's own mechanisms against HAND-computed tiny cases (three taps, the
[B | C | u] order, the bias in the selection alone, the per-head norm ahead of
the rotation), its ``logits`` through the configuration's own keys, the file's
byte arithmetic and the floors of ``lib/peaks_lfm2.py`` against the numbers
ISSUE 64 was sized from, the readers on a perfect kernel and on a program
without the counters, the cell among the manifest's per-layer lists, and the
proof that the cell came as NEW files and APPENDED entries
(``data/lfm2_addition.json`` holds the parent's hashes)."""

import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import manifest as mf
from benchmark.lib import peaks_lfm2 as pkl
from benchmark.reference import decoder as dense_ref
from benchmark.reference import lfm2_decoder as ref

ROOT = Path(__file__).resolve().parents[2]
CELL = "lfm2_flood"
CONF = mf.load_json("benchmark/configs/lfm2-8b-a1b-int8.json")
MODEL = {k: v for k, v in CONF.items() if not isinstance(v, (dict, list))}
V5E = {"bytes_per_s": 819e9, "flops_per_s": 197e12}
OWN = ["shortconv_device_ms_per_forward", "shortconv_roofline", "conv_advance_share", "admit_state_restore_ms"]
NEW = ("lfm2", "benchmark/SHORTCONV.md", "benchmark/tools/shortconv_check.py")
F32 = jnp.float32
plain = lambda leaf: jnp.asarray(leaf, F32)  # ``dense`` of a float leaf


# ---------------------------------------------------------------- the reference, by hand


def test_three_taps_oldest_first_over_the_gated_input_with_zeros_before_position_zero():
    """d = 1: B = 2 z, C = z, u = z -> g = 2 z^2; taps (w0, w1, w2) = (100, 10, 1):
    c_t = 100 g_{t-2} + 10 g_{t-1} + g_t, g = 0 before position 0; out = C * c."""
    z = jnp.asarray([[1.0], [2.0], [3.0], [4.0]])
    w = {"in_proj": jnp.asarray([[2.0, 1.0, 1.0]]), "conv_w": jnp.asarray([[100.0], [10.0], [1.0]]),
         "out_proj": jnp.asarray([[1.0]])}
    g = [2.0, 8.0, 18.0, 32.0]
    c = [g[0], 10 * g[0] + g[1], 100 * g[0] + 10 * g[1] + g[2], 100 * g[1] + 10 * g[2] + g[3]]
    want = [zt * ct for zt, ct in zip([1.0, 2.0, 3.0, 4.0], c)]
    assert want == [2.0, 56.0, 894.0, 4048.0]
    assert np.allclose(np.asarray(ref.short_conv(z, w, plain))[:, 0], want)


def test_the_projection_splits_b_then_c_then_u():
    """Columns in THAT order: B and u meet BEFORE the taps, C behind them. With
    c_t = g_{t-1}: out_t = C_t * (B_{t-1} * u_{t-1}); a [B | u | C] reading
    gives u_t * (B_{t-1} * C_{t-1})."""
    d = 2
    z = jnp.asarray([[1.0, 2.0], [3.0, 1.0], [2.0, 2.0]])
    b, c, u = (jnp.asarray(m) for m in ([[1.0, 1.0], [0.0, 2.0]], [[3.0, 0.0], [1.0, 5.0]], [[7.0, 2.0], [0.0, 1.0]]))
    w = {"in_proj": jnp.concatenate([b, c, u], axis=1), "conv_w": jnp.asarray([[0.0, 0.0], [1.0, 1.0], [0.0, 0.0]]),
         "out_proj": jnp.eye(d)}
    g = (z @ b) * (z @ u)
    want = (z @ c) * jnp.concatenate([jnp.zeros((1, d)), g[:-1]])
    assert np.asarray(want).tolist() == [[0.0, 0.0], [70.0, 100.0], [504.0, 350.0]]  # (z C)_t * ((z B) * (z u))_{t-1}
    assert np.allclose(np.asarray(ref.short_conv(z, w, plain)), np.asarray(want))
    swapped = {**w, "in_proj": jnp.concatenate([b, u, c], axis=1)}
    assert not np.allclose(np.asarray(ref.short_conv(z, swapped, plain)), np.asarray(want))


def test_the_bias_is_in_the_selection_only_and_the_gates_carry_the_1e_6():
    """Scores s = sigmoid(0) = 0.5 on every expert but expert 2 (sigmoid(2)): the
    bias lifts experts 0 and 3 over it. The gates are s of the chosen over their
    sum + 1e-6 — 0.5 / (1 + 1e-6) each — and carry nothing of the bias."""
    h = jnp.asarray([[1.0, 0.0]])
    router = jnp.asarray([[0.0, 0.0, 2.0, 0.0], [0.0, 0.0, 0.0, 0.0]])
    bias = jnp.asarray([1.0, 0.0, 0.0, 0.9])
    gates = np.asarray(ref.gate_matrix(h, router, bias, top_k=2, scale=1.0))[0]
    assert gates[1] == gates[2] == 0.0 and np.allclose(gates[[0, 3]], 0.5 / (1.0 + 1e-6), rtol=1e-7)
    unbiased = np.asarray(ref.gate_matrix(h, router, jnp.zeros(4), top_k=2, scale=1.0))[0]
    assert unbiased[2] > 0.6 and unbiased[3] == 0.0  # without it expert 2 is chosen
    assert np.allclose(np.asarray(ref.gate_matrix(h, router, bias, top_k=2, scale=2.5))[0], 2.5 * gates)


def test_a_routed_layer_is_the_chosen_experts_sum_under_their_gates():
    ks = jax.random.split(jax.random.key(0), 5)
    h = jax.random.normal(ks[0], (5, 8), F32)
    w = {"router": jax.random.normal(ks[1], (8, 4), F32), "router_bias": jnp.asarray([0.3, -0.2, 0.0, 0.1]),
         "moe_gate": jax.random.normal(ks[2], (4, 8, 6), F32), "moe_up": jax.random.normal(ks[3], (4, 8, 6), F32),
         "moe_down": jax.random.normal(ks[4], (4, 6, 8), F32)}
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.routed_mlp(h, w, plain, top_k=2, scale=1.0))
        s = np.asarray(jax.nn.sigmoid(h @ w["router"]))
        want = np.zeros((5, 8), np.float32)
        for t in range(5):
            chosen = np.argsort(-(s[t] + np.asarray(w["router_bias"])))[:2]
            for e in chosen:
                y = (jax.nn.silu(h[t] @ w["moe_gate"][e]) * (h[t] @ w["moe_up"][e])) @ w["moe_down"][e]
                want[t] += s[t, e] / (s[t, chosen].sum() + 1e-6) * np.asarray(y)
    assert np.allclose(got, want, rtol=2e-5, atol=2e-5)


def test_q_and_k_are_normed_a_head_before_they_are_rotated():
    """One head of 4, T = 2: q = RMSNorm_4(z W_q; gain) THEN rope. With W_k = W_q
    and an identity value, position 1 attends (0, 1) with the scores of the
    normed and rotated vectors — computed here step by step."""
    z = jnp.asarray([[1.0, 2.0, 3.0, 4.0], [4.0, 3.0, 2.0, 1.0]])
    gain = jnp.asarray([1.0, 2.0, 0.5, 1.5])
    eye = jnp.eye(4)
    w = {"wqkv": jnp.concatenate([eye, eye, eye], axis=1), "q_norm": gain, "k_norm": gain, "wo": eye}
    pos = jnp.arange(2)
    got = np.asarray(ref.attention(z, pos, w, plain, nq=1, nkv=1, eps=1e-5, theta=100.0))
    n = z / jnp.sqrt(jnp.mean(z * z, axis=1, keepdims=True) + 1e-5) * gain
    r = np.asarray(dense_ref.rope(n[:, None, :], pos, 100.0))[:, 0]
    s = np.asarray([r[1] @ r[0], r[1] @ r[1]]) * 4 ** -0.5
    p = np.exp(s - s.max()) / np.exp(s - s.max()).sum()
    assert np.allclose(got[0], np.asarray(z[0]), atol=1e-5)  # position 0 sees itself alone
    assert np.allclose(got[1], p[0] * np.asarray(z[0]) + p[1] * np.asarray(z[1]), atol=1e-5)
    # the gain is applied BEFORE the rotation: behind it, it would scale other lanes' pairs
    late = np.asarray(dense_ref.rope((z / jnp.sqrt(jnp.mean(z * z, 1, keepdims=True) + 1e-5))[:, None, :], pos, 100.0))[:, 0] * np.asarray(gain)
    assert not np.allclose(late[1], r[1])


def test_the_reference_reads_each_rule_of_the_model_from_the_configuration_s_keys():
    """``logits`` on the rehearsal's widths equals the program's float32 forward,
    and every key it reads changes its answer."""
    from benchmark.builders import parse_stack
    from tpu_voice_agent.models import lfm2
    from tpu_voice_agent.models.llama import forward_paged, init_params
    from tpu_voice_agent.serve.paged import build_pools

    builder = mf.load_code("builders", CONF["builder"])
    model, serving = parse_stack.as_run(CONF, rehearsal=True)
    cfg = builder.llama_config(model, {**serving, "max_len": 256})
    assert (cfg.pattern, cfg.first_dense_layers, cfg.n_experts, cfg.top_k, cfg.head_dim) == ("CCFCFCC", 1, 8, 2, 16)
    params = init_params(cfg, jax.random.PRNGKey(1), dtype=F32)
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 40), 0, cfg.vocab_size)
    sample = {"tokens": [int(t) for t in toks[0]], "rows": 40}
    kp, vp = build_pools(lfm2.cache_spec(cfg), 6, 8, 2, zeros=lambda shape, dt: jnp.zeros(shape, F32))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(forward_paged(params, cfg, toks, jnp.arange(40, dtype=jnp.int32)[None], kp, vp,
                                        jnp.asarray([[1, 2, 3, 4, 5, 1]], jnp.int32), attn_impl="xla")[0][0])
    rel = lambda got: float(np.max(np.abs(np.asarray(got) - want)) / np.max(np.abs(want)))
    assert rel(ref.logits(params, model, sample)) < 2e-4
    for change in ({"num_experts_per_tok": 1}, {"rope_theta": 100}, {"norm_eps": 1e-1},
                   {"routed_scaling_factor": 2.0}, {"layer_kinds": "CCFCCCF"}):
        assert rel(ref.logits(params, {**model, **change}, sample)) > 1e-3, change
    assert rel(ref.logits(params, model, sample, control=True)) > 0.05  # int4 weights are another model


# ---------------------------------------------------------------- the file and the floors


def test_the_file_holds_the_catalog_s_numbers_and_nothing_is_reduced():
    manifest = mf.load_manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == "lfm2-8b-a1b-int8")
    assert entry["reduced"] == [] and CONF["left_out"] == "" and entry["source"] == CONF["source"]
    assert entry["source"] == "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
    published = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 7168,
                 "max_position_embeddings": 128000, "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
                 "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32, "num_dense_layers": 2,
                 "num_experts": 32, "num_experts_per_tok": 4, "num_hidden_layers": 24, "num_key_value_heads": 8,
                 "rope_theta": 1000000, "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
    assert {k: CONF[k] for k in published} == published
    kinds = ["conv", "conv", "full_attention"] + ["conv", "conv", "conv", "full_attention"] * 4 + ["conv", "conv", "full_attention", "conv", "conv"]
    assert CONF["layer_types"] == kinds and CONF["layer_types"].count("conv") == 18
    assert CONF["layer_kinds"] == "".join("C" if k == "conv" else "F" for k in kinds)
    mistral = mf.load_json("benchmark/configs/mistral-7b-v0.1-int8.json")["serving"]
    assert {**CONF["serving"], "weights_seed": 0} == {**mistral, "weights_seed": 0}  # key for key but the seed
    assert len(CONF["assumed"]) >= 8 and CONF["deployment"] and CONF["arithmetic"]
    r = CONF["rehearsal"]
    assert (r["num_experts"], r["num_experts_per_tok"], r["hidden_size"] // r["num_attention_heads"]) == (8, 2, 16)
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("lfm2-8b-a1b-int8", "parse_flood", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200


def test_the_byte_arithmetic_is_the_issues():
    """ISSUE 64's sizing, number for number: 16.78 M a convolution mixer, 10.49 M
    an attention mixer, 44.04 M a dense MLP, 11.01 M an expert and 352.3 M a
    routed layer's 32; 8.20 GB int8 in the layers; ~8.34 B parameters."""
    k, s = pkl.kind_params(MODEL), pkl.dims(MODEL)
    assert (s["nC"], s["nF"], s["nD"], s["nR"], s["hd"], s["K"]) == (18, 6, 2, 22, 64, 3)
    assert k["C"][0] == 2048 * 6144 + 2048 * 2048 == 16_777_216 and k["F"][0] == 10_485_760
    assert k["dense"][0] == 3 * 2048 * 7168 == 44_040_192 and k["expert"] == 3 * 2048 * 1792 == 11_010_048
    assert 32 * k["expert"] == 352_321_536
    layers = pkl.shared_params(MODEL)[0] + 22 * 32 * k["expert"]
    assert layers == 18 * 16_777_216 + 6 * 10_485_760 + 2 * 44_040_192 + 22 * 352_321_536
    assert round(layers / 1e9, 2) == 8.20 and round(pkl.model_params(MODEL) / 1e9, 2) == 8.34
    # K/V a token: 6 planes x 8 heads x 64 x 2 (K, V) x 2 B; a request's tails 147 KB
    assert 2 * s["nF"] * s["nkv"] * s["hd"] * 2 == 12288 and pkl.tail_bytes(MODEL, 18) == 2 * 147456


def test_the_floors_count_what_is_needed():
    """A forward of the flood: ~45 real positions, 23 live rows at a context of
    ~950 behind 879 common positions, every expert of every routed layer touched
    by 180 picks, 18 x 23 tails moved."""
    touched, assigned, moved = 22 * 32, 22 * 45 * 4, 18 * 23
    kv = 2 * 6 * (879 + 23 * (950 - 879)) * 8 * 64 * 2
    quant, small = pkl.shared_params(MODEL)
    want = (quant + 65536 * 2048 + touched * 11_010_048 + small * 2 + 45 * 2048 * 2 + moved * 2 * 2048 * 2 * 2 + kv)
    assert pkl.forward_bytes(MODEL, 1, 23, 45, 950, touched, moved, common=879) == want
    floor, roof = pkl.forward_floor_s(MODEL, V5E, 1, 23, 45, 950, touched, assigned, moved, common=879)
    assert roof == "bytes" and 0.0101 < floor < 0.0104  # the issue's 10.5 ms held a bf16 head: 0.13 GB more
    floor, roof = pkl.grouped_matmul_floor_s(MODEL, V5E, 1, touched, assigned)
    assert roof == "bytes" and floor == touched * 11_010_048 / 819e9 and 0.0094 < floor < 0.0096
    floor, roof = pkl.shortconv_floor_s(MODEL, V5E, 1, 45, moved)
    assert roof == "bytes" and 0.00036 < floor < 0.00040  # the issue's 0.37 ms: W_in and W_out of 18 layers
    assert floor == (18 * (16_777_216 + 2 * (3 * 2048 + 2048) + 45 * 2 * 2048 * 2) + moved * 16384) / 819e9
    # the FLOPs of 4096 real positions pass the bytes: a prefill is compute-bound
    assert pkl.forward_floor_s(MODEL, V5E, 1, 4, 4096, 4096, touched, 22 * 4096 * 4, 18 * 4)[1] == "flops"
    # experts nobody read are not in the floor
    assert pkl.expert_bytes(MODEL, 1, 0) == 0 and pkl.expert_flops(MODEL, 0) == 0


def test_a_perfect_kernel_reads_100_percent_and_a_program_without_the_counters_reads_nothing(monkeypatch):
    from benchmark.readers import roofline
    from benchmark.readers import roofline_lfm2 as rd

    fwds, touched, assigned, moved = 16, 22 * 32.0, 22 * 180.0, 18 * 23.0
    n = {"steps": [], "rows": 32.0, "context": 950.0, "positions": 45.0, "row_blocks": 6 * 23 * 8.0,
         "common_row_blocks": 6 * 23 * 6.0, "block_size": 128, "live": 23.0, "common": 768.0}
    perfect = {"layer/conv": pkl.shortconv_floor_s(MODEL, V5E, 1, 45.0, moved)[0],
               "grouped_matmul": pkl.grouped_matmul_floor_s(MODEL, V5E, 1, touched, assigned)[0]}
    under = lambda plane, scopes, program: {
        "ns": perfect.get((scopes or [None])[0], 0) * 1e9 * fwds, "program_ns": 0.020 * 1e9 * fwds, "forwards": fwds}
    for mod in (roofline, rd):
        monkeypatch.setattr(mod, "run_trace", lambda ctx: object())
        monkeypatch.setattr(mod, "scope_ns", under)
    monkeypatch.setattr(rd, "needed", lambda ctx: n)
    counters = {"scheduler.forwards": 100.0, "moe.experts_touched": 100.0 * touched,
                "moe.assigned_rows": 100.0 * assigned, "conv.tail_rows_moved": 100.0 * moved}
    ctx = {"counters": counters, "peaks": V5E, "model": MODEL, "serving": {"quant": "int8", "fast_forward": 8}}
    for what in ("shortconv_roofline", "kernel_roofline"):
        assert abs(rd.read(ctx, what) - 100.0) < 1e-9, what
    assert 40.0 < rd.read(ctx, "program_roofline") < 60.0  # a 10.2 ms floor over the 20 ms a forward here
    # the parent of PR 64, another model, a CPU rehearsal: nothing, and no raise
    for lacking in counters:
        parent = dict(ctx, counters={k: v for k, v in counters.items() if k != lacking})
        assert [rd.read(parent, w) for w in ("shortconv_roofline", "kernel_roofline", "program_roofline", "step_mfu")] == [None] * 4
    assert rd.read(dict(ctx, peaks=None), "shortconv_roofline") is None
    assert rd.read(dict(ctx, model={"hidden_size": 4096}), "program_roofline") is None
    with pytest.raises(ValueError, match="unknown quantity"):
        rd.read(ctx, "no_such_share")


# ---------------------------------------------------------------- the manifest


def test_the_manifest_is_valid_and_the_cell_reads_what_its_siblings_read_and_four_of_its_own():
    manifest = mf.load_manifest()
    assert mf.validate(manifest) == [] and len(manifest["per_layer"]) <= 128
    cell = mf.load_cell(manifest, CELL)
    assert mf.code_problems(cell) == []
    names = [m["name"] for m in cell["per_layer"]]
    hybrid = [m["name"] for m in mf.load_cell(manifest, "olmohybrid_flood")["per_layer"] if m["name"].endswith(".floods")]
    routed = ["expert_matmul_device_ms_per_forward.floods", "moe_dispatch_device_ms_per_forward.floods",
              "expert_ffn_share.floods", "moe_experts_touched_per_layer.floods", "moe_padding_share.floods",
              "moe_load_max_over_mean.floods", "grouped_matmul_roofline.floods"]
    assert set(hybrid) | set(routed) | {f"{q}.{CELL}" for q in OWN} == set(names)
    assert "shared_expert_device_ms_per_forward.floods" not in names  # it has none
    assert [m["name"] for m in cell["end_to_end"]] == ["setup_s", "out_tokens_per_s"]
    for q in OWN:
        m = next(x for x in manifest["per_layer"] if x["name"] == f"{q}.{CELL}")
        assert m["workloads"] == [CELL] and m["moves"] == "out_tokens_per_s"
    for name in ("decode_program_roofline.floods", "step_mfu.floods", "grouped_matmul_roofline.floods"):
        assert mf.load_layer_metric(name, CELL)["reader"] == "roofline_lfm2"
    assert mf.load_layer_metric("moe_experts_touched_per_layer.floods", CELL)["args"]["scale"] == 1 / 22
    assert mf.load_layer_metric("moe_load_max_over_mean.floods", CELL)["args"]["scale"] == 32
    assert mf.load_layer_metric("moe_padding_share.floods", CELL)["reader"] == "roofline_routed"  # counters alone


def test_nothing_the_benchmark_had_was_edited_and_every_entry_was_appended():
    """``data/lfm2_addition.json``: sha256 of every file under ``benchmark/`` and
    of the manifest as PR 64's parent (81fa147) held them, and the cells it had.
    Each file is still that file; the manifest cut back to the parent's counts
    of entries and to the parent's cells in every list IS the parent's, entry
    for entry and in order — whatever later PRs append behind this one (the
    proofs of PRs 43 and 61 ``pop()`` the LAST entry and so hold only until the
    next cell arrives)."""
    held = json.loads((Path(__file__).parent / "data" / "lfm2_addition.json").read_text())
    now = {p.relative_to(ROOT).as_posix(): hashlib.sha256(p.read_bytes()).hexdigest()
           for p in sorted((ROOT / "benchmark").rglob("*"))
           if p.is_file() and "__pycache__" not in p.parts and ".jax_cache" not in p.parts}
    assert {k: now.get(k) for k in held["files"]} == held["files"]
    mine = [k for k in set(now) - set(held["files"]) if "lfm2" in k or "shortconv" in k.lower()]
    assert len(mine) >= 17 and all(any(n in k for n in NEW) or "shortconv" in k for k in mine)
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert manifest["configs"][held["configs"]]["name"] == "lfm2-8b-a1b-int8"
    assert manifest["workloads"][len(held["cells"])]["name"] == CELL
    own = manifest["per_layer"][held["per_layer"]:held["per_layer"] + len(OWN)]
    assert [m["name"] for m in own] == [f"{q}.{CELL}" for q in OWN]
    del manifest["configs"][held["configs"]:], manifest["workloads"][len(held["cells"]):]
    del manifest["per_layer"][held["per_layer"]:]
    joined = 0
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in m:
            joined += CELL in m["workloads"]
            at = m["workloads"].index(CELL) if CELL in m["workloads"] else len(m["workloads"])
            assert all(w in held["cells"] for w in m["workloads"][:at])  # behind every cell the parent had
            m["workloads"] = [w for w in m["workloads"] if w in held["cells"]]
    assert joined == 35  # out_tokens_per_s and 34 per-layer lists
    assert "lfm2" not in json.dumps(manifest)
    assert hashlib.sha256(json.dumps(manifest, sort_keys=True).encode()).hexdigest() == held["manifest_sha256"]
