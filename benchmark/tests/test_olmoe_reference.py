"""The OLMoE reference, reached as the harness reaches it: by the name the
configuration gives, through the protocol's ``logits`` with the
configuration's own keys — against the program's forward at test widths in
float32, and through ``lib/refcheck.compare`` on the rehearsal's served
stack, where its int4 control has to land above its tolerance."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import manifest as mf
from benchmark.lib import refcheck

CONF = mf.load_json("benchmark/configs/olmoe-1b-7b-0125-int8.json")


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def test_olmoe_reference_matches_llama_forward_and_reads_its_keys():
    from benchmark.builders import olmoe_stack, parse_stack
    from tpu_voice_agent.models.llama import forward, init_kv_cache, init_params, quantize_params

    ref = mf.load_code("reference", CONF["reference"])
    model, serving = parse_stack.as_run(CONF, rehearsal=True)
    cfg = dataclasses.replace(olmoe_stack.llama_config(model, serving), max_seq_len=256)
    assert (cfg.n_experts, cfg.top_k, cfg.norm_topk, cfg.qk_norm) == (8, 2, False, True)
    params = init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 48), 0, cfg.vocab_size)
    sample = {"tokens": [int(t) for t in toks[0]], "rows": 48}
    for tree in (params, quantize_params(params)):
        with jax.default_matmul_precision("highest"):
            want, _ = forward(tree, cfg, toks, jnp.arange(48, dtype=jnp.int32)[None],
                              init_kv_cache(cfg, 1, 64, dtype=jnp.float32))
        # float32 on both sides, the same int8 planes: the order of sums only (1e-6 measured)
        assert _rel(ref.logits(tree, model, sample), want[0]) < 2e-4
    # each of the model's two properties is read from the configuration's keys
    assert _rel(ref.logits(params, dict(model, norm_topk_prob=True), sample), want[0]) > 1e-2
    assert _rel(ref.logits(params, dict(model, qk_norm=False), sample), want[0]) > 1e-2
    assert _rel(ref.logits(params, model, sample, control=True), want[0]) > ref.TOLERANCE
    assert ref.CONTROL == "int4" and ref.TOLERANCE == 0.03 and ref.SAMPLE == "paged_decoder"


def test_olmoe_rehearsal_stack_passes_the_comparison_with_its_control_above():
    said = []
    served = mf.load_code("builders", CONF["builder"]).build(CONF, True, said.append)
    try:
        assert served.engine.cfg.n_experts == 8 and served.dims["model"]["qk_norm"] is True
        seen = refcheck.compare(served, CONF, 3, said.append)
    finally:
        served.close()
    ref = mf.load_code("reference", CONF["reference"])
    assert [c["reference"] for c in seen] == ["olmoe_decoder"] and seen[0]["ok"]
    assert seen[0]["rel_err"] <= ref.TOLERANCE < seen[0]["control"]
    assert any("reference olmoe_decoder:" in line and line.endswith("-> ok") for line in said)


# ---- the routed roofline arithmetic (lib/peaks_routed.py, readers/roofline_routed.py) on hand-made counts


MODEL = {k: CONF[k] for k in ("hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
                              "num_key_value_heads", "vocab_size", "num_experts", "num_experts_per_tok")}
V5E = {"bytes_per_s": 819e9, "flops_per_s": 197e12}
PLANE = 2048 * 1024  # one expert matrix, int8 bytes


def test_routed_floor_counts_the_experts_touched_and_the_rows_routed():
    from benchmark.lib import peaks_routed as pkr

    L, E, K = 16, 64, 8
    assert pkr.expert_bytes(MODEL, 1, touched=L * E) == L * E * 3 * PLANE  # 6.44 GB: every expert of every layer
    assert pkr.expert_flops(MODEL, assigned=L * 288 * K) == L * 288 * K * 3 * 2 * PLANE
    # decode is bytes-bound (36 rows an expert): 40 experts a layer touched -> 40 / 64 of the full read
    floor, roof = pkr.grouped_matmul_floor_s(MODEL, V5E, 1, touched=L * 40, assigned=L * 288 * K)
    assert roof == "bytes" and floor == L * 40 * 3 * PLANE / 819e9
    # enough rows and the flops roof takes over (one expert, 4096 rows: 51 GFLOP against a 6 MB read)
    assert pkr.grouped_matmul_floor_s(MODEL, V5E, 1, touched=1, assigned=4096)[1] == "flops"
    # the whole forward: shared weights + the touched planes + the live rows' KV, never E by assumption
    few = pkr.forward_bytes(MODEL, 1, rows=32, ctx=950, touched=L * 10)
    all_ = pkr.forward_bytes(MODEL, 1, rows=32, ctx=950, touched=L * E)
    assert all_ - few == L * 54 * 3 * PLANE
    assert few > 32 * 950 * 131072  # the KV alone: 131072 B a token, as Mistral's


def test_a_perfect_kernel_reads_100_percent_when_fewer_than_64_experts_are_touched(monkeypatch):
    """The kernel's floor comes from the SAME stretch's counters: a kernel
    that streams exactly the 40 experts a layer it touched, at the peak
    bandwidth, reads 100 % — a floor that assumed all 64 would read 160 %,
    which the driver refuses."""
    from benchmark.readers import roofline_routed as rr

    L, fwds, touched = 16, 16, 40
    perfect_ns = L * touched * 3 * PLANE / 819e9 * 1e9 * fwds
    from benchmark.readers import roofline

    monkeypatch.setattr(rr, "run_trace", lambda ctx: object())
    monkeypatch.setattr(roofline, "run_trace", lambda ctx: object())
    monkeypatch.setattr(rr, "needed", lambda ctx: {"steps": [], "rows": 32.0, "context": 950.0, "positions": 45.0,
                                                 "common_row_blocks": 192.0, "block_size": 128, "live": 32.0, "common": 768.0})
    monkeypatch.setattr(roofline, "scope_ns", lambda plane, scopes, program: {
        "ns": perfect_ns if scopes else 0, "program_ns": 4 * perfect_ns, "forwards": fwds})
    counters = {"scheduler.forwards": 100.0, "moe.experts_touched": 100.0 * L * touched,
                "moe.assigned_rows": 100.0 * L * 2304, "moe.padded_rows": 100.0 * L * 4000}
    ctx = {"counters": counters, "peaks": V5E, "model": MODEL,
           "serving": {"quant": "int8", "fast_forward": 8}}
    assert abs(rr.read(ctx, "kernel_roofline") - 100.0) < 1e-9
    assert 0 < rr.read(ctx, "program_roofline") < 100.0
    assert abs(rr.read(ctx, "padding_share") - 100.0 * (1 - 2304 / 4000)) < 1e-9  # of the rows computed
    # a program without the counters (the parent, a dense model) gives nothing to read and never raises
    dense = dict(ctx, counters={"scheduler.forwards": 100.0})
    assert [rr.read(dense, w) for w in ("kernel_roofline", "program_roofline", "padding_share")] == [None] * 3
