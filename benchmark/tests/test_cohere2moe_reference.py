"""The Command A+ reference, reached as the harness reaches it: by the name
the configuration gives, through the protocol's ``logits`` with the
configuration's own keys — each of the model's rules read from them — and
through ``lib/refcheck.compare`` on the rehearsal's served stack (a window of
16 that BINDS, 4 of 16 experts held from id 4), where its int4 control has to
land above its tolerance; then the share's roofline arithmetic on hand-made
counts."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import manifest as mf
from benchmark.lib import refcheck

CONF = mf.load_json("benchmark/configs/command-a-plus-05-2026-int8.json")


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def test_the_file_holds_the_catalog_s_numbers_but_for_the_three_reduced_keys():
    entry = next(c for c in mf.load_manifest()["configs"] if c["name"] == "command-a-plus-05-2026-int8")
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert sorted(CONF["reduced_why"]) == sorted(entry["reduced"])
    assert (CONF["num_hidden_layers_published"], CONF["num_experts_published"],
            CONF["vocab_size_published"], CONF["chips_sharing_a_layer"]) == (32, 128, 262144, 8)
    assert CONF["num_experts"] * CONF["chips_sharing_a_layer"] == CONF["num_experts_published"]
    assert CONF["vocab_size"] * 8 == CONF["vocab_size_published"]  # exactly an eighth
    assert len(CONF["layer_types"]) == 32 and CONF["layer_types"][:4] == ["sliding_attention"] * 3 + ["full_attention"]
    assert CONF["num_hidden_layers"] % CONF["layer_switch"] == 0  # whole periods


def test_the_reference_reads_each_rule_of_the_model_from_the_configuration_s_keys():
    from benchmark.builders import cohere2moe_stack, parse_stack
    from tpu_voice_agent.models.llama import forward_paged, init_params

    ref = mf.load_code("reference", CONF["reference"])
    model, serving = parse_stack.as_run(CONF, rehearsal=True)
    cfg = dataclasses.replace(cohere2moe_stack.llama_config(model, serving), max_seq_len=256)
    assert (cfg.n_experts, cfg.n_held, cfg.first_expert, cfg.top_k, cfg.router_fn) == (16, 4, 4, 2, "sigmoid")
    params = init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 48), 0, cfg.vocab_size)
    sample = {"tokens": [int(t) for t in toks[0]], "rows": 48}
    shape = (cfg.n_layers, 5, 16, cfg.n_kv_heads, cfg.head_dim)
    with jax.default_matmul_precision("highest"):
        want = forward_paged(params, cfg, toks, jnp.arange(48, dtype=jnp.int32)[None],
                             jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32),
                             jnp.asarray([[1, 2, 3, 4]], jnp.int32), attn_impl="xla", fresh_block=True)[0][0]
    assert _rel(ref.logits(params, model, sample), want) < 2e-4
    for change in ({"sliding_window": 4096}, {"first_expert": 0}, {"num_experts_per_tok": 3},
                   {"num_shared_experts": 1}, {"layer_switch": 2}, {"rope_theta": 10000},
                   {"logit_scale": 2}):
        assert _rel(ref.logits(params, dict(model, **change), sample), want) > 1e-3, change
    assert _rel(ref.logits(params, model, sample, control=True), want) > ref.TOLERANCE
    assert ref.CONTROL == "int4" and ref.SAMPLE == "paged_decoder"


def test_the_rehearsal_stack_passes_the_comparison_with_its_control_above():
    said = []
    served = mf.load_code("builders", CONF["builder"]).build(CONF, True, said.append)
    try:
        cfg = served.engine.cfg
        assert cfg.layer_types == ("sliding",) * 3 + ("full",) and cfg.sliding_window == 16
        assert served.dims["model"]["num_experts_published"] == 16 and cfg.n_held == 4
        seen = refcheck.compare(served, CONF, 3, said.append)
    finally:
        served.close()
    ref = mf.load_code("reference", CONF["reference"])
    assert [c["reference"] for c in seen] == ["cohere2moe_decoder"] and seen[0]["ok"]
    assert seen[0]["rel_err"] <= ref.TOLERANCE < seen[0]["control"]
    assert any("reference cohere2moe_decoder:" in line and line.endswith("-> ok") for line in said)


# ---- the share's roofline arithmetic (lib/peaks_cohere2moe.py, readers/roofline_cohere2moe.py)

MODEL = {k: v for k, v in CONF.items() if not isinstance(v, (dict, list))}
V5E = {"bytes_per_s": 819e9, "flops_per_s": 197e12}
PLANE = 4096 * 4096  # one expert matrix, int8 bytes


def test_the_floor_counts_held_experts_touched_local_rows_and_the_head_once_a_row():
    from benchmark.lib import peaks_cohere2moe as pkc

    L, held = 8, 16
    int8, bf16 = pkc.layer_params(MODEL)
    assert int8 == L * (2 * 4096 * 16384 + 2 * 4096 * 1024 + 4 * 3 * PLANE) and bf16 == L * 4096 * 128
    assert pkc.expert_bytes(MODEL, 1, touched=L * held) == L * held * 3 * PLANE  # 6.44 GB
    assert pkc.expert_flops(MODEL, local_rows=L * 288) == L * 288 * 3 * 2 * PLANE
    # 18 rows an expert: the planes bound the kernel; 10 of 16 touched -> 10 / 16 of the read
    floor, roof = pkc.grouped_matmul_floor_s(MODEL, V5E, 1, touched=L * 10, local_rows=L * 288)
    assert roof == "bytes" and floor == L * 10 * 3 * PLANE / 819e9
    few = pkc.forward_bytes(MODEL, 1, rows=32, ctx=950, touched=L * 4)
    all_ = pkc.forward_bytes(MODEL, 1, rows=32, ctx=950, touched=L * held)
    assert all_ - few == L * 12 * 3 * PLANE
    assert all_ == int8 + 32768 * 4096 + 2 * bf16 + L * held * 3 * PLANE + 32 * 950 * L * 4096
    # K/V: a sliding layer reads min(context, window), the positions live rows hold in common once (PR 42);
    # the head's FLOPs on ONE position a row
    assert pkc.kv_positions(MODEL, 1, 950) == 8 * 950 and pkc.kv_positions(MODEL, 1, 6000) == 2 * 6000 + 6 * 4096
    assert pkc.kv_positions(MODEL, 32, 950, common=768) == 8 * (768 + 32 * 182)
    base = pkc.forward_flops(MODEL, rows=32, positions=288, ctx=950, local_rows=0)
    assert pkc.forward_flops(MODEL, rows=33, positions=288, ctx=950, local_rows=0) - base == 2 * 32768 * 4096


def test_a_perfect_kernel_reads_100_percent_and_a_program_without_the_counter_reads_nothing(monkeypatch):
    from benchmark.readers import roofline_cohere2moe as rc

    L, fwds, touched = 8, 16, 12
    perfect_ns = L * touched * 3 * PLANE / 819e9 * 1e9 * fwds
    from benchmark.readers import roofline

    monkeypatch.setattr(roofline, "run_trace", lambda ctx: object())
    monkeypatch.setattr(rc, "needed", lambda ctx: {"steps": [], "rows": 32.0, "context": 950.0, "positions": 45.0,
                                                 "common_row_blocks": 192.0, "block_size": 128, "live": 32.0, "common": 768.0})
    monkeypatch.setattr(roofline, "scope_ns", lambda plane, scopes, program: {
        "ns": perfect_ns if scopes else 0, "program_ns": 4 * perfect_ns, "forwards": fwds})
    counters = {"scheduler.forwards": 100.0, "moe.experts_touched": 100.0 * L * touched,
                "moe.assigned_rows": 100.0 * L * 2304, "moe.local_rows": 100.0 * L * 288,
                "moe.padded_rows": 100.0 * L * 400}
    ctx = {"counters": counters, "peaks": V5E, "model": MODEL,
           "serving": {"quant": "int8", "fast_forward": 8}}
    assert abs(rc.read(ctx, "kernel_roofline") - 100.0) < 1e-9
    assert 0 < rc.read(ctx, "program_roofline") < 100.0
    assert abs(rc.read(ctx, "padding_share") - 100.0 * (1 - 288 / 400)) < 1e-9  # of the rows computed
    # the parent of PR 34, or a model that holds all its experts: no ``moe.local_rows``
    parent = dict(ctx, counters={k: v for k, v in counters.items() if k != "moe.local_rows"})
    assert [rc.read(parent, w) for w in ("kernel_roofline", "program_roofline", "padding_share")] == [None] * 3
    from benchmark.readers import counters as plain

    assert plain.read(parent, "moe.local_rows", "moe.assigned_rows", 100.0) is None
    assert abs(plain.read(dict(ctx, window_s=45.0), "moe.local_rows", "moe.assigned_rows", 100.0) - 12.5) < 1e-9
