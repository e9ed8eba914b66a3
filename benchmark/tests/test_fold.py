"""PR 42: one per-layer entry a (metric, end-to-end metric it moves), a cell's
own reader found by name, and step 4's corrections by hand counts.

``data/fold_table.json`` is the parent's manifest as data: each of its 149
(entry, cell) pairs with the ``reader`` and ``args`` its file held, the entry
it is reported under now, and which of step 4's corrections (a: real
positions, b: common K/V once, c: a padding share that is a share, d: per
admission) changed what it reads."""

import json
from pathlib import Path

import pytest

from benchmark.lib import manifest as mf
from benchmark.lib import peaks as pk
from benchmark.lib import peaks_cohere2moe as pkc
from benchmark.lib import peaks_hybrid as pkh
from benchmark.lib import peaks_mla_moe as pkm
from benchmark.lib import peaks_routed as pkr

M = mf.load_manifest()
LM = mf.BENCH_DIR / "layer_metrics"
TABLE = json.loads((Path(__file__).parent / "data" / "fold_table.json").read_text())
V5E = pk.PEAK_TABLE["TPU v5 lite"]
CONFIG = lambda name: mf.load_json(f"benchmark/configs/{name}.json")
MISTRAL = CONFIG("mistral-7b-v0.1-int8")


def test_the_table_holds_every_pair_the_parent_reported_once():
    assert len(TABLE) == 149 and len({(r["old"], r["cell"]) for r in TABLE}) == 149
    assert len({r["old"] for r in TABLE}) == 128  # the parent's manifest was full
    assert {r.get("step4") for r in TABLE} == {None, "a", "b", "a,b", "c", "d"}


@pytest.mark.parametrize("row", TABLE, ids=[f"{r['old']}@{r['cell']}" for r in TABLE])
def test_a_pair_of_the_parent_is_reported_under_its_folded_name_by_the_same_reader(row):
    entry = next(m for m in M["per_layer"] if m["name"] == row["new"])
    assert row["cell"] in entry["workloads"]
    stem = lambda n: n.rsplit(".", 1)[0]
    assert stem(row["old"]) == stem(row["new"])  # no metric changes its stem
    spec = mf.load_layer_metric(row["new"], row["cell"])
    if row.get("step4") == "d":  # the one correction that is an argument: device ms a CALL over rows a call
        assert spec["reader"] == row["reader"] and spec["args"] == dict(
            row["args"], per={"num": "admit.rows", "den": "admit.calls"})
    else:  # a, b and c are arithmetic inside the reader the pair already named
        assert (spec["reader"], spec["args"]) == (row["reader"], row["args"])


def test_every_entry_has_a_list_and_the_room_is_there():
    assert len(M["per_layer"]) <= 80 and all(m.get("workloads") for m in M["per_layer"])
    pairs = sum(len(m["workloads"]) for m in M["per_layer"])
    assert pairs == 149 + 30 + 6  # moonlight_flood reads what its siblings read (4 -> 34), and step_mfu in every cell
    assert sum("moonlight_flood" in m["workloads"] for m in M["per_layer"]) == 35
    bad = json.loads(json.dumps(M))
    del bad["per_layer"][3]["workloads"]
    assert any("no workloads list" in p for p in mf.validate(bad))


def test_a_file_repeats_no_list_and_a_cells_own_file_holds_a_reader_and_nothing_of_the_entry():
    names = {m["name"]: m for m in M["per_layer"]}
    for name in names:
        assert "workloads" not in json.loads((LM / f"{name}.json").read_text()), name
    own = sorted(p for p in LM.glob("*/*.json"))
    assert len(own) == 14
    for path in own:
        entry, cell = names[path.parent.name], path.stem  # found from the two names, and from nothing else
        assert cell in entry["workloads"], path
        held = json.loads(path.read_text())
        assert {"reader", "args"} <= set(held) <= mf.VARIANT_KEYS, path
        spec, default = mf.load_layer_metric(entry["name"], cell), mf.load_layer_metric(entry["name"])
        assert (spec["reader"], spec["args"]) == (held["reader"], held["args"])
        assert (spec["reader"], spec["args"]) != (default["reader"], default["args"]), path
        assert {k: spec[k] for k in entry} == entry  # unit, better, source, layer, moves: the entry's
        assert hasattr(mf.load_code("readers", spec["reader"]), "read")


def test_a_cells_own_file_that_holds_more_than_a_reader_is_refused(tmp_path, monkeypatch):
    (tmp_path / "benchmark" / "layer_metrics" / "step_ms.floods").mkdir(parents=True)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(M))
    (tmp_path / "benchmark" / "layer_metrics" / "step_ms.floods.json").write_text(
        (LM / "step_ms.floods.json").read_text())
    (tmp_path / "benchmark" / "layer_metrics" / "step_ms.floods" / "olmoe_flood.json").write_text(
        json.dumps({"reader": "steplog", "args": {"what": "step_ms"}, "unit": "s"}))
    monkeypatch.setattr(mf, "ROOT", tmp_path)
    with pytest.raises(ValueError, match="nothing else"):
        mf.load_layer_metric("step_ms.floods", "olmoe_flood")
    assert mf.load_layer_metric("step_ms.floods", "parse_flood")["reader"] == "steplog"


# ---- step 4, by hand


def test_a_the_dense_floor_at_45_real_positions_of_288():
    params = 32 * (2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336) + 32000 * 4096
    assert pk.matmul_params(MISTRAL) == params == 7_110_393_856
    per_position = 2 * params + 920 * 4 * 32 * 128
    assert pk.forward_flops(MISTRAL, 45, 920) == 45 * per_position
    # what the parent's floor was handed: 32 rows x 9 positions, K/V a row -> FLOP-bound, 20.8 ms
    old, roof = pk.forward_floor_s(MISTRAL, V5E, 1, 32, 288, 920)
    assert roof == "flops" and abs(old - 288 * per_position / 197e12) < 1e-12 and 0.0207 < old < 0.0209
    # the needed work: 45 positions' FLOPs are 3.3 ms, the weights' read 8.7 ms + the K/V sets the floor
    new, roof = pk.forward_floor_s(MISTRAL, V5E, 1, 32, 45, 920, common=768)
    assert roof == "bytes" and 45 * per_position / 197e12 < 0.0033
    kv = 2 * 32 * (768 + 32 * 152) * 8 * 128 * 2
    assert abs(new - (params + kv) / 819e9) < 1e-12 and 0.0095 < new < 0.0097
    # 22.07 ms a forward (PERF.md section 5): 94 % of the parent's floor, 43 % of the needed work
    assert 0.93 < old / 0.02207 < 0.95 and 0.42 < new / 0.02207 < 0.45


def test_a_every_floor_counts_real_positions_and_the_head_on_one_a_row():
    olmoe, phi4, cmda, moon = (CONFIG(n) for n in (
        "olmoe-1b-7b-0125-int8", "phi-4-mini-flash-reasoning-int8", "command-a-plus-05-2026-int8",
        "moonlight-16b-a3b-int8"))
    quant, plain = pkr.shared_params(olmoe)
    assert pkr.forward_flops(olmoe, 36, 950, assigned=0) == 36 * (2 * (quant + plain) + 950 * 4 * 16 * 128)
    # one more ROW adds a head, one more real POSITION a pass through the layers
    for flops, model, kw in ((pkh.forward_flops, phi4, {}), (pkc.forward_flops, cmda, {"local_rows": 0}),
                             (pkm.forward_flops, moon, {"assigned_rows": 0})):
        base = flops(model, rows=32, positions=42, ctx=950, **kw)
        head = model["vocab_size"] * model["hidden_size"]
        assert flops(model, rows=33, positions=42, ctx=950, **kw) - base == 2 * head
        a_position = flops(model, rows=32, positions=43, ctx=950, **kw) - base
        assert a_position > 0 and abs(flops(model, rows=32, positions=288, ctx=950, **kw)
                                      - base - 246 * a_position) < 1e-6 * base
    # the latent kernel's dots: real positions x 16 heads x 17 layers, not all 1 + 8 of every live row
    assert pkm.query_rows(moon, 45) == 45 * 16 * 17
    floor, roof = pkm.latent_attention_floor_s(moon, V5E, keys_read=17 * 52 * 128, positions=45, ctx=950)
    assert roof == "bytes" and floor == 17 * 52 * 128 * 1152 / 819e9
    floor, roof = pkm.latent_attention_floor_s(moon, V5E, keys_read=17 * 52 * 128, positions=288, ctx=950)
    assert roof == "flops" and floor == 288 * 16 * 17 * 950 * 2 * 1088 / 197e12


def test_b_the_blocks_live_rows_hold_in_common_are_read_once():
    # 32 rows behind an 879-token prefix: 6 full blocks of 128 in common, 192 (row, block) pairs a forward
    assert pk.common_positions(192, 32, 128) == 768
    # the rows that attend: 32 slots hold 183 blocks a forward in parse_flood, rows of ~930 positions at most
    # 930 / 128 + 1 of them each: 22 live rows, never 32 (a plan that ended inside a chunk attends nothing)
    assert 22.0 < pk.live_rows(183, 930, 128, rows=32) < 22.3 and pk.live_rows(0, 930, 128, rows=32) == 32
    assert 0.97 < pk.live_rows(8.0, 920, 128, rows=1.0) <= 1.0 == pk.live_rows(9.0, 920, 128, rows=1.0)  # never over the slots
    assert pk.kv_positions(32, 920, 768) == 768 + 32 * (920 - 768)
    assert pk.kv_positions(32, 920) == 32 * 920 and pk.kv_positions(1, 920, 768) == 920  # one row: nothing to share
    assert pk.kv_positions(32, 700, 768) == 700  # never more than the context
    a_position = 2 * 32 * 8 * 128 * 2  # K and V, 32 layers, 8 heads of 128, bf16
    assert pk.forward_bytes(MISTRAL, 1, 32, 920) - pk.forward_bytes(MISTRAL, 1, 32, 920, common=768) \
        == 31 * 768 * a_position
    olmoe = CONFIG("olmoe-1b-7b-0125-int8")
    assert pkr.forward_bytes(olmoe, 1, 32, 950, touched=0) - pkr.forward_bytes(olmoe, 1, 32, 950, 0, common=768) \
        == 31 * 768 * 2 * 16 * 16 * 128 * 2
    cmda = CONFIG("command-a-plus-05-2026-int8")  # every layer rides the common pass (the window identity)
    assert pkc.kv_positions(cmda, 32, 950) - pkc.kv_positions(cmda, 32, 950, 768) == 8 * 31 * 768
    phi4 = CONFIG("phi-4-mini-flash-reasoning-int8")  # the full layer and the 7 cross reads; the 8 windowed walk alone
    assert pkh.kv_positions(phi4, 32, 950) == 8 * 32 * 512 + 8 * 32 * 950
    assert pkh.kv_positions(phi4, 32, 950) - pkh.kv_positions(phi4, 32, 950, 768) == 8 * 31 * 768
    assert pk.common_positions(8 * 192, 32, 128, reads=8) == 768  # its counter is summed over those 8 reads


def test_c_a_padding_share_is_a_share():
    from benchmark.readers import roofline_cohere2moe as rc
    from benchmark.readers import roofline_routed as rr

    half = {"counters": {"moe.padded_rows": 4000.0, "moe.assigned_rows": 2000.0, "moe.local_rows": 2000.0}}
    assert rr.read(half, "padding_share") == rc.read(half, "padding_share") == 50.0  # padded / filled - 1 read 100
    ledger = {"counters": {"moe.padded_rows": 2.0687, "moe.local_rows": 1.0}}  # cmdaplus_flood, PR 41: 106.87 %
    assert abs(rc.read(ledger, "padding_share") - 51.66) < 0.01
    assert rr.read({"counters": {"moe.padded_rows": 10.0, "moe.assigned_rows": 10.0}}, "padding_share") == 0.0
    assert rr.read({"counters": {"moe.assigned_rows": 10.0}}, "padding_share") is None


def test_the_whole_steps_share_of_the_peak_counts_needed_flops_over_the_windows_seconds():
    from benchmark.readers import roofline

    steps = [{"forwards": 16, "occupancy": 32, "tokens": 720}] * 90  # 1440 forwards of 45 real positions in 45 s
    ctx = {"steps": steps, "window_s": 45.0, "peaks": V5E, "model": MISTRAL, "counters": {}, "records": [],
           "serving": {"quant": "int8", "block_size": 128}, "prefix_tokens": 879, "tokens_per_request": 34.0}
    a_forward = 45 * (2 * pk.matmul_params(MISTRAL) + (879 + 17) * 4 * 32 * 128)
    want = 100.0 * a_forward * 1440 / 45.0 / 197e12
    assert abs(roofline.read(ctx, "step_mfu") - want) < 1e-9 and 10.0 < want < 10.6  # read-bound: single digits
    assert {n: mf.load_layer_metric("step_mfu.floods", c)["reader"] for n, c in (
        ("dense", "parse_flood"), ("routed", "olmoe_flood"), ("hybrid", "phi4flash_flood"),
        ("share", "cmdaplus_flood"), ("latent", "moonlight_flood"))} == {
        "dense": "roofline", "routed": "roofline_routed", "hybrid": "roofline_hybrid",
        "share": "roofline_cohere2moe", "latent": "roofline_mla_moe"}
    assert roofline.read(dict(ctx, peaks=None), "step_mfu") is None  # a CPU rehearsal: never a device number


def test_d_prefill_device_time_is_read_per_admission():
    from benchmark.readers import trace as rt

    programs = {"jit_forward_paged_first_tokens(1)": {"count": 9, "total_s": 0.252},
                "jit_forward_paged(2)": {"count": 1, "total_s": 0.020},
                "jit__first_token_into_slot(3)": {"count": 1, "total_s": 0.002},
                "jit_paged_chunk_decode_loop(4)": {"count": 3, "total_s": 1.2}}
    ctx = {"trace": {"programs": programs, "busy_s": 1.5, "window_s": 2.0},
           "counters": {"admit.rows": 740.0, "admit.calls": 200.0}}
    args = mf.load_layer_metric("prefill_device_ms.floods", "parse_flood")["args"]
    a_call = 1e3 * (0.252 + 0.020 + 0.002) / 10
    assert abs(rt.read(ctx, **{k: v for k, v in args.items() if k != "per"}) - a_call) < 1e-9
    assert abs(rt.read(ctx, **args) - a_call / 3.7) < 1e-9
    assert rt.read(dict(ctx, counters={}), **args) is None  # a program that counts no admissions
    solo = dict(ctx, counters={"admit.rows": 160.0, "admit.calls": 160.0})  # one row a call: per call it is
    assert abs(rt.read(solo, **mf.load_layer_metric("prefill_device_ms.solo", "parse_solo")["args"]) - a_call) < 1e-9
    restore = mf.load_layer_metric("prefill_device_ms.floods", "phi4flash_flood")["args"]
    assert restore["programs"][-1] == "_restore_state" and restore["per"] == args["per"]
