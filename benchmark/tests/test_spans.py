"""The three readers of the program's own spans and scopes: on hand-made
intervals, and on a small trace recorded on a TPU v5e with host spans and
scoped operations (``data/spans_trace.json``, ``tools/record_spans_trace.py``)."""

import json
from pathlib import Path

import pytest

from benchmark.lib import trace as tr
from benchmark.readers import admissions, host_spans, scopes

DATA = Path(__file__).parent / "data" / "spans_trace.json"


def recorded() -> dict:
    data = json.loads(DATA.read_text())
    for key in ("spans", "ops", "modules"):
        data[key] = [tuple(e) for e in data[key]]
    return data


def test_admissions_reads_the_median_of_a_part_over_the_windows_admissions():
    steps = [{"admissions": [{"queue_ms": 1.0, "request_ms": 30.0, "tokenize_ms": 12.0},
                             {"queue_ms": 3.0, "request_ms": 28.0, "tokenize_ms": 10.0}]},
             {"forwards": 16},  # a step that admitted nobody
             {"admissions": [{"queue_ms": 1400.0, "request_ms": 31.0}]}]  # a chunked one: no tokenize
    ctx = {"steps": steps}
    assert admissions.read(ctx, "queue_ms") == 3.0
    assert admissions.read(ctx, "request_ms") == 30.0
    assert admissions.read(ctx, "tokenize_ms") == 11.0
    # the parent writes no such entries: nothing to read, and no raise
    assert admissions.read({"steps": [{"forwards": 16, "stages": {}}]}, "queue_ms") is None
    assert admissions.read({}, "queue_ms") is None


def test_a_span_half_over_a_gap_takes_half_of_it():
    idle = [(0, 100), (500, 700), (800, 1000)]
    assert host_spans.overlap_ns(idle, [(50, 600)]) == 50 + 100
    assert host_spans.overlap_ns(idle, [(600, 900)]) == 100 + 100  # half of two gaps
    assert host_spans.overlap_ns(idle, [(100, 500)]) == 0
    assert host_spans.overlap_ns(idle, [(0, 40), (60, 90), (650, 2000)]) == 40 + 30 + 50 + 200
    # the same answer as the yardstick's quadratic walk
    spans = [("a", 50, 600), ("b", 650, 2000)]
    got = tr.attribute(idle, spans)
    assert host_spans.overlap_ns(idle, [(50, 600)]) == got["a"]
    assert host_spans.overlap_ns(idle, [(650, 2000)]) == got["b"]


def hand_made(shift: int = 0) -> dict:
    """Two admissions and a chunk; the host's stamps run ``shift`` ns late."""
    dev_ops = [("fusion.1", 1_100, 300), ("fusion.2", 2_100, 300), ("while.3", 3_050, 900)]
    modules = [("jit_forward_paged(1)", 1_100, 300), ("jit_forward_paged(1)", 2_100, 300),
               ("jit_paged_chunk_decode_loop(2)", 3_050, 900)]
    host = [("sched.step", 900, 4_100), ("sched.admit", 900, 3_000),
            ("sched.admit.request", 1_000, 1_900), ("sched.admit.request.prefill_call", 1_050, 1_150),
            ("sched.admit.request", 2_000, 2_900), ("sched.admit.request.prefill_call", 2_050, 2_150),
            ("sched.decode_dispatch", 3_000, 3_100), ("sched.readback", 3_100, 4_000),
            ("sched.release", 4_000, 4_100), ("sched.wait_for_work", 4_200, 4_900)]
    return {"spans": sorted(((n, a + shift, b + shift) for n, a, b in host), key=lambda s: s[1]),
            "anchors": {tr.ANCHOR: 1_000, tr.ANCHOR_END: 5_000},
            "ops": dev_ops, "modules": modules}


def test_idle_is_cut_by_the_spans_that_cover_it():
    r = host_spans.reduce(hand_made())
    # idle in [1000, 5000): 100 + 700 + 650 + 1050
    assert r["idle_ns"] == 2_500 and r["shift_ns"] == 0 and r["pairs"] == 3
    assert r["under_ns"]["sched.admit.request"] == 100 + 500 + 100 + 500
    assert r["started"]["sched.admit.request"] == 2
    assert r["under_ns"]["sched.admit.request.prefill_call"] == 50 + 50
    assert r["under_ns"]["sched.wait_for_work"] == 700
    # what no span covers: 4100..4200 and 4900..5000
    assert r["covered_ns"] == 2_500 - 200


def test_a_negative_clock_shift_is_found_and_taken_out():
    """The host's stamps 300 ns late: the first prefill reads as starting
    250 ns BEFORE the call that launched it. Shifted back by that much, no
    program starts before its launch, and what is left of the error (50 ns,
    the launch's own latency) is inside the span again."""
    late = hand_made(shift=300)
    shift, pairs = host_spans.clock_shift(late["spans"], late["modules"])
    assert (shift, pairs) == (-250, 3)
    r = host_spans.reduce(late)
    assert r["shift_ns"] == -250
    assert r["under_ns"]["sched.admit.request.prefill_call"] == 0 + 0  # 1100..1200 is busy
    assert r["under_ns"]["sched.admit.request"] == host_spans.reduce(hand_made(50))["under_ns"][
        "sched.admit.request"]
    # clocks that agree are left alone, however late a program starts
    assert host_spans.clock_shift(hand_made()["spans"], hand_made()["modules"])[0] == 0


def test_readers_find_nothing_without_a_traced_stretch():
    assert host_spans.read({"trace": None}, "attributed_share") is None
    assert scopes.read({"trace": None, "steps": []}, ["layer/ffn"], "paged_chunk_decode_loop") is None
    assert host_spans.reduce({"spans": [], "anchors": {}, "ops": [("f", 0, 1)], "modules": []}) is None


def test_a_scope_matches_whole_path_components():
    path = "jit(paged_chunk_decode_loop)/while/body/jit(forward_paged)/while/body/closed_call/layer/attn_qkv/dot_general"
    assert scopes.in_scope(path, ["layer/attn_qkv"])
    assert not scopes.in_scope(path, ["layer/attn"])  # not a prefix of a component
    assert scopes.in_scope(path.replace("attn_qkv", "attn/kv_gather"), ["kv_gather"])
    assert not scopes.in_scope("", ["layer/ffn"])


def test_host_spans_on_the_recorded_trace():
    """Two admissions and a chunk recorded on a TPU v5e: the device's clock
    reads ~1.2 ms ahead of the host's there (the chunk program "starts"
    1.26 ms before the ``sched.decode_dispatch`` that launched it)."""
    data = recorded()
    assert data["device_kind"] == "TPU v5 lite"
    shift, pairs = host_spans.clock_shift(data["spans"], data["modules"])
    assert pairs == 3 and -1_400_000 < shift < -1_100_000
    r = host_spans.reduce(data)
    assert r["shift_ns"] == shift
    # three programs of microseconds in a 14.5 ms stretch: nearly all idle
    assert 14_000_000 < r["idle_ns"] < 14_516_000
    # the 2 ms the recording sleeps under no span, and the stretch's two
    # ends, are what no span covers
    assert 0.70 < r["covered_ns"] / r["idle_ns"] < 0.85
    assert 3_000_000 < r["under_ns"]["sched.wait_for_work"] < 3_400_000
    assert r["under_ns"]["sched.admit.request"] <= r["under_ns"]["sched.step"]
    # shifted by 1.26 ms, the first request starts before the anchor: one of
    # the two starts inside the stretch
    assert r["started"]["sched.admit.request"] == 1
    assert r["started"]["sched.admit.request.prefill_call"] == 2


def test_scopes_on_the_recorded_trace():
    data = recorded()
    assert set(data["scope"].values()) == {
        "jit(paged_chunk_decode_loop)/while/body/lm_head/div",
        "jit(paged_chunk_decode_loop)/while/body/layer/ffn/dot_general",
        "jit(forward_paged)/layer/ffn/dot_general"}
    chunk = scopes.scope_ns(data, ["layer/ffn"], "paged_chunk_decode_loop")
    ffn = chunk["ns"]
    head = scopes.scope_ns(data, ["lm_head"], "paged_chunk_decode_loop")["ns"]
    (program_ns,) = [d for n, _, d in data["modules"] if "paged_chunk_decode_loop" in n]
    assert chunk["runs"] == 1 and 0 < head < ffn and ffn + head <= program_ns
    # the recorded chunk is a while loop of four forwards: counted in the
    # same execution, from the occurrences of the op under ``lm_head``
    assert chunk["forwards"] == 4
    # listing a scope twice, or a scope inside another, counts an op once
    assert scopes.scope_ns(data, ["layer/ffn", "layer"], "paged_chunk_decode_loop")["ns"] == ffn
    # the admission's program has its own ops, under the same scope name,
    # and no loop: no forwards to divide by
    pre = scopes.scope_ns(data, ["layer/ffn"], "forward_paged")
    assert pre["runs"] == 2 and 0 < pre["ns"] < ffn and pre["forwards"] == 0
    assert scopes.scope_ns(data, ["layer/ffn"], "no_such_program")["runs"] == 0


def test_scopes_divides_by_the_forwards_of_the_stretch_and_warns_without_scopes(monkeypatch, capsys):
    """Per forward = by the forwards counted in the traced executions, not
    by the ledger's mean per chunk over the window (a stretch of chunks of
    16 and of 1 moved the reading by 13 % with that divisor); a program
    whose operations carry no scope path is said so, and reads nothing."""
    data = recorded()
    monkeypatch.setattr(scopes, "run_trace", lambda ctx: data)
    ctx = {"trace": {}, "steps": [{"forwards": 16}]}  # the ledger says 16 a chunk; the trace holds 4
    ffn = scopes.scope_ns(data, ["layer/ffn"], "paged_chunk_decode_loop")["ns"]
    assert scopes.read(ctx, ["layer/ffn"], "paged_chunk_decode_loop") == pytest.approx(ffn / 1e6 / 4)
    out = capsys.readouterr().out
    assert "4 forwards" in out and "WARNING" not in out
    assert scopes.read(ctx, ["layer/ffn"], "forward_paged") is None  # no loop, no ``lm_head``: no forwards
    # the same program out of an older compile cache: every op still has a
    # path (the primitives' own), none a scope
    bare = recorded()
    bare["scope"] = {k: v.replace("lm_head/", "").replace("layer/ffn/", "") for k, v in bare["scope"].items()}
    monkeypatch.setattr(scopes, "run_trace", lambda ctx: bare)
    assert scopes.read(ctx, ["layer/ffn"], "paged_chunk_decode_loop") is None
    assert "WARNING: none of its operations is under 'lm_head'" in capsys.readouterr().out


def test_program_roofline_divides_by_the_forwards_of_the_traced_executions():
    """The same divisor as ``scopes``: the recorded chunk ran four forwards,
    whatever the ledger's mean over the window says (16 here)."""
    from benchmark.lib import peaks as pk
    from benchmark.readers import roofline

    data = recorded()
    model = {"hidden_size": 4096, "intermediate_size": 14336, "num_hidden_layers": 32,
             "num_attention_heads": 32, "num_key_value_heads": 8, "vocab_size": 32000}
    peaks = pk.peaks_for("TPU v5 lite")
    ctx = {"trace": {"plane": data}, "peaks": peaks, "model": model, "window_s": 1.0,
           "serving": {"quant": "int8", "fast_forward": 8}, "prefix_tokens": 879,
           "tokens_per_request": 34.0, "steps": [{"forwards": 16, "occupancy": 32, "tokens": 720}] * 3}
    (program_ns,) = [d for n, _, d in data["modules"] if "paged_chunk_decode_loop" in n]
    floor, _ = pk.forward_floor_s(model, peaks, 1, 32, 45, 879 + 17)  # 720 tokens in 16 forwards: 45 real positions
    got = roofline.read(ctx, "program_roofline", "paged_chunk_decode_loop")
    assert got == pytest.approx(100.0 * floor / (program_ns / 1e9 / 4))
    # a program with no loop has no forwards to divide by; no trace, nothing to read
    assert roofline.read(ctx, "program_roofline", "forward_paged") is None
    assert roofline.read(dict(ctx, trace=None), "program_roofline", "paged_chunk_decode_loop") is None
    assert roofline.read(dict(ctx, trace=None), "weight_read_util") > 0  # needs no trace


def test_op_scopes_reads_the_metadata_stat_off_the_wire():
    """A hand-built XSpace: one plane, one stat name, two event metadata."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)

    def field(no, payload):  # length-delimited
        return varint(no << 3 | 2) + varint(len(payload)) + payload

    def num(no, n):
        return varint(no << 3) + varint(n)

    stat_meta = field(5, num(1, 7) + field(2, num(1, 7) + field(2, b"tf_op")))
    other = field(5, num(1, 8) + field(2, num(1, 8) + field(2, b"flops")))
    ev1 = field(4, num(1, 1) + field(2, num(1, 1) + field(2, b"%fusion.1 = f32[] fusion()")
                                     + field(5, num(1, 8) + num(3, 99))
                                     + field(5, num(1, 7) + field(5, b"jit(f)/layer/ffn/dot_general:"))))
    ev2 = field(4, num(1, 2) + field(2, num(1, 2) + field(2, b"%copy.2 = f32[] copy()")))
    plane = field(1, num(1, 3) + field(2, b"/device:TPU:0") + ev1 + ev2 + stat_meta + other)
    decoy = field(1, field(2, b"/host:CPU") + ev2)
    import tempfile

    with tempfile.NamedTemporaryFile(suffix=".xplane.pb") as f:
        f.write(decoy + plane)
        f.flush()
        assert tr.op_scopes(f.name, "/device:TPU:0") == {
            "%fusion.1 = f32[] fusion()": "jit(f)/layer/ffn/dot_general"}
        assert tr.op_scopes(f.name, "/device:TPU:1") == {}
