"""The reduction from a trace to numbers: on hand-made intervals, and on a
small trace recorded on a TPU v5e (``tools/record_trace.py``)."""

import json
from pathlib import Path

from benchmark.lib import trace as tr

DATA = Path(__file__).parent / "data" / "small_trace.json"
STAGES = ("admit", "prefill", "draft", "decode", "readback", "release")


def test_busy_is_a_union_and_idle_its_complement():
    evs = [("while", 100, 400), ("fusion.1", 100, 150), ("fusion.2", 300, 100), ("copy", 700, 100)]
    clipped = tr.clip(evs, 0, 1000)
    assert tr.busy_ns(clipped) == 500  # nested ops are not counted twice
    assert tr.gaps(clipped, 0, 1000) == [(0, 100), (500, 700), (800, 1000)]
    assert tr.busy_ns(tr.clip(evs, 200, 750)) == 350
    assert tr.self_times(evs) == {"while": 150, "fusion.1": 150, "fusion.2": 100, "copy": 100}


def test_gaps_are_attributed_to_the_host_span_that_covers_them():
    spans = [("admit", 0, 150), ("decode", 150, 520), ("readback", 520, 560), ("release", 560, 760)]
    got = tr.attribute([(0, 100), (500, 700), (800, 1000)], spans)
    assert got == {"admit": 100, "decode": 20, "readback": 40, "release": 140, "unattributed": 200}


def test_stage_spans_tile_a_step_backwards_from_its_wall_stamp():
    steps = [{"t_s": 10.0, "wall_ms": 100.0, "stages": {"admit": 30.0, "decode": 50.0, "release": 20.0}},
             {"t_s": 10.3, "wall_ms": 100.0, "stages": {"decode": 100.0}}]
    spans = tr.stage_spans(steps, offset_ns=-int(9.0e9), stages=STAGES)
    assert [(n, round(a / 1e6), round(b / 1e6)) for n, a, b in spans] == [
        ("admit", 900, 930), ("decode", 930, 980), ("release", 980, 1000),
        ("between_steps", 1000, 1200), ("decode", 1200, 1300)]


def test_reduce_on_the_recorded_trace():
    data = json.loads(DATA.read_text())
    data["host"] = [tuple(e) for e in data["host"]]
    wall = data["anchor_wall_s"]
    out = tr.reduce(data, [], wall, STAGES)
    assert out["anchored"] and 0 < out["busy_s"] < out["window_s"]
    # five ~1024^3 bf16 matmuls with 4 ms of sleep after each: mostly idle
    assert 0.015 < out["window_s"] < 0.2 and out["busy_s"] / out["window_s"] < 0.5
    assert out["device_ops"] and out["device_ops"][0][1] > 0
    assert abs(sum(s for _, s in out["idle_gaps"]) - (out["window_s"] - out["busy_s"])) < 1e-6
    assert [k for k, _ in out["idle_gaps"]] == ["unattributed"]  # no ledger given
    # the device's clock runs ~1.2 ms ahead of the host's in this trace: the
    # first program starts BEFORE the anchor the host wrote ahead of its
    # launch, so the anchored window holds four of the five whole
    assert sum(p["count"] for p in out["programs"].values()) in (4, 5)
    # a step ledger laid over the window names the host stage behind the gaps
    step = {"t_s": wall + out["window_s"], "wall_ms": out["window_s"] * 1e3,
            "stages": {"readback": out["window_s"] * 1e3}}
    named = tr.reduce(data, [step], wall, STAGES)
    assert named["idle_gaps"][0][0] == "readback"


def test_trace_reader_times_a_program_on_the_device_per_execution():
    from benchmark.readers import trace as reader

    data = json.loads(DATA.read_text())
    data["host"] = [tuple(e) for e in data["host"]]
    ctx = {"trace": tr.reduce(data, [], data["anchor_wall_s"], STAGES)}
    ms = reader.read(ctx, "program_ms", ["jit__lambda"])
    assert 0.0120 < ms < 0.0130  # each recorded execution took 12.6 us on the device
    share = reader.read(ctx, "program_share", ["jit__lambda"])
    # a program's span is its operations plus a little launch time
    assert 0 < (100 - reader.read(ctx, "idle_share")) <= share < 1.01 * (100 - reader.read(ctx, "idle_share"))
    assert reader.read(ctx, "program_ms", ["no_such_program", "jit__lambda"]) is None
    assert reader.read({"trace": None}, "program_ms", ["jit__lambda"]) is None


def test_the_spans_readers_get_the_first_plane_out_of_the_same_pass():
    """``reduce`` hands on what ``load_xplane`` read, in the form the
    readers of the program's spans take: the file is not opened again."""
    trace = {"device": {"/device:TPU:1": {"XLA Ops": [("b", 5, 1)]},
                        "/device:TPU:0": {"XLA Ops": [("%f = f32[] fusion()", 100, 50)],
                                          "XLA Modules": [("jit_f(1)", 90, 70)], "Steps": []}},
             "host": [(tr.ANCHOR, 80, 1), ("sched.step", 85, 100), (tr.ANCHOR_END, 200, 1)],
             "scope": {"%f = f32[] fusion()": "jit(f)/layer/ffn/dot_general"}}
    plane = tr.reduce(trace, [], 0.0, STAGES)["plane"]
    assert plane == tr.first_plane(trace) == {
        "spans": [("sched.step", 85, 185)], "anchors": {tr.ANCHOR: 80, tr.ANCHOR_END: 200},
        "ops": [("%f = f32[] fusion()", 100, 50)], "modules": [("jit_f(1)", 90, 70)],
        "scope": {"%f = f32[] fusion()": "jit(f)/layer/ffn/dot_general"}}
    from benchmark.readers import host_spans

    assert host_spans.run_trace({"trace": {"plane": plane}}) is plane
    assert host_spans.run_trace({"trace": None}) is None and host_spans.run_trace({}) is None
    # a trace recorded before scopes were kept still reduces
    old = json.loads(DATA.read_text())
    old["host"] = [tuple(e) for e in old["host"]]
    assert "scope" not in old and tr.first_plane(old)["scope"] == {}


def test_a_trace_without_device_operations_reduces_to_nothing():
    assert tr.reduce({"device": {}, "host": []}, [], 0.0, STAGES) is None
    assert tr.reduce({"device": {"/device:TPU:0": {"XLA Ops": []}}, "host": []}, [], 0.0, STAGES) is None
