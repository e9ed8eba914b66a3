"""ISSUE 36's reader and metric files: ``readers/step_fields.py`` on
hand-written records, every new ``layer_metrics`` file against the manifest,
and the program's new metric names in its lint and its catalog."""

import pathlib
import sys

import pytest

from benchmark.lib import manifest as mf
from benchmark.readers import counters, host_spans, step_fields

ROOT = mf.ROOT
M = mf.load_manifest()
NEW = sorted(p.stem for p in (mf.BENCH_DIR / "layer_metrics").glob("*.json")
             if p.stem.split(".")[0] in {
                 "admit_head_ms", "admit_head_off_cpu_share", "admit_head_others_cpu_ms",
                 "idle_in_admit_head_ms", "step_gap_ms", "host_lock_wait_ms_per_step",
                 "gc_pause_ms_per_step", "gc_longest_pause_ms", "deliver_ms_mean"})

# three steps of a window; the second admitted nobody, the third predates the keys
STEPS = [
    {"wall_ms": 700.0, "admitted": 21, "head_ms": 24.0, "head_cpu_ms": 6.0, "gap_ms": 3.0,
     "lock_wait_ms": 0.5, "gc_ms": 1.5, "gc_max_ms": 1.0},
    {"wall_ms": 500.0, "head_ms": 0.2, "head_cpu_ms": 0.2, "gap_ms": 0.4,
     "lock_wait_ms": 0.1, "gc_ms": 0.0, "gc_max_ms": 0.0},
    {"wall_ms": 710.0, "admitted": 20, "head_ms": 16.0, "head_cpu_ms": 4.0, "gap_ms": 5.0,
     "lock_wait_ms": 0.0, "gc_ms": 45.0, "gc_max_ms": 44.0},
    {"wall_ms": 720.0, "admitted": 22},
]


@pytest.mark.parametrize("args,want", [
    ({"what": "head_ms"}, 16.0),  # the median over the steps that hold the key
    ({"what": "head_ms", "where": "admitted"}, 20.0),
    ({"what": "gc_max_ms", "stat": "max"}, 44.0),
    ({"what": "gc_ms", "stat": "sum"}, 46.5),
    ({"what": "gc_ms", "stat": "sum", "per": "steps"}, 15.5),  # of the three that hold it
    ({"what": "head_ms", "minus": "head_cpu_ms", "stat": "sum"}, 30.0),
    ({"what": "head_ms", "minus": "head_cpu_ms", "over": "head_ms", "stat": "sum",
      "where": "admitted", "scale": 100.0}, 75.0),  # a share of SUMS: (18 + 12) / 40
    ({"what": "no_such_key"}, None),
    ({"what": "head_ms", "minus": "no_such_key"}, None),
    ({"what": "head_ms", "where": "no_such_key"}, None),
])
def test_step_fields_reads_a_record_key(args, want):
    got = step_fields.read({"steps": STEPS}, **args)
    assert got == (pytest.approx(want) if want is not None else None)


def test_step_fields_gives_nothing_without_steps_and_refuses_an_unknown_stat():
    assert step_fields.read({}, what="head_ms") is None
    assert step_fields.read({"steps": [{"wall_ms": 1.0}]}, what="gap_ms") is None  # the parent's records
    assert step_fields.read({"steps": [{"head_ms": 0.0, "admitted": 1}]}, what="head_ms",
                            over="head_ms", stat="sum") is None  # nothing to divide by
    with pytest.raises(ValueError):
        step_fields.read({"steps": STEPS}, what="head_ms", stat="mean")
    with pytest.raises(ValueError):
        step_fields.read({"steps": STEPS}, what="head_ms", per="requests")


def test_the_existing_readers_give_nothing_where_the_program_wrote_nothing():
    """What the PARENT's traced run reads for the metrics that go through the
    readers the benchmark already had: no ``sched.admit.head`` span, no
    ``brain.parse_deliver_ms`` counter — nothing, and no raise."""
    trace = {"plane": {"reduced": {"under_ns": {"sched.admit": 5}, "started": {"sched.admit": 1},
                                   "idle_ns": 10, "covered_ns": 5}}}
    assert host_spans.read({"trace": trace}, what="idle_ms_per_span", span="sched.admit.head") is None
    assert counters.read({"counters": {"brain.parse_completed": 3.0}, "window_s": 45.0},
                         num="brain.parse_deliver_ms", den="brain.parse_completed") is None
    assert counters.read({"counters": {"brain.parse_completed": 4.0, "brain.parse_deliver_ms": 6.0},
                          "window_s": 45.0}, num="brain.parse_deliver_ms",
                         den="brain.parse_completed") == 1.5


def test_every_new_metric_is_a_file_and_an_entry_behind_what_the_manifest_had():
    """PR 36 appended these sixteen; PRs 37-42 appended behind them (until
    PR 42 this asserted they were the manifest's LAST and failed from PR 37
    on), so: every one is an entry, in one run, behind every entry the
    manifest had before them."""
    assert len(NEW) == 16
    names = [m["name"] for m in M["per_layer"]]
    at = sorted(names.index(n) for n in NEW)
    assert at == list(range(at[0], at[0] + len(NEW)))  # appended together
    assert names[at[0] - 1] == "admit_rows_per_call.solo"  # PR 35's last, folded or not
    assert len(names) <= 128 and mf.validate(M) == []


@pytest.mark.parametrize("metric", NEW)
def test_a_new_metric_names_cells_that_exist_and_report_what_it_moves(metric):
    spec = mf.load_layer_metric(metric)
    entry = next(m for m in M["per_layer"] if m["name"] == metric)
    for key in ("name", "layer", "unit", "better", "source", "moves", "workloads"):
        assert spec[key] == entry[key], key
    assert spec["better"] == "lower" and spec["layer"] in ("batcher", "brain")
    reader = mf.load_code("readers", spec["reader"])
    assert spec["source"] == {"step_fields": "program_span", "host_spans": "device_trace",
                              "counters": "program_counter"}[spec["reader"]]
    cells = {w["name"] for w in M["workloads"]}
    for cell in spec["workloads"]:
        assert cell in cells
        assert spec["moves"] in {m["name"] for m in mf.metrics_of(M, "end_to_end", cell)}
    # the reader takes the file's arguments, and finds nothing in an empty run
    assert reader.read({"steps": [], "counters": {}, "window_s": 45.0, "trace": None},
                       **spec["args"]) is None


def test_the_programs_new_metric_names_are_linted_and_catalogued():
    sys.path.insert(0, str(ROOT / "tools"))
    import metrics_lint

    found = metrics_lint.scan_source(pathlib.Path(ROOT / "tpu_voice_agent"))
    catalog = (ROOT / "docs" / "OBSERVABILITY.md").read_text()
    for name, kind in (("host.gc_collections", "counter"), ("host.gc_pause", "histogram"),
                       ("host.watchdog_late", "histogram"), ("brain.parse_deliver_ms", "counter")):
        assert set(found[name]) == {kind}, (name, found.get(name))
        assert metrics_lint.PINNED[name] == kind
        assert f"| `{name}` | {kind} |" in catalog, name
    assert metrics_lint.main([str(ROOT / "tpu_voice_agent")]) == 0
