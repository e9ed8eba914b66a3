"""BENCHMARK.json and the data files it names hold together."""

import json
import re
from pathlib import Path

import pytest

from benchmark.lib import manifest as mf

M = mf.load_manifest()
BENCH = mf.BENCH_DIR


def test_manifest_meets_the_contracts_static_rules():
    assert mf.validate(M) == []
    assert M["paths"] == ["benchmark"] and M["command"][:2] == ["python3", "benchmark/run.py"]
    assert 1 <= M["run_seconds"] <= 51
    assert len(json.dumps(M)) < 64 * 1024


def test_budget_fits_a_full_check_with_24_cells():
    assert (2 + 14 * 24) * (M["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("cell", [w["name"] for w in M["workloads"]])
def test_cell_resolves_to_files_and_code(cell):
    c = mf.load_cell(M, cell)
    assert c["cell"]["chips"] == 1
    assert c["config_entry"]["file"].startswith("benchmark/configs/")
    assert hasattr(mf.load_code("builders", c["config"]["builder"]), "build")
    gen = mf.load_code("generators", c["traffic"]["generator"])
    assert hasattr(gen, "run") and hasattr(gen, "warm")
    assert mf.code_problems(c) == []
    names = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2 and c["per_layer"]


@pytest.mark.parametrize("metric", [m["name"] for m in M["per_layer"]])
def test_layer_metric_file_agrees_with_the_manifest(metric):
    entry = next(m for m in M["per_layer"] if m["name"] == metric)
    held = json.loads((BENCH / "layer_metrics" / f"{metric}.json").read_text())
    assert "workloads" not in held  # the list is BENCHMARK.json's alone: a cell joins it and edits no file
    for key in ("name", "layer", "unit", "better", "source", "moves"):
        assert held[key] == entry[key], key
    moved = next(m for m in M["end_to_end"] if m["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(moved.get("workloads", entry["workloads"]))
    for cell in entry["workloads"]:  # resolved for each cell: the entry's fields, that cell's reader
        spec = mf.load_layer_metric(metric, cell)
        assert {k: spec[k] for k in entry} == entry
        assert hasattr(mf.load_code("readers", spec["reader"]), "read")


def test_validate_refuses_a_metric_whose_cells_do_not_report_what_it_moves():
    bad = json.loads(json.dumps(M))
    flood = next(i for i, m in enumerate(bad["per_layer"]) if m["moves"] == "out_tokens_per_s")
    bad["per_layer"][flood]["workloads"] = ["parse_solo"]  # which does not report out_tokens_per_s
    assert any("does not report" in p for p in mf.validate(bad))
    bad = json.loads(json.dumps(M))
    bad["per_layer"][0]["unit"] = "tokens per second"
    assert any("bad unit" in p for p in mf.validate(bad))


def test_code_problems_names_every_piece_of_code_that_is_not_there():
    cell = mf.load_cell(M, "parse_solo")
    assert mf.code_problems(cell) == []
    bad = json.loads(json.dumps(cell))
    bad["config"]["reference"] = "whisperr"
    bad["config"]["builder"] = "client"  # no such file under builders/
    bad["traffic"]["generator"] = "_http"  # a module there, without warm / run
    bad["config"]["decoder"] = {"reference": "../decoder"}
    got = mf.code_problems(bad)
    assert len(got) == 4 and all("Error" in g for g in got)
    assert any(g.startswith("generator '_http'") and "lacks" in g for g in got)
    assert any(g.startswith("reference '../decoder'") and "bad reference name" in g for g in got)
    del bad["config"]["builder"]
    assert any("builder None" in g for g in mf.code_problems(bad))


def test_the_voice_configuration_pulls_in_the_decoder_file_unchanged():
    voice = mf.load_json("benchmark/configs/voice-whisper-large-v3-mistral-7b.json")
    solo = mf.load_cell(M, "parse_solo")["config"]
    assert mf.load_json(f"benchmark/configs/{voice['decoder_config']}.json") == solo
    assert solo["serving"]["max_len"] < solo["sliding_window"]


def test_the_entries_held_back_would_hold_together_with_the_manifest():
    held = mf.load_json("benchmark/held_back.json")
    merged = json.loads(json.dumps(M))
    for kind in ("configs", "workloads", "end_to_end"):
        merged[kind] += [dict(e, bound=0.1) if kind == "end_to_end" else e for e in held[kind]]
    cells = {w["name"] for w in held["workloads"]}
    for path in sorted((BENCH / "layer_metrics").glob("*.json")):
        spec = json.loads(path.read_text())  # a file names cells only while no manifest does
        if set(spec.get("workloads", [])) & cells:
            merged["per_layer"].append({k: spec[k] for k in (
                "name", "unit", "better", "source", "layer", "moves", "workloads")})
    assert len(merged["per_layer"]) > len(M["per_layer"]) and mf.validate(merged) == []
    assert mf.load_json(held["configs"][0]["file"])["builder"] == "voice_stack"


def test_every_data_file_names_code_that_exists_whether_or_not_the_manifest_names_it():
    """The voice cell's files are proven on the chip and held back from the
    manifest (PERF.md section 7): a later PR adds entries, not files."""
    for path in sorted((BENCH / "configs").glob("*.json")):
        conf = json.loads(path.read_text())
        assert mf.load_code("builders", conf["builder"]) and mf.load_code("reference", conf["reference"])
    for path in sorted((BENCH / "traffic").glob("*.json")):
        gen = mf.load_code("generators", json.loads(path.read_text())["generator"])
        assert hasattr(gen, "run") and hasattr(gen, "warm"), path
    for path in sorted((BENCH / "layer_metrics").glob("*.json")):
        spec = json.loads(path.read_text())
        assert spec["name"] == path.stem and spec["source"] in mf.SOURCES, path
        assert hasattr(mf.load_code("readers", spec["reader"]), "read"), path


def test_nothing_under_benchmark_imports_the_repos_other_harnesses():
    pat = re.compile(r"^\s*(from|import)\s+(bench|benches|tools|chip_smoke)\b", re.M)
    for path in BENCH.rglob("*.py"):
        if "tests" in path.parts:
            continue
        assert not pat.search(path.read_text()), path


def test_file_names_use_only_name_characters():
    for path in BENCH.rglob("*"):
        if "__pycache__" in path.parts:
            continue
        rel = path.relative_to(BENCH.parent).as_posix()
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", rel), rel


def test_pool_arithmetic_written_in_the_configuration_holds():
    s = mf.load_cell(M, "parse_solo")["config"]["serving"]
    blocks = s["max_len"] // s["block_size"]
    shared = 879 // s["block_size"]
    assert shared + s["batch_slots"] * (blocks - shared) + 1 <= s["pool_blocks"]
