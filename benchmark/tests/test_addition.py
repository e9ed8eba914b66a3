"""A cell of a decoder that is NOT dense is added to the benchmark as new
files and appended manifest entries only — and a name that points at no
code is refused before anything is built. Both on a copy of the benchmark
in a temporary directory (``data/routed_fixture/README.md``)."""

import hashlib
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
FIXTURE = Path(__file__).parent / "data" / "routed_fixture"


def _hashes(top: Path) -> dict:
    return {p.relative_to(top).as_posix(): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(top.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts and ".jax_cache" not in p.parts}


@pytest.fixture
def copy(tmp_path):
    """BENCHMARK.json + benchmark/ copied, the program linked beside them."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(ROOT / "tpu_voice_agent", tmp_path / "tpu_voice_agent")
    return tmp_path


def _add(copy: Path, files: Path, entries: dict) -> None:
    """New files only, appended entries only."""
    for src in sorted(p for p in files.rglob("*") if p.is_file()):
        dst = copy / "benchmark" / src.relative_to(files)
        assert not dst.exists(), f"{dst} is a file the benchmark already has"
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(src, dst)
    manifest = json.loads((copy / "BENCHMARK.json").read_text())
    for kind in ("configs", "workloads", "per_layer"):
        manifest[kind] += entries.get(kind, [])
    for kind, key in (("end_to_end", "append_workloads"), ("per_layer", "append_per_layer")):
        for metric, cells in entries.get(key, {}).items():  # the one touch of an entry that is there
            next(m for m in manifest[kind] if m["name"] == metric)["workloads"] += cells
    (copy / "BENCHMARK.json").write_text(json.dumps(manifest, indent=1))


def _run(copy: Path, workload: str, seed: int):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "benchmark/run.py", "--workload", workload, "--seed",
                           str(seed), "--seconds", "3", "--trace", "0"], cwd=copy, env=env,
                          capture_output=True, text=True, timeout=600)


def test_a_routed_expert_cell_is_added_as_files_and_entries_only(copy):
    before = _hashes(copy / "benchmark")
    _add(copy, FIXTURE / "benchmark", json.loads((FIXTURE / "add.json").read_text()))
    p = _run(copy, "routed_solo", 2**31 + 26)
    assert p.returncode == 0, p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(last) == {"correct", "attempted", "failed", "metrics", "device"}
    assert last["device"]["platform"] == "cpu" and last["correct"] is False  # a rehearsal
    assert "setup_s" in last["metrics"] and set(last["metrics"]) <= {"setup_s", "parse_p50_ms"}
    # the comparison ran under the NAME the fixture's configuration gave, against the
    # fixture's own reference: within its tolerance, its control above it, 13 rows
    (line,) = [ln for ln in p.stdout.splitlines() if ln.startswith("[benchmark] reference ")]
    assert line.startswith("[benchmark] reference routed_decoder: ") and line.endswith("-> ok"), line
    assert "13 logit rows" in line and "int4 control" in line
    num = lambda after: float(line.split(after)[1].split()[0].rstrip(",;)"))
    assert num("max|ref| = ") <= num("(tolerance ") < num("int4 control ")
    assert "NOT CORRECT: the served model disagrees" not in p.stdout
    # and nothing the benchmark had was edited to get there
    after = _hashes(copy / "benchmark")
    assert {k: after[k] for k in before} == before and len(after) == len(before) + 6
    # the cell joined two lists the manifest had and reads one of them through a file of its own,
    # found from the entry's and the cell's name; the cell that was there reads what it read
    probe = ("import json; from benchmark.lib import manifest as mf; m = mf.load_manifest(); "
             "c = mf.load_cell(m, 'routed_solo'); print(json.dumps([mf.validate(m), mf.code_problems(c), "
             "sorted(e['name'] for e in c['per_layer']), "
             "[mf.load_layer_metric('decode_program_roofline.solo', w)['reader'] for w in ('routed_solo', 'parse_solo')], "
             "mf.load_layer_metric('rows_per_forward.solo', 'routed_solo')['reader']]))")
    out = subprocess.run([sys.executable, "-c", probe], cwd=copy, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) == [
        [], [], ["decode_program_roofline.solo", "out_tokens_per_req.routed", "rows_per_forward.solo"],
        ["roofline_routed", "roofline"], "counters"]


@pytest.mark.parametrize("key", ["reference", "builder"])
def test_a_name_that_points_at_no_code_is_refused_before_any_build(copy, key):
    entries = json.loads((FIXTURE / "add.json").read_text())
    _add(copy, FIXTURE / "benchmark", entries)
    conf = copy / "benchmark" / "configs" / "routed-test.json"
    data = json.loads(conf.read_text())
    data[key] = "no_such_module"
    conf.write_text(json.dumps(data))
    t0 = time.perf_counter()
    p = _run(copy, "routed_solo", 1)
    assert p.returncode == 2 and time.perf_counter() - t0 < 15
    assert f"REFUSED: cell routed_solo names code that is not there: {key} 'no_such_module'" in p.stderr
    assert p.stdout.strip() == ""  # no result line, no build, no window
