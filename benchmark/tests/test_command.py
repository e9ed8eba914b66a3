"""The command itself: a CPU rehearsal ends in a last line with exactly the
contract's keys and never claims a device number; with no TPU and no
request for the CPU it prints no result."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CMD = [sys.executable, "benchmark/run.py", "--workload", "parse_solo", "--seed", str(2**31 + 11)]


def _run(env_over: dict, *extra: str):
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env.update(env_over, BENCH_RUN="ignored")
    return subprocess.run(CMD + list(extra), cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=600)


def test_cpu_rehearsal_prints_the_contracts_last_line():
    p = _run({"JAX_PLATFORMS": "cpu"}, "--seconds", "3", "--trace", "0")
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(last) == {"correct", "attempted", "failed", "metrics", "device"}
    assert last["correct"] is False  # a CPU run is a rehearsal, never a result
    assert last["device"]["platform"] == "cpu" and set(last["device"]) == {
        "platform", "kind", "count", "memory_peak_bytes"}
    assert set(last["metrics"]) <= {"setup_s", "parse_p50_ms"} and "setup_s" in last["metrics"]
    assert all(set(v) == {"value", "unit"} for v in last["metrics"].values())
    assert "reference decoder" in p.stdout and "-> ok" in p.stdout


def test_without_a_tpu_and_without_asking_for_the_cpu_there_is_no_result():
    p = _run({}, "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert "REFUSED" in p.stderr


def test_alone_in_a_directory_it_prints_nothing(tmp_path):
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload", "parse_solo", "--seed",
                        "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
