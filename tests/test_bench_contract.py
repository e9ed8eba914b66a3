"""The driver contracts of the two root scripts, run as subprocesses the way
the driver runs them.

``python bench.py`` lands exactly one parseable JSON row on stdout.

``python chip_smoke.py`` is the standing proof that the main path starts on
the chip: without a TPU it must refuse before any stage runs and print no
result; its one explicit switch rehearses the same stages on the CPU and
says so in every line."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _run(script: str, *args: str, timeout: int) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # the explicit CPU run; the suite holds no chip
    env.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
    return subprocess.run(
        [sys.executable, str(ROOT / script), *args], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=timeout)


@pytest.mark.slow
def test_bench_emits_one_parseable_row():
    proc = _run("bench.py", timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
    assert len(lines) == 1, f"stdout must be exactly ONE JSON row: {lines}"
    row = json.loads(lines[0])
    assert row["metric"] == "voice_to_intent_p50_e2e"
    assert row["unit"] == "ms"
    assert row["value"] > 0
    assert row["vs_baseline"] > 0
    assert row["backend"] == "cpu"
    assert 0.0 <= row["spec_hit_rate"] <= 1.0
    # the stderr narrative carries the breakdown the JSON can't
    assert "e2e p50" in proc.stderr


def test_chip_smoke_refuses_without_a_tpu():
    """No switch, no TPU (JAX_PLATFORMS=cpu cannot select the rehearsal):
    non-zero exit before any stage, and no JSON result."""
    proc = _run("chip_smoke.py", timeout=300)
    assert proc.returncode != 0
    assert "REFUSED" in proc.stdout and "No stage ran" in proc.stdout
    assert "stage " not in proc.stdout.replace("No stage ran", "")
    assert "REHEARSAL" not in proc.stdout
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


@pytest.mark.slow
def test_chip_smoke_cpu_rehearsal_passes_and_says_so():
    proc = _run("chip_smoke.py", "--rehearse-cpu", timeout=1500)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    ours = [ln for ln in lines if ln.startswith("[chip_smoke]")]
    assert ours and all("REHEARSAL platform=cpu" in ln for ln in ours)
    # the LAST line is the driver's contract: exactly these keys, no more
    last = json.loads(lines[-1])
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"
    assert isinstance(last["device"]["kind"], str)
    assert type(last["device"]["count"]) is int
    # the detail rides one line above it, labelled like every other line
    head, _, body = lines[-2].partition(" REPORT ")
    assert head == "[chip_smoke] REHEARSAL platform=cpu"
    report = json.loads(body)
    assert report["ok"] is True
    assert report["rehearsal"] == "REHEARSAL platform=cpu"
    assert report["device"] == last["device"]
    assert {k: v["pass"] for k, v in report["stages"].items()} == {
        "K": True, "A": True, "B": True, "C": True}
