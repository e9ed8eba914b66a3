"""Shared bench harness bits.

Every bench prints one JSON row per metric:
``{"metric", "value", "unit", "vs_baseline"}`` — the same contract as the
root ``bench.py`` the driver runs (BASELINE.md targets; the reference
publishes no numbers, SURVEY.md §6, so vs_baseline compares against the
BASELINE.json north-star budgets).
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

# benches run as scripts; make the repo root importable
_ROOT = str(Path(__file__).parent.parent)
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def checkpoints_dir() -> str:
    """Repo-root-anchored checkpoints/ (benches run with cwd benches/)."""
    return str(Path(_ROOT) / "checkpoints")


# Every bench imports common before touching jax, so this is each bench
# process's first JAX touch: the compile cache is placed, and the devices
# must be a TPU — or the CPU when JAX_PLATFORMS=cpu asked for it; anything
# else exits (ops.backend.measurement_devices). From here on THIS process
# holds the chip: a bench may only spawn children that stay off JAX
# (bench_fleet's fleetview render does; run_all.py, the parent of every
# bench, never imports this module).
from tpu_voice_agent.ops.backend import measurement_devices  # noqa: E402
from tpu_voice_agent.utils.compilecache import place_compile_cache  # noqa: E402

place_compile_cache()
_DEVICES = measurement_devices()


def on_tpu() -> bool:
    return _DEVICES[0].platform == "tpu"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def emit(metric: str, value: float, unit: str, vs_baseline: float | None = None) -> None:
    row = {"metric": metric, "value": round(value, 3), "unit": unit}
    if vs_baseline is not None:
        row["vs_baseline"] = round(vs_baseline, 3)
    print(json.dumps(row), flush=True)


def percentile(xs, q) -> float:
    import numpy as np

    return float(np.percentile(xs, q))


def snapshot_observability(service_url: str, timeout_s: float = 5.0) -> dict:
    """One service's SLO verdict + per-stage latency decomposition, shaped
    for embedding in a BENCH_* artifact (``{"slo": ..., "stage_latency_ms":
    ..., "runtime_gauges": ...}``). Benches call it before teardown so the
    artifact carries the stage breakdown, not just headline numbers;
    failures degrade to {} — observability must never fail a bench run."""
    import json as _json
    import urllib.request

    try:
        with urllib.request.urlopen(service_url.rstrip("/") + "/metrics",
                                    timeout=timeout_s) as r:
            m = _json.loads(r.read().decode())
    except Exception as e:
        log(f"observability snapshot failed: {e}")
        return {}
    out = {
        "slo": m.get("slo"),
        "stage_latency_ms": m.get("local", {}).get("latency_ms", {}),
        "runtime_gauges": m.get("runtime", {}).get("gauges", {}),
        "runtime_counters": m.get("runtime", {}).get("counters", {}),
    }
    # the device-plane decomposition (ISSUE 9): every bench artifact that
    # touches an engine-backed service carries the step-ledger stage
    # histograms, the compile-sentinel counters, and the live HBM ledger
    # as their own sections — empty dicts when the scraped service runs no
    # engine (rule-based brain, executor)
    # the fleet telemetry plane (ISSUE 14) rides the same lift: gray
    # demotion counts, scrape cadence, and outlier scores land in every
    # artifact scraped off a router-fronted stack
    hists = m.get("runtime", {}).get("latency_ms", {})
    for section, prefix in (("engine_step", "engine.step."),
                            ("xla", "xla."), ("hbm", "hbm."),
                            ("fleet", "fleet."), ("cost", "cost.")):
        sec: dict = {}
        for src in (out["runtime_gauges"], out["runtime_counters"], hists):
            sec.update({k: v for k, v in src.items() if k.startswith(prefix)})
        out[section] = sec
    # the cost observatory's roofline gauges (ISSUE 17) live under
    # engine.* by design (they ARE engine utilization) — lift them into
    # the cost section so every artifact carries MFU/MBU beside the spend
    # counters
    for k in ("engine.mfu", "engine.mbu", "engine.mfu_prefill"):
        if k in out["runtime_gauges"]:
            out["cost"][k] = out["runtime_gauges"][k]
    return out
