"""Capacity observatory: max concurrent voice sessions at SLO.

Boots the real voice + brain + executor services on sockets (rule-based
brain, fake-page executor, scripted-STT audio path — the same CPU harness
as bench_faults) and turns tools/swarm.py loose on them: N concurrent WS
sessions running the full scenario mix (single-shot, multi-turn, compound,
barge-in, paced/unpaced audio, garbage, abort), binary-searched to the
largest N whose client-side SLO verdict is ``ok`` (utils/slo.py
thresholds). The knee probe's saturation-gauge timeline names **which
resource saturated first** — the bottleneck the next scaling PR must move.

Emits the standard one-JSON-row-per-metric contract plus a
``BENCH_swarm_<ts>.json`` artifact whose ``swarm`` section run_all.py
merges into the combined snapshot (incl. ``--quick`` at trimmed N).

Knobs: BENCH_SWARM_MAX_N (default 192), BENCH_SWARM_UTTERANCES (6),
BENCH_SWARM_THINK_S (0.05), BENCH_SWARM_BRAIN_INFLIGHT (8),
BENCH_SWARM_EXEC_INFLIGHT (8).
"""

from __future__ import annotations

import datetime
import json
import os
import sys
import tempfile
import urllib.request
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))
from common import _ROOT, emit, log, snapshot_observability  # noqa: E402

sys.path.insert(0, str(Path(_ROOT) / "tools"))
import swarm  # noqa: E402


def main() -> None:
    max_n = int(os.environ.get("BENCH_SWARM_MAX_N", "192"))
    utterances = int(os.environ.get("BENCH_SWARM_UTTERANCES", "6"))
    think_s = float(os.environ.get("BENCH_SWARM_THINK_S", "0.05"))
    brain_inflight = int(os.environ.get("BENCH_SWARM_BRAIN_INFLIGHT", "8"))
    exec_inflight = int(os.environ.get("BENCH_SWARM_EXEC_INFLIGHT", "8"))

    tmp = tempfile.mkdtemp(prefix="bench_swarm_")
    urls, servers = swarm.build_local_stack(
        tmp, brain_inflight=brain_inflight, exec_inflight=exec_inflight)
    obs: dict = {}
    flight: dict = {}
    try:
        log(f"binary-searching capacity up to {max_n} sessions "
            f"({utterances} utterances/session, think {think_s}s, "
            f"brain/exec inflight caps {brain_inflight}/{exec_inflight})")
        result = swarm.binary_search_capacity(
            urls["voice"], max_n=max_n,
            sample_urls=list(urls.values()),
            utterances=utterances, think_s=think_s)
        obs = snapshot_observability(urls["voice"])
        # did the overload knee freeze a flight-recorder dump? (the services
        # run in-process here, so the process-global recorder is shared)
        try:
            with urllib.request.urlopen(
                    urls["voice"] + "/debug/flightrecorder", timeout=5) as r:
                body = json.loads(r.read().decode())
            flight = {"frozen": bool(body.get("frozen")),
                      "reason": body.get("reason")}
        except Exception as e:
            log(f"flightrecorder probe failed: {e}")
    finally:
        for srv in servers:
            srv.__exit__(None, None, None)

    cap = result["capacity_sessions"]
    at_cap = result.get("at_capacity") or {}
    knee = result.get("knee")
    sat = (knee or at_cap or {}).get("saturation", {})
    first = sat.get("first_saturated") or sat.get("nearest_bottleneck")
    slo_at_cap = at_cap.get("slo", {})
    log(f"capacity: {cap} sessions at SLO "
        f"({'saturated' if result['saturated'] else 'NOT saturated at max_n'}); "
        f"first saturated resource: {first or 'none'}; "
        f"flight recorder {'FROZE: ' + str(flight.get('reason')) if flight.get('frozen') else 'stayed armed'}")

    emit("swarm_capacity_sessions", float(cap), "sessions")
    if slo_at_cap.get("p50_ms") is not None:
        emit("swarm_p50_at_capacity", slo_at_cap["p50_ms"], "ms")
    if slo_at_cap.get("p99_ms") is not None:
        emit("swarm_p99_at_capacity", slo_at_cap["p99_ms"], "ms")
    if slo_at_cap.get("error_rate") is not None:
        emit("swarm_error_rate_at_capacity", slo_at_cap["error_rate"], "fraction")
    emit("swarm_probes", float(len(result["probes"])), "runs")

    art_dir = Path(_ROOT) / "bench_artifacts"
    art_dir.mkdir(exist_ok=True)
    stamp = datetime.datetime.now().strftime("%Y%m%d_%H%M%S")
    art = art_dir / f"BENCH_swarm_{stamp}.json"
    art.write_text(json.dumps({
        "bench": "bench_swarm",
        "ts": stamp,
        "config": {"max_n": max_n, "utterances": utterances,
                   "think_s": think_s, "brain_inflight": brain_inflight,
                   "exec_inflight": exec_inflight},
        "swarm": {
            "capacity_sessions": cap,
            "saturated": result["saturated"],
            "probes": result["probes"],
            "at_capacity": at_cap,
            "knee": knee,
            "first_saturated": first,
            "flight_recorder": flight,
        },
        **obs,
    }, indent=1))
    log(f"artifact: {art}")


if __name__ == "__main__":
    main()
