"""Run the full bench table (BASELINE.md configs) and print one JSON row per
metric. The root ``bench.py`` (the driver's single headline number) stays
separate; this is the wide table.

Besides streaming every bench's rows to stdout, the run is snapshotted into
``bench_artifacts/BENCH_runall_<ts>.json``: all parsed metric rows per
bench, plus the observability sections (``slo`` / ``stage_latency_ms``,
written by benches that boot real services — bench_faults) merged in, so
BENCH_* files carry the stage decomposition, not just headline numbers.

This runner never imports jax (nor ``common``): each bench is a child that
holds the chip while it runs, one at a time — a parent that had touched JAX
would hold it instead and every child would fail or hang.

Usage: python benches/run_all.py [--quick]
"""

from __future__ import annotations

import datetime
import json
import os
import subprocess
import sys
from pathlib import Path

BENCHES = ["bench_batch.py", "bench_stt.py", "bench_grounding.py",
           "bench_quality.py", "bench_quality_online.py", "bench_faults.py",
           "bench_radix.py", "bench_swarm.py", "bench_chaos.py",
           "bench_steplog.py", "bench_router.py", "bench_handoff.py",
           "bench_fleet.py", "bench_autopilot.py", "bench_cost.py",
           "bench_tenancy.py", "bench_streaming_prefill.py",
           "bench_disagg.py"]
# --quick: the fast subset (quality rows always run — they skip cleanly
# when no checkpoint is configured; the heavy latency benches are dropped;
# the fault drill stays — it is service-level, no model, seconds on CPU;
# the STT bench stays at trimmed stream counts/seconds so the multi-stream
# capacity number lands in every combined artifact; the radix bench runs
# UNTRIMMED — the tiny model makes its full 4-session x 4-turn workload
# ~30 s on CPU, and the turn-2+ prefill-collapse verdict is a mean over
# warm turns whose margin a smaller sample would wobble across the bar)
# the swarm bench stays on --quick too — it is the capacity regression
# gate, service-level with no model, and the quick trims cap its binary
# search at tiny N (seconds on CPU); the chaos bench stays as well — it is
# the fault-containment regression gate (tiny engine, trimmed search) and
# a PR that breaks quarantine/cancellation must fail the quick table too
# the steplog bench stays on --quick too — it is the telemetry-overhead
# regression gate (tiny engine, seconds on CPU), and a PR that makes the
# step ledger cost >2% of a decode chunk must fail the quick table
# the router bench stays on --quick as well — it is the replica-fault-
# domain regression gate (rule-based replicas, no model, trimmed search),
# and a PR that breaks failover/drain must fail the quick table too
# the handoff bench stays on --quick too — it is the STT-failover and
# warm-re-home regression gate (tiny engines, fixed-N drill, seconds on
# CPU), and a PR that breaks zero-lost failover or the warm re-home's
# prefill collapse must fail the quick table as well
# the fleet bench stays on --quick too — it is the gray-failure-detection
# regression gate (rule replicas, no model, trimmed search), and a PR
# that blinds the detector or breaks gray placement demotion must fail
# the quick table as well
# the autopilot bench stays on --quick too — it is the elastic-capacity
# regression gate (the ramp runs on rule replicas with no model; the
# join-stall drill's two tiny engines are the same cost class as the
# handoff bench), and a PR that breaks zero-drop scale-down, bounded
# time-to-scale, or join-stall containment must fail the quick table
# the quality-observatory online drill stays on --quick too — it is the
# quality-regression gate (rule replicas, no model, trimmed capacity
# probes, ~seconds of canary cadence), and a PR that blinds the golden
# canary, breaks the quality-SLO freeze, or makes quality instrumentation
# expensive must fail the quick table as well; the offline bench_quality
# rows run on --quick with EVAL_BACKEND pinned to the rule parser so the
# accuracy trajectory always has a deterministic row to gate
# the cost bench stays on --quick too — it is the efficiency-metering
# regression gate (tiny engine, trimmed workload, seconds on CPU), and a
# PR that breaks exact ledger conservation, makes the cost lanes change
# tokens, or makes metering cost >5% of capacity must fail the quick table
# the tenancy bench stays on --quick too — it is the tenant-isolation
# regression gate (tiny engine, two fixed-N swarm runs, seconds on CPU),
# and a PR that lets an abusive tenant starve premium sessions or disarms
# the token-bucket capacity gate must fail the quick table as well
# the streaming-prefill bench stays on --quick too — it is the warm-start
# regression gate (tiny engines, trimmed rounds/utterances, seconds on
# CPU), and a PR that breaks chunked-admission batch-mate isolation or
# lets prefix feeds stop collapsing the endpoint's prefill debt must
# fail the quick table as well
# the disagg bench stays on --quick too — it is the prefill/decode-
# disaggregation regression gate (tiny engines, trimmed rounds and a
# fixed small capacity search, ~minutes on CPU), and a PR that makes the
# decode pool pay barrier prefills again, breaks KV-stream token
# identity, or leaks blocks on the prefill-kill drill must fail the
# quick table as well
QUICK_BENCHES = ["bench_quality.py", "bench_quality_online.py",
                 "bench_faults.py",
                 "bench_stt.py", "bench_radix.py", "bench_swarm.py",
                 "bench_chaos.py", "bench_steplog.py", "bench_router.py",
                 "bench_handoff.py", "bench_fleet.py", "bench_autopilot.py",
                 "bench_cost.py", "bench_tenancy.py",
                 "bench_streaming_prefill.py", "bench_disagg.py"]
# env trims applied on --quick only when the operator has not pinned them
QUICK_ENV = {"EVAL_BACKEND": "rule",
             "BENCH_QO_MAX_N": "4", "BENCH_QO_UTTERANCES": "2",
             "BENCH_QO_DETECT_TIMEOUT_S": "30",
             "BENCH_STT_SECONDS": "4", "BENCH_STT_STREAMS": "1,4",
             "BENCH_SWARM_MAX_N": "8", "BENCH_SWARM_UTTERANCES": "3",
             "BENCH_CHAOS_MAX_N": "4", "BENCH_CHAOS_UTTERANCES": "2",
             "BENCH_STEPLOG_SESSIONS": "6", "BENCH_STEPLOG_ROUNDS": "2",
             "BENCH_ROUTER_MAX_N": "6", "BENCH_ROUTER_UTTERANCES": "2",
             "BENCH_ROUTER_REPLICAS": "2",
             "BENCH_HANDOFF_STT_STREAMS": "2",
             "BENCH_HANDOFF_STT_UTTERANCES": "2",
             "BENCH_HANDOFF_TURNS": "5",
             "BENCH_FLEET_MAX_N": "6", "BENCH_FLEET_UTTERANCES": "2",
             "BENCH_AUTOPILOT_HIGH_N": "6", "BENCH_AUTOPILOT_UTTERANCES": "2",
             "BENCH_AUTOPILOT_TURNS": "2",
             "BENCH_COST_SESSIONS": "6", "BENCH_COST_ROUNDS": "2",
             "BENCH_TENANCY_PREMIUM_N": "3", "BENCH_TENANCY_ABUSE_N": "3",
             "BENCH_TENANCY_UTTERANCES": "2",
             "BENCH_SPF_ROUNDS": "2", "BENCH_SPF_UTTERANCES": "2",
             "BENCH_SPF_TOKENS": "16",
             "BENCH_DISAGG_ROUNDS": "2", "BENCH_DISAGG_TOKENS": "16",
             "BENCH_DISAGG_MAX_N": "2"}


def _parse_rows(stdout: str) -> list[dict]:
    """Benches emit one JSON object per stdout line (benches/common.emit);
    anything unparseable is narrative and skipped."""
    rows = []
    for line in stdout.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            rows.append(json.loads(line))
        except json.JSONDecodeError:
            continue
    return rows


def _newer_artifacts(art_dir: Path, since: set[Path]) -> list[Path]:
    return sorted(p for p in art_dir.glob("BENCH_*.json") if p not in since)


def main() -> None:
    here = Path(__file__).parent
    root = here.parent
    art_dir = root / "bench_artifacts"
    art_dir.mkdir(exist_ok=True)
    quick = "--quick" in sys.argv[1:]
    failures = 0
    summary: dict = {"quick": quick, "benches": {}}
    pre_existing = set(art_dir.glob("BENCH_*.json"))
    env = None
    if quick:
        env = dict(os.environ)
        for k, v in QUICK_ENV.items():
            env.setdefault(k, v)
    # invariant firewall (ISSUE 11, tools/analyze): the bench table runs on
    # an analyzer-clean tree or not at all — a bench number measured on a
    # tree that violates the serving plane's contracts (unsentineled jit,
    # blocking call on a service loop, undeclared knob) is not a number
    # worth recording. Runs on --quick too: AST-only, ~seconds.
    print("[run_all] tools.analyze (invariant firewall)", file=sys.stderr,
          flush=True)
    firewall = subprocess.run([sys.executable, "-m", "tools.analyze"],
                              cwd=root)
    if firewall.returncode != 0:
        print("[run_all] invariant firewall FAILED — fix or suppress (with "
              "justification) the findings above before benching",
              file=sys.stderr, flush=True)
        sys.exit(1)
    summary["analyze"] = "clean"

    for name in (QUICK_BENCHES if quick else BENCHES):
        print(f"[run_all] {name}", file=sys.stderr, flush=True)
        try:
            proc = subprocess.run(
                [sys.executable, str(here / name)], cwd=root,
                capture_output=True, text=True, timeout=3600, env=env,
            )
        except subprocess.TimeoutExpired as e:
            # count the timeout as this bench's failure and keep going —
            # one slow checkpoint eval must not eat the rest of the table
            failures += 1
            for stream, buf in (("stderr", e.stderr), ("stdout", e.stdout)):
                if buf:
                    out = buf.decode() if isinstance(buf, bytes) else buf
                    (sys.stderr if stream == "stderr" else sys.stdout).write(out)
            print(f"[run_all] {name} TIMED OUT after {e.timeout:.0f}s",
                  file=sys.stderr, flush=True)
            summary["benches"][name] = {"status": "timeout"}
            continue
        sys.stderr.write(proc.stderr)
        sys.stdout.write(proc.stdout)
        sys.stdout.flush()
        entry: dict = {
            "status": "ok" if proc.returncode == 0 else f"failed ({proc.returncode})",
            "rows": _parse_rows(proc.stdout),
        }
        # merge the bench's own artifact (bench_faults carries the SLO
        # verdict + stage decomposition) into the combined snapshot
        for art in _newer_artifacts(art_dir, pre_existing):
            pre_existing.add(art)
            try:
                body = json.loads(art.read_text())
            except (OSError, json.JSONDecodeError):
                continue
            if body.get("bench") == name.removesuffix(".py"):
                entry["artifact"] = art.name
                for key in ("slo", "stage_latency_ms", "runtime_gauges",
                            "stt", "radix", "swarm", "chaos",
                            "steplog", "engine_step", "xla", "hbm",
                            "router", "kv_quant", "handoff", "fleet",
                            "quality", "autopilot", "cost", "tenancy",
                            "prefill", "disagg"):
                    if key in body:
                        entry[key] = body[key]
        summary["benches"][name] = entry
        if proc.returncode != 0:
            failures += 1
            print(f"[run_all] {name} FAILED ({proc.returncode})", file=sys.stderr)

    stamp = datetime.datetime.now().strftime("%Y%m%d_%H%M%S")
    combined = art_dir / f"BENCH_runall_{stamp}.json"
    combined.write_text(json.dumps(summary, indent=1))
    print(f"[run_all] combined artifact: {combined}", file=sys.stderr, flush=True)

    # bench trajectory gate (ISSUE 9, tools/benchdiff.py): diff this
    # artifact against the previous run (and BENCHDIFF_BASELINE when the
    # operator pins one) and fail the table on >10% per-row regressions in
    # the gated direction. BENCHDIFF_SKIP=1 disarms on known-noisy boxes.
    if os.environ.get("BENCHDIFF_SKIP") != "1":
        cmd = [sys.executable, str(root / "tools" / "benchdiff.py"), "--gate"]
        base = os.environ.get("BENCHDIFF_BASELINE")
        if base:
            cmd += ["--baseline", base]
        diff = subprocess.run(cmd, cwd=root)
        if diff.returncode != 0:
            failures += 1
            print("[run_all] benchdiff GATE FAILED (regressions vs previous "
                  "run — see rows above)", file=sys.stderr, flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
