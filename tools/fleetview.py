#!/usr/bin/env python
"""Live fleet dashboard: one sparkline row per replica per signal.

The fleet telemetry plane (ISSUE 14) gives every service a time-series
ring (``/debug/timeseries``) and the router a peer-relative gray-failure
detector; this tool is the operator's eyes on both — the time-resolved
"which replica is drifting away from its peers" view a point-in-time
``/health`` poll cannot give:

    python tools/fleetview.py [--router http://127.0.0.1:8095]
        [--watch SECS] [--width N] [--json]
    python tools/fleetview.py --file SAVED.json
    python tools/fleetview.py --self-test

Live mode reads the router's aggregated ``/health`` (replica states:
up / draining / drained / down, GRAY verdicts with outlier scores,
pressure, clock skew) plus the ``/debug/replicas/timeseries`` fan-out,
and renders per replica one sparkline per fleet signal (the same
``FLEET_SIGNALS`` the detector scores — parse wall, SLO p99, decode
wall, tokens/forward, KV utilization, quarantine/poison rates). Gray,
draining, and ejected replicas are highlighted in the roster.

``--file`` renders a saved body instead of polling: a frozen flight dump
(renders the ``fleet`` peer-comparison evidence a gray freeze carries),
a saved ``/debug/replicas/timeseries`` fan-out, or one service's
``/debug/timeseries`` body. ``--self-test`` runs the extraction/render
pipeline on synthetic data (wired into tier-1 via tests/test_fleet.py).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

_ROOT = str(Path(__file__).resolve().parents[1])
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from tpu_voice_agent.services.replicaset import (  # noqa: E402
    FLEET_SIGNALS,
    signal_values,
)

DEFAULT_ROUTER = "http://127.0.0.1:8095"
SPARK = " ▁▂▃▄▅▆▇█"


def fetch_json(url: str, timeout_s: float = 5.0, quiet: bool = False) -> dict:
    try:
        with urllib.request.urlopen(url, timeout=timeout_s) as r:
            body = json.loads(r.read().decode())
        return body if isinstance(body, dict) else {}
    except (urllib.error.URLError, OSError, ValueError) as e:
        if not quiet:
            print(f"[fleetview] {url}: {e}", file=sys.stderr)
        return {}


def sparkline(xs: list[float | None], width: int) -> str:
    """Right-aligned sparkline over the last ``width`` values; gaps (None)
    render as '·'. Scaled per row min..max so shape survives any unit."""
    xs = xs[-width:]
    vals = [x for x in xs if x is not None]
    if not vals:
        return "·" * min(width, max(1, len(xs)))
    lo, hi = min(vals), max(vals)
    span = (hi - lo) or 1.0
    out = []
    for x in xs:
        if x is None:
            out.append("·")
        else:
            out.append(SPARK[1 + int((x - lo) / span * (len(SPARK) - 2))])
    return "".join(out)


def signal_rows(samples: list[dict]) -> dict[str, list[float | None]]:
    """Per-signal value series over a replica's samples (None where the
    sample lacks the signal — a slow replica's sparse windows render as
    gaps, which is itself a signal)."""
    rows: dict[str, list[float | None]] = {name: [] for name, *_ in FLEET_SIGNALS}
    for s in samples:
        vals = signal_values(s)
        for name in rows:
            rows[name].append(vals.get(name))
    # drop signals this replica never reported (an all-gap row is noise)
    return {k: v for k, v in rows.items() if any(x is not None for x in v)}


def _fmt(v: float | None) -> str:
    if v is None:
        return "-"
    return f"{v:.3g}"


def _status_tag(detail: dict) -> str:
    state = detail.get("state", "?")
    if detail.get("gray"):
        sig = detail.get("outlier_signal") or "?"
        return (f"** GRAY ** score {detail.get('outlier_score', 0):.1f} "
                f"on {sig}")
    if state == "down":
        return "** DOWN/EJECTED **"
    if state in ("draining", "drained"):
        return f"** {state.upper()} **"
    return "up"


def render_fleet(health: dict, series: dict[str, list[dict]],
                 width: int = 48) -> str:
    """One dashboard frame: roster header, then per replica a status line
    plus one sparkline row per fleet signal (latest value in the margin)."""
    lines: list[str] = []
    reps = health.get("replicas") or {}
    lines.append(
        f"fleet: {reps.get('total', len(series))} replicas — "
        f"{reps.get('healthy', '?')} healthy, {reps.get('gray', 0)} gray, "
        f"{reps.get('draining', 0)} draining")
    dz = health.get("disagg") or {}
    if dz.get("enabled"):
        # the per-pool roll-up (ISSUE 20): the disaggregated fleet's
        # prefill vs decode split, live export queue, KV stream rate
        pf, dec = dz.get("prefill") or {}, dz.get("decode") or {}
        lines.append(
            f"disagg: prefill {pf.get('admitting', 0)}/{pf.get('total', 0)}"
            f" admitting (queue {pf.get('queue_depth', 0)}), decode "
            f"{dec.get('admitting', 0)}/{dec.get('total', 0)} admitting, "
            f"{_fmt(dz.get('streamed_blocks_per_s'))} KV blocks/s")
    details = {d.get("url"): d for d in health.get("replica_detail") or []}
    urls = list(details) or sorted(series)
    for url in urls:
        d = details.get(url, {})
        samples = series.get(url) or []
        lines.append("")
        role = f"  role={d['role']}" if d.get("role") else ""
        lines.append(
            f"{url}  [{_status_tag(d)}]{role}"
            f"  pressure {_fmt(d.get('pressure'))}"
            f"  skew {1e3 * (d.get('clock_skew_s') or 0.0):+.1f}ms")
        rows = signal_rows(samples)
        if not rows:
            lines.append("  (no timeseries samples)")
            continue
        label_w = max(len(k) for k in rows) + 2
        for name, xs in rows.items():
            latest = next((x for x in reversed(xs) if x is not None), None)
            lines.append(f"  {name.ljust(label_w)}"
                         f"|{sparkline(xs, width)}| {_fmt(latest)}")
    fleet = health.get("fleet") or {}
    if fleet.get("aggregates"):
        lines.append("")
        lines.append("fleet aggregates (median / MAD / max):")
        for name, agg in sorted(fleet["aggregates"].items()):
            lines.append(f"  {name}: {_fmt(agg.get('median'))} / "
                         f"{_fmt(agg.get('mad'))} / {_fmt(agg.get('max'))} "
                         f"(n={agg.get('n')})")
    return "\n".join(lines)


def render_autopilot(desc: dict) -> str:
    """The autopilot panel (ISSUE 16): target vs actual per tier, the
    control signals (load, forecast, streaks, cooldown), and the last
    decisions with their reasons — the operator's answer to "why is the
    fleet this size, and what will the controller do next"."""
    if not desc.get("enabled"):
        return "autopilot: not attached"
    lines: list[str] = []
    b = desc.get("brain") or {}
    lines.append(
        f"autopilot[brain]: target {b.get('target')} / actual "
        f"{b.get('actual')} up (+{b.get('joining', 0)} joining, "
        f"{b.get('draining', 0)} draining) in [{b.get('min')}, "
        f"{b.get('max')}] — load {_fmt(b.get('load'))} forecast "
        f"{_fmt(b.get('forecast'))}, streaks +{b.get('up_streak', 0)}/"
        f"-{b.get('down_streak', 0)}, cooldown "
        f"{_fmt(b.get('cooldown_remaining_s'))}s")
    if b.get("retiring"):
        lines.append(f"  retiring: {', '.join(b['retiring'])}")
    p = desc.get("prefill")
    if p:
        lines.append(
            f"autopilot[prefill]: target {p.get('target')} / actual "
            f"{p.get('actual')} ({p.get('servable')} servable, queue "
            f"{p.get('queue_depth', 0)}), streaks +{p.get('up_streak', 0)}/"
            f"-{p.get('down_streak', 0)}, cooldown "
            f"{_fmt(p.get('cooldown_remaining_s'))}s")
    s = desc.get("stt")
    if s:
        lines.append(
            f"autopilot[stt]: target {s.get('target')} / actual "
            f"{s.get('actual')} ({s.get('healthy')} healthy) in "
            f"[{s.get('min')}, {s.get('max')}], streaks "
            f"+{s.get('up_streak', 0)}/-{s.get('down_streak', 0)}, "
            f"cooldown {_fmt(s.get('cooldown_remaining_s'))}s")
    decisions = desc.get("decisions") or []
    if decisions:
        lines.append("last decisions:")
        for d in decisions[-6:]:
            extra = ""
            if "adopted_tokens" in d:
                extra = f" adopted={d['adopted_tokens']}"
            if "replica" in d:
                extra += f" {d['replica']}"
            lines.append(
                f"  [{d.get('tier')}] {d.get('action')}/{d.get('reason')} "
                f"target {d.get('target')} actual {d.get('actual')} "
                f"signal {_fmt(d.get('signal'))} forecast "
                f"{_fmt(d.get('forecast'))} cooldown "
                f"{_fmt(d.get('cooldown_remaining_s'))}s{extra}")
    return "\n".join(lines)


def render_costs(costs_fan: dict, series: dict[str, list[dict]],
                 width: int = 48) -> str:
    """The efficiency panel (ISSUE 17): per-replica roofline sparklines
    (``engine.mfu`` / ``engine.mbu`` ride the same timeseries ring every
    gauge does), the analytic meter's totals off the
    ``/debug/replicas/costs`` fan-out, and the fleet-wide top-cost
    sessions — the operator's answer to "where are the FLOPs going, and
    who is spending them"."""
    reps = costs_fan.get("replicas") or {}
    lines = ["efficiency (analytic roofline; off-TPU peaks are a "
             "documented CPU proxy):"]
    top_all: list[tuple[float, str, dict]] = []
    for url in sorted(set(reps) | set(series)):
        body = reps.get(url) if isinstance(reps.get(url), dict) else {}
        lines.append("")
        if not body.get("enabled"):
            lines.append(f"{url}  [cost lanes off]")
        else:
            t = body.get("totals") or {}
            eng = body.get("engine") or {}
            pf = (t.get("prefill_flops", 0)
                  + t.get("prefill_cached_flops", 0))
            total = pf + t.get("decode_flops", 0)
            cached = t.get("prefill_cached_flops", 0) / pf if pf else 0.0
            dec = t.get("decode_flops", 0) / total if total else 0.0
            lines.append(
                f"{url}  mfu {_fmt(body.get('mfu'))} mbu "
                f"{_fmt(body.get('mbu'))} prefill-mfu "
                f"{_fmt(body.get('mfu_prefill'))}  chunks "
                f"{eng.get('chunks', 0)}")
            lines.append(
                f"  flops {total:.3g} — decode {dec:.0%}, prefill cache "
                f"hit {cached:.0%}; kv "
                f"{t.get('kv_block_us', 0) / 1e6:.3g} block-s")
            for sess in body.get("top_sessions") or []:
                fl = (sess.get("prefill_flops", 0)
                      + sess.get("decode_flops", 0))
                top_all.append((fl, url, sess))
        samples = series.get(url) or []
        rows = {k: [s.get("gauges", {}).get(k) for s in samples]
                for k in ("engine.mfu", "engine.mbu", "engine.mfu_prefill")}
        for name, xs in rows.items():
            if not any(x is not None for x in xs):
                continue
            latest = next((x for x in reversed(xs) if x is not None), None)
            lines.append(f"  {name.ljust(20)}"
                         f"|{sparkline(xs, width)}| {_fmt(latest)}")
    if top_all:
        top_all.sort(key=lambda e: e[0], reverse=True)
        lines.append("")
        lines.append("top-cost sessions (fleet-wide):")
        for fl, url, sess in top_all[:8]:
            lines.append(f"  {sess.get('session')}: {fl:.3g} flops over "
                         f"{sess.get('utterances')} utterance(s) ({url})")
    return "\n".join(lines)


def render_tenants(costs_fan: dict, series: dict[str, list[dict]],
                   width: int = 48) -> str:
    """The tenant panel (ISSUE 18): per-lane occupancy/fairness off the
    ``tenants`` section the cost fan-out carries when a brain's tenancy
    plane is on, plus the ``tenant.token_share.*`` gauge sparklines from
    the same timeseries ring every panel reads — the operator's answer to
    "who is holding the slots, and is the fair share actually fair"."""
    reps = costs_fan.get("replicas") or {}
    lines = ["tenants (QoS lanes):"]
    for url in sorted(reps):
        body = reps.get(url) if isinstance(reps.get(url), dict) else {}
        lanes = (body.get("tenants") or {}).get("lanes") or {}
        if not lanes:
            continue
        lines.append(f"{url}")
        for name, ln in sorted(lanes.items()):
            p50 = ln.get("p50_ms")
            lines.append(
                f"  {name.ljust(12)} w={ln.get('weight')} active "
                f"{ln.get('active')} queued {ln.get('queued')} tokens "
                f"{ln.get('tokens')} throttled {ln.get('throttled')} "
                f"preempt {ln.get('preemptions')}"
                + (f" p50 {p50:.0f}ms" if p50 is not None else ""))
        samples = series.get(url) or []
        shares = sorted({k for s in samples for k in (s.get("gauges") or {})
                         if k.startswith("tenant.token_share.")})
        for k in shares:
            xs = [s.get("gauges", {}).get(k) for s in samples]
            latest = next((x for x in reversed(xs) if x is not None), None)
            lines.append(f"  {k.removeprefix('tenant.').ljust(24)}"
                         f"|{sparkline(xs, width)}| {_fmt(latest)}")
    return "\n".join(lines) if len(lines) > 1 else ""


def render_evidence(evidence: dict) -> str:
    """The peer-comparison evidence a gray freeze carries: who was
    demoted, on which signal, how far from the fleet — the dump answers
    the "was the demotion right?" question without a re-run."""
    lines = [
        f"gray evidence: {evidence.get('replica')} demoted on "
        f"{evidence.get('signal')} = {_fmt(evidence.get('value'))} "
        f"(fleet median {_fmt(evidence.get('fleet_median'))}, "
        f"MAD {_fmt(evidence.get('mad'))}, score "
        f"{_fmt(evidence.get('score'))} >= {_fmt(evidence.get('threshold'))} "
        f"for {evidence.get('windows')} windows)",
        "peer signals at detection:",
    ]
    victim = evidence.get("replica")
    for url, sig in sorted((evidence.get("peers") or {}).items()):
        mark = " <-- GRAY" if url == victim else ""
        pretty = " ".join(f"{k}={_fmt(v)}" for k, v in sorted(sig.items()))
        lines.append(f"  {url}: {pretty}{mark}")
    return "\n".join(lines)


def render_file(body: dict, width: int = 48) -> str:
    """Render a saved body by shape: flight dump (fleet evidence +
    snapshot timeline), ``/debug/replicas/timeseries`` fan-out, or a
    single service's ``/debug/timeseries``."""
    # frozen flight dump (possibly with the fleet gray evidence)
    if "frozen" in body:
        lines = []
        if body.get("frozen"):
            lines.append(f"flight dump: frozen by {body.get('reason')}"
                         + (f" ({body['detail']})" if body.get("detail")
                            else ""))
        else:
            lines.append("flight dump: not frozen")
        evidence = (body.get("extra") or {}).get("fleet")
        if evidence:
            lines.append(render_evidence(evidence))
        snaps = body.get("metric_snapshots") or []
        if snaps:
            keys = sorted({k for s in snaps for k in (s.get("gauges") or {})
                           if k.startswith(("fleet.", "router.", "ts.",
                                            "autopilot."))})
            lines.append(f"{len(snaps)} metric snapshots; fleet gauges:")
            for k in keys:
                xs = [s.get("gauges", {}).get(k) for s in snaps]
                latest = next((x for x in reversed(xs) if x is not None), None)
                lines.append(f"  {k.ljust(26)}|{sparkline(xs, width)}| "
                             f"{_fmt(latest)}")
        return "\n".join(lines)
    # a saved /admin/autopilot body (the controller's describe())
    if "decisions" in body and "brain" in body:
        return render_autopilot(body)
    # router fan-out: {"replicas": {url: timeseries body}} — or the cost
    # fan-out (ISSUE 17), whose per-replica bodies carry meter totals
    # instead of ring samples
    if isinstance(body.get("replicas"), dict):
        vals = [b for b in body["replicas"].values() if isinstance(b, dict)]
        if any("totals" in b or "enabled" in b for b in vals):
            return render_costs(body, {}, width=width)
        series = {url: (b.get("samples") or [])
                  for url, b in body["replicas"].items()
                  if isinstance(b, dict)}
        return render_fleet({"replicas": {"total": len(series)}}, series,
                            width=width)
    # one service's /debug/costs body
    if "enabled" in body and ("totals" in body or "service" in body) \
            and "samples" not in body:
        svc = body.get("service", "service")
        return render_costs({"replicas": {svc: body}}, {}, width=width)
    # one service's own ring
    if "samples" in body:
        url = body.get("service", "service")
        return render_fleet({"replicas": {"total": 1}},
                            {url: body.get("samples") or []}, width=width)
    return "(unrecognized file shape — expected a flight dump or a "\
        "/debug/timeseries body)"


def one_frame(router_url: str, width: int) -> tuple[dict, dict, dict, dict]:
    health = fetch_json(router_url.rstrip("/") + "/health")
    fan = fetch_json(router_url.rstrip("/") + "/debug/replicas/timeseries")
    series = {url: (b.get("samples") or [])
              for url, b in (fan.get("replicas") or {}).items()
              if isinstance(b, dict)}
    # 404s (no autopilot attached) come back as {} (quiet — absence is a
    # legitimate deployment, not an error worth a line per frame)
    autopilot = fetch_json(router_url.rstrip("/") + "/admin/autopilot",
                           quiet=True)
    # the cost fan-out (ISSUE 17) — quiet for the same reason: replicas
    # predating the observatory simply have no panel
    costs = fetch_json(router_url.rstrip("/") + "/debug/replicas/costs",
                       quiet=True)
    return health, series, autopilot, costs


# -------------------------------------------------------------- self-test


def _synthetic_samples(n: int, parse_ms: float, jitter: float = 0.0) -> list[dict]:
    return [{"seq": i, "t_s": 1000.0 + i, "dt_s": 1.0,
             "gauges": {"slo.brain.p99_ms": parse_ms * 2,
                        "paged.kv_utilization": 0.4},
             "rates": {"scheduler.slots_quarantined": 0.0},
             "hist": {"brain.parse": {"ms_per": parse_ms + (i % 3) * jitter,
                                      "per_s": 2.0}}}
            for i in range(n)]


def self_test() -> int:
    # sparkline scaling + gap rendering
    assert sparkline([1.0, 2.0, 3.0], 8) == "▁▄█"
    assert "·" in sparkline([1.0, None, 3.0], 8)
    assert sparkline([], 8) == "·"
    # signal extraction from a synthetic ring sample
    rows = signal_rows(_synthetic_samples(4, 10.0, jitter=1.0))
    assert rows["parse_ms"][0] == 10.0 and rows["parse_p99_ms"][0] == 20.0
    assert "kv_utilization" in rows
    # a fleet frame: healthy + gray + down replicas, sparklines per signal
    health = {
        "replicas": {"total": 3, "healthy": 3, "gray": 1, "draining": 0},
        "replica_detail": [
            {"url": "http://r0", "state": "up", "gray": False,
             "pressure": 0.2, "clock_skew_s": 0.001},
            {"url": "http://r1", "state": "up", "gray": True,
             "outlier_score": 9.3, "outlier_signal": "parse_ms",
             "role": "prefill",
             "pressure": 0.3, "clock_skew_s": -0.002},
            {"url": "http://r2", "state": "down", "gray": False,
             "pressure": 0.0, "clock_skew_s": 0.0},
        ],
        "fleet": {"aggregates": {"parse_ms": {
            "median": 10.0, "mad": 0.5, "min": 9.5, "max": 250.0, "n": 3}}},
        "disagg": {"enabled": True, "min_tokens": 256, "stream_blocks": 4,
                   "streamed_blocks_per_s": 12.5,
                   "prefill": {"total": 1, "admitting": 1, "queue_depth": 2,
                               "urls": ["http://r1"]},
                   "decode": {"total": 2, "admitting": 2}},
    }
    series = {"http://r0": _synthetic_samples(12, 10.0, 1.0),
              "http://r1": _synthetic_samples(12, 250.0, 5.0),
              "http://r2": []}
    txt = render_fleet(health, series)
    assert "GRAY" in txt and "score 9.3" in txt and "parse_ms" in txt
    assert "DOWN/EJECTED" in txt and "no timeseries samples" in txt
    assert "fleet aggregates" in txt and "█" in txt
    # the disagg roll-up (ISSUE 20): per-pool line + per-replica role tag
    assert "disagg: prefill 1/1 admitting (queue 2)" in txt
    assert "decode 2/2 admitting" in txt and "KV blocks/s" in txt
    assert "role=prefill" in txt
    # file mode: a frozen gray flight dump with evidence
    dump = {"frozen": True, "reason": "fleet.gray", "detail": "http://r1",
            "extra": {"fleet": {
                "replica": "http://r1", "signal": "parse_ms", "value": 250.0,
                "fleet_median": 10.0, "mad": 0.5, "score": 48.0,
                "threshold": 4.0, "windows": 3,
                "peers": {"http://r0": {"parse_ms": 10.0},
                          "http://r1": {"parse_ms": 250.0}}}},
            "metric_snapshots": [
                {"t_s": 1.0, "gauges": {"fleet.gray_replicas": 0.0}},
                {"t_s": 2.0, "gauges": {"fleet.gray_replicas": 1.0}}]}
    ftxt = render_file(dump)
    assert "fleet.gray" in ftxt and "demoted on parse_ms" in ftxt
    assert "<-- GRAY" in ftxt and "fleet.gray_replicas" in ftxt
    # file mode: a saved fan-out body
    fan = {"service": "router",
           "replicas": {"http://r0": {"samples": series["http://r0"]}}}
    assert "http://r0" in render_file(fan)
    assert "unrecognized" in render_file({"bogus": 1})
    # the autopilot panel (ISSUE 16): live describe() body + dump gauges
    desc = {"enabled": True,
            "brain": {"target": 3, "actual": 2, "joining": 1, "draining": 0,
                      "retiring": ["http://r9"], "min": 1, "max": 4,
                      "load": 1.61, "forecast": 2.05, "up_streak": 1,
                      "down_streak": 0, "cooldown_remaining_s": 0.4},
            "prefill": {"target": 2, "actual": 1, "servable": 1,
                        "queue_depth": 3, "up_streak": 2, "down_streak": 0,
                        "cooldown_remaining_s": 1.5},
            "stt": {"target": 2, "actual": 2, "healthy": 2, "min": 1,
                    "max": 4, "up_streak": 0, "down_streak": 0,
                    "cooldown_remaining_s": 0.0},
            "decisions": [
                {"t": 1.0, "tier": "brain", "action": "scale_up",
                 "reason": "forecast", "signal": 1.5, "forecast": 2.0,
                 "target": 3, "actual": 2, "cooldown_remaining_s": 0.0},
                {"t": 2.0, "tier": "brain", "action": "join",
                 "reason": "prewarmed", "signal": None, "forecast": None,
                 "target": 3, "actual": 3, "cooldown_remaining_s": 0.4,
                 "replica": "http://r3", "adopted_tokens": 57},
            ]}
    atxt = render_autopilot(desc)
    assert "target 3 / actual 2" in atxt and "scale_up/forecast" in atxt
    assert "join/prewarmed" in atxt and "adopted=57" in atxt
    assert "autopilot[stt]" in atxt and "retiring: http://r9" in atxt
    assert "autopilot[prefill]: target 2 / actual 1" in atxt
    assert "queue 3" in atxt
    assert render_autopilot({"enabled": False}) == "autopilot: not attached"
    assert "join/prewarmed" in render_file(desc)  # saved describe() body
    apdump = {"frozen": True, "reason": "slo.p99", "detail": None,
              "metric_snapshots": [
                  {"t_s": 1.0, "gauges": {"autopilot.target_replicas": 2.0,
                                          "autopilot.load": 0.8}},
                  {"t_s": 2.0, "gauges": {"autopilot.target_replicas": 3.0,
                                          "autopilot.load": 1.9}}]}
    aptxt = render_file(apdump)
    assert "autopilot.target_replicas" in aptxt and "autopilot.load" in aptxt
    # the efficiency panel (ISSUE 17): cost fan-out + MFU gauge sparklines
    cost_body = {
        "service": "brain", "enabled": True,
        "totals": {"prefill_flops": 8e9, "prefill_cached_flops": 2e9,
                   "decode_flops": 30e9, "decode_bytes": 5e9,
                   "kv_block_us": 4_000_000},
        "engine": {"weights_stream_bytes": 9e9, "fwds": 900, "chunks": 60},
        "mfu": 0.31, "mbu": 0.62, "mfu_prefill": 0.4,
        "top_sessions": [{"session": "s-big", "prefill_flops": 6e9,
                          "decode_flops": 20e9, "utterances": 7}]}
    cost_fan = {"service": "router",
                "replicas": {"http://r0": cost_body,
                             "http://r1": {"enabled": False}}}
    mfu_series = {"http://r0": [
        {"gauges": {"engine.mfu": 0.1 + 0.05 * i, "engine.mbu": 0.5}}
        for i in range(8)]}
    ctxt = render_costs(cost_fan, mfu_series)
    assert "mfu 0.31" in ctxt and "engine.mfu" in ctxt and "█" in ctxt
    assert "decode 75%" in ctxt and "cache hit 20%" in ctxt
    assert "s-big" in ctxt and "7 utterance(s)" in ctxt
    assert "[cost lanes off]" in ctxt
    # file-mode shape detection: fan-out vs one service's own body
    assert "s-big" in render_file(cost_fan)
    assert "mfu 0.31" in render_file(cost_body)
    # the tenant panel (ISSUE 18): lanes off the cost fan-out + share rings
    cost_body["tenants"] = {"lanes": {
        "premium": {"weight": 3.0, "vtime": 120.0, "active": 2, "queued": 1,
                    "tokens": 900, "throttled": 0, "preemptions": 0,
                    "p50_ms": 80.0},
        "free": {"weight": 1.0, "vtime": 350.0, "active": 1, "queued": 4,
                 "tokens": 350, "throttled": 12, "preemptions": 2,
                 "p50_ms": None}}, "ledgers": {}}
    share_series = {"http://r0": [
        {"gauges": {"tenant.token_share.premium": 0.6 + 0.02 * i}}
        for i in range(8)]}
    ttxt = render_tenants(cost_fan, share_series)
    assert "premium" in ttxt and "throttled 12" in ttxt and "preempt 2" in ttxt
    assert "token_share.premium" in ttxt and "█" in ttxt
    assert render_tenants({"replicas": {"http://r1": {"enabled": False}}},
                          {}) == ""
    print(txt)
    print("fleetview self-test ok")
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--router", default=DEFAULT_ROUTER)
    ap.add_argument("--watch", type=float, default=0.0,
                    help="refresh every SECS (0 = one frame)")
    ap.add_argument("--width", type=int, default=48)
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--file", metavar="SAVED",
                    help="render a saved dump/timeseries body instead of polling")
    ap.add_argument("--self-test", action="store_true")
    args = ap.parse_args(argv)

    if args.self_test:
        return self_test()
    if args.file:
        try:
            with open(args.file) as f:
                body = json.load(f)
        except (OSError, ValueError) as e:
            print(f"[fleetview] cannot read {args.file}: {e}", file=sys.stderr)
            return 2
        if args.json:
            print(json.dumps(body, indent=1))
        else:
            print(render_file(body, width=args.width))
        return 0
    while True:
        health, series, autopilot, costs = one_frame(args.router, args.width)
        if not health and not series:
            return 2
        if args.json:
            print(json.dumps({"health": health, "series": series,
                              "autopilot": autopilot, "costs": costs},
                             indent=1))
        else:
            if args.watch:
                print("\x1b[2J\x1b[H", end="")  # clear between frames
            print(render_fleet(health, series, width=args.width))
            if autopilot.get("enabled"):
                print()
                print(render_autopilot(autopilot))
            if any(isinstance(b, dict) and b.get("enabled")
                   for b in (costs.get("replicas") or {}).values()):
                print()
                print(render_costs(costs, series, width=args.width))
            tpanel = render_tenants(costs, series, width=args.width)
            if tpanel:
                print()
                print(tpanel)
        if not args.watch:
            return 0
        time.sleep(args.watch)


if __name__ == "__main__":
    sys.exit(main())
