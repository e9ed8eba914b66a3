#!/usr/bin/env python
"""Scenario swarm: N concurrent WS sessions against the live voice service,
and the binary search that turns them into a capacity number.

Every bench before this was a microbench — spec decode, batched STT, radix
reuse each proved a multiplier in isolation. This tool answers the question
the ROADMAP's north star actually asks: **how many concurrent voice sessions
does the stack hold at SLO?** It drives N real WebSocket sessions against
live voice→brain→executor services with a mix of scripted scenarios:

- ``single_shot``    one typed command, await the intent
- ``multi_turn``     several commands on one connection (radix-warm when the
                     brain backend is session-keyed)
- ``compound``       multi-intent utterances (the planner-backend shape)
- ``barge_in``       a second command fired before the first one's
                     execution/TTS settles (mid-TTS interruption)
- ``paced_audio``    binary PCM frames at real-time pacing through the real
                     audio ingest path (partials, spec-finals, endpoint)
- ``unpaced_audio``  the same frames as a firehose (no inter-frame sleep)
- ``garbage``        malformed PCM + bad control frames; the session must
                     survive (warn, not die) and still parse afterwards
- ``abort``          disconnect mid-utterance (client gone before ``final``)
                     — exercises the aborted-utterance SLO accounting

Per-utterance latency (send→intent) and the server's ``latency_budget``
stage splits are recorded per scenario; the run's verdict is a **fresh
client-side SLOTracker** over those samples, reusing exactly the
``utils/slo.py`` thresholds (``SLO_TARGET_P50_MS``/``P99``/``ERROR_RATE``…).
``binary_search_capacity`` bisects N and reports
**capacity = max concurrent sessions with SLO ok**.

While a run is live, a sampler thread drains every service's
``/debug/timeseries?since=`` ring (the fleet telemetry plane, ISSUE 14 —
falling back to the legacy JSON ``/metrics?gauges=1`` poll for services
without it) and keeps a timeline of the saturation gauges
(``scheduler.batch_occupancy``, ``paged.kv_utilization``,
``stt.batch_occupancy``, admission inflight fractions, breaker states).
``attribute_saturation`` reads that timeline back: *which resource
saturated first* at the knee — the next bottleneck every future scaling PR
should aim at.

Usage (against a running stack; benches/bench_swarm.py boots one for you):

    python tools/swarm.py [--voice URL] [--n 8] [--utterances 4]
        [--mix single_shot=4,multi_turn=2,paced_audio=1] [--json]
    python tools/swarm.py --search --max-n 64   # the capacity bisect

A mix key may carry a QoS lane: ``single_shot@premium=4,compound@free=2``
runs those sessions with a ``tenant`` control frame dealt right after
connect (ISSUE 18 — pair with ``TENANT_CLASSES`` on the brain stack).
The full ``scenario@tenant`` key labels the verdict rollup, so per-tenant
latency/error splits come out of the standard per-scenario report.

The audio scenarios assume the swarm stack's ``ScriptedSTT`` cadence
(a final every ``--frames-per-final`` frames); against a real-STT stack
prefer the typed scenarios or feed real speech.

Chaos mode: the deterministic fault layer (``tpu_voice_agent.utils.chaos``)
is armed IN the services, not in this client — launch the stack with
``CHAOS_FAULTS="nan_logits:0.05,prefill_exc:0.05,..."`` (and optionally
``CHAOS_SEED``) or pass ``chaos_spec=`` to ``build_local_stack`` for the
in-process harness. ``benches/bench_chaos.py`` runs exactly that drill:
capacity-at-SLO with 5% injected faults vs clean, same swarm, same SLO.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

_ROOT = str(Path(__file__).resolve().parents[1])
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

COMMANDS = [
    "search for usb hubs", "scroll down", "go back", "take a screenshot",
    "sort by price", "search for mechanical keyboards",
]
COMPOUND_COMMANDS = [
    "search for usb hubs and take a screenshot",
    "scroll down and summarize the page",
    "go back and sort by price",
]

# per-scenario quality mining (ISSUE 15): the PRIMARY intent type each
# scripted command is designed to yield (matches the rule parser's
# precedence — e.g. "go back and sort by price" hits the sort branch).
# Typed scenarios score their intent events against this; a swarm run's
# verdict then carries per-scenario type_match/degraded fractions beside
# latency, so a capacity probe also says whether answers stayed RIGHT.
EXPECTED_PRIMARY = {
    "search for usb hubs": "search",
    "scroll down": "scroll",
    "go back": "back",
    "take a screenshot": "screenshot",
    "sort by price": "sort",
    "search for mechanical keyboards": "search",
    "search for usb hubs and take a screenshot": "search",
    "scroll down and summarize the page": "scroll",
    "go back and sort by price": "sort",
}

DEFAULT_URLS = {
    "voice": "http://127.0.0.1:7072",
    "brain": "http://127.0.0.1:8090",
    "executor": "http://127.0.0.1:7081",
}

# scenario mix weights (sessions are dealt round-robin proportional to
# weight). abort stays a small share on purpose: every abort burns SLO
# error budget server-side (that is the point of the accounting), and a
# mix dominated by deliberate churn would measure the mix, not the stack.
DEFAULT_MIX = {
    "single_shot": 5, "multi_turn": 3, "compound": 2, "barge_in": 2,
    "paced_audio": 2, "unpaced_audio": 1, "garbage": 1, "abort": 1,
}

FRAME_SAMPLES = 1600  # 100 ms of 16 kHz PCM16 silence per binary frame
SILENCE_FRAME = b"\x00\x00" * FRAME_SAMPLES


class ScriptedSTT:
    """Server-side STT stand-in for swarm stacks: no endpointer, no model.
    Emits a partial mid-utterance, a ``spec_final`` one frame before the
    endpoint (exercising the speculative-parse path), and a ``final`` every
    ``frames_per_final`` frames, cycling the command list — so the swarm's
    audio scenarios traverse the REAL binary-ingest path (arming,
    audio_ingest spans, abort accounting) with deterministic transcripts."""

    def __init__(self, commands=None, frames_per_final: int = 4):
        self.commands = list(commands or COMMANDS)
        self.frames_per_final = max(2, frames_per_final)
        self.frames = 0
        self.idx = 0

    def reset(self) -> None:
        self.frames = 0

    def _cmd(self) -> str:
        return self.commands[self.idx % len(self.commands)]

    def feed(self, samples) -> list[tuple[str, str]]:
        self.frames += 1
        k = self.frames % self.frames_per_final
        if k == 0:
            cmd = self._cmd()
            self.idx += 1
            return [("final", cmd)]
        if k == self.frames_per_final - 1:
            return [("spec_final", self._cmd())]
        if k == 1:
            return [("partial", self._cmd().split()[0])]
        return []


# --------------------------------------------------------------- sampling


# resource -> saturation fraction, from a merged runtime-gauge dict.
# Fractions are comparable across resources: 1.0 means "this resource can
# absorb nothing more" (full batch, full pool, admission cap, open breaker).
def _frac(g: dict, used: str, total: str):
    t = g.get(total)
    return (g.get(used, 0.0) / t) if t else None


RESOURCE_FRACTIONS = {
    "scheduler.batch_occupancy": lambda g: g.get("scheduler.batch_occupancy"),
    "paged.kv_utilization": lambda g: g.get("paged.kv_utilization"),
    "stt.batch_occupancy": lambda g: g.get("stt.batch_occupancy"),
    "brain.admission": lambda g: _frac(g, "resilience.brain.inflight",
                                       "resilience.brain.max_inflight"),
    "executor.admission": lambda g: _frac(g, "resilience.executor.inflight",
                                          "resilience.executor.max_inflight"),
    # breaker_state: 0 closed / 1 half-open / 2 open -> 0 / 0.5 / 1.0
    "brain.breaker": lambda g: (g["resilience.brain.breaker_state"] / 2.0
                                if "resilience.brain.breaker_state" in g else None),
    "executor.breaker": lambda g: (g["resilience.executor.breaker_state"] / 2.0
                                   if "resilience.executor.breaker_state" in g else None),
}
SATURATED_AT = 0.95  # a fraction at/above this counts as "saturated"


def fetch_metrics_json(url: str, timeout_s: float = 5.0,
                       gauges_only: bool = False) -> dict:
    """One service's JSON /metrics. ``gauges_only`` uses the cheap
    ``?gauges=1`` mode (dict copies, no percentile sorting server-side) —
    the fallback path when a service predates /debug/timeseries."""
    q = "?gauges=1" if gauges_only else ""
    try:
        with urllib.request.urlopen(url.rstrip("/") + "/metrics" + q,
                                    timeout=timeout_s) as r:
            return json.loads(r.read().decode())
    except Exception:
        return {}


def fetch_timeseries(url: str, since: int,
                     timeout_s: float = 2.0) -> dict | str | None:
    """One service's ``/debug/timeseries?since=`` delta body. Returns the
    body dict, the string ``"missing"`` for a definitive 404 (the service
    predates the endpoint — the caller may latch its legacy fallback), or
    None for a transient failure (timeout, reset — retry next poll; a
    loaded service mid-saturation-run must NOT get demoted to the
    instantaneous-gauge path exactly when history matters most)."""
    try:
        with urllib.request.urlopen(
                url.rstrip("/") + f"/debug/timeseries?since={since}",
                timeout=timeout_s) as r:
            body = json.loads(r.read().decode())
        return body if isinstance(body, dict) and "samples" in body else None
    except urllib.error.HTTPError as e:
        return "missing" if e.code == 404 else None
    except Exception:
        return None


class MetricsSampler:
    """Background thread keeping a gauge timeline while a swarm run is
    live, so saturation attribution can say which resource crossed the
    line FIRST.

    Since ISSUE 14 the sampler reads each service's ``/debug/timeseries
    ?since=`` delta (the services sample THEMSELVES on the `TS_INTERVAL_S`
    cadence; this thread just drains the rings) — the same surface the
    router's fleet gray-failure detector scrapes, so the bench-side
    attribution and the production detector can never disagree about what
    the data was. Services without the endpoint fall back to the legacy
    ``/metrics?gauges=1`` dict-copy poll. The timeline schema is
    unchanged: one ``{"t_s", "gauges"}`` entry per poll, gauges max-merged
    across services."""

    def __init__(self, urls: list[str], interval_s: float = 0.3):
        self.urls = list(urls)
        self.interval_s = interval_s
        self.samples: list[dict] = []
        self._since: dict[str, int] = {}
        # only ring samples stamped at/after this moment count: the rings
        # outlive runs, and a PRIOR probe's saturated gauges merged into
        # this run's first timeline entry would corrupt the first-crossed
        # attribution (refreshed in __enter__, when the run truly starts)
        self._t0 = time.time()
        self._legacy: set[str] = set()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def _poll_once(self) -> None:
        merged: dict = {}
        for u in self.urls:
            if u not in self._legacy:
                body = fetch_timeseries(u, self._since.get(u, 0))
                if isinstance(body, dict):
                    nxt = body.get("next_seq")
                    if isinstance(nxt, int):
                        self._since[u] = nxt
                    else:
                        self._since.setdefault(u, 0)
                    for s in body.get("samples") or []:
                        # the first fetch drains the ring's backlog, which
                        # may hold a PRIOR run's saturated history — only
                        # samples taken during THIS run belong on its
                        # timeline (later fetches the cursor makes this a
                        # no-op)
                        if s.get("t_s", 0.0) < self._t0:
                            continue
                        for k, v in (s.get("gauges") or {}).items():
                            if isinstance(v, (int, float)):
                                merged[k] = max(merged.get(k, float("-inf")),
                                                float(v))
                    continue
                if body == "missing":
                    self._legacy.add(u)  # definitively absent: fall back
                else:
                    continue  # transient failure: retry next poll
            body = fetch_metrics_json(u, timeout_s=2.0, gauges_only=True)
            for k, v in (body.get("runtime", {}).get("gauges") or {}).items():
                if isinstance(v, (int, float)):
                    merged[k] = max(merged.get(k, float("-inf")), float(v))
        if merged:
            self.samples.append({"t_s": time.time(), "gauges": merged})

    def _run(self) -> None:
        while not self._stop.is_set():
            self._poll_once()
            self._stop.wait(self.interval_s)
        self._poll_once()  # one last sample after the load stops
        if not self.samples:
            # a sub-TS_INTERVAL_S run can start and finish entirely
            # between two ring ticks; one live instantaneous snapshot
            # keeps the attribution timeline non-empty for tiny probes
            merged: dict = {}
            for u in self.urls:
                body = fetch_metrics_json(u, timeout_s=2.0, gauges_only=True)
                for k, v in (body.get("runtime", {}).get("gauges") or {}).items():
                    if isinstance(v, (int, float)):
                        merged[k] = max(merged.get(k, float("-inf")), float(v))
            if merged:
                self.samples.append({"t_s": time.time(), "gauges": merged})

    def __enter__(self) -> "MetricsSampler":
        self._t0 = time.time()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="swarm-sampler")
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)


def attribute_saturation(samples: list[dict]) -> dict:
    """Read the gauge timeline back into a verdict: the first resource to
    cross SATURATED_AT (time-ordered; ties broken by higher fraction), the
    peak fraction per resource, and — when nothing crossed — the nearest
    bottleneck (highest peak) so a sub-knee run still names its pressure
    point."""
    peaks: dict[str, float] = {}
    first_cross: dict[str, float] = {}
    for s in samples:
        g = s["gauges"]
        for name, fn in RESOURCE_FRACTIONS.items():
            v = fn(g)
            if v is None:
                continue
            peaks[name] = max(peaks.get(name, 0.0), v)
            if v >= SATURATED_AT and name not in first_cross:
                first_cross[name] = s["t_s"]
    verdict: dict = {
        "samples": len(samples),
        "peak_fractions": {k: round(v, 4) for k, v in sorted(peaks.items())},
        "saturated": sorted(first_cross),
    }
    if first_cross:
        verdict["first_saturated"] = min(
            first_cross, key=lambda k: (first_cross[k], -peaks[k]))
    elif peaks:
        verdict["first_saturated"] = None
        verdict["nearest_bottleneck"] = max(peaks, key=peaks.get)
    else:
        verdict["first_saturated"] = None
    return verdict


# --------------------------------------------------------------- scenarios


class Utt:
    """One utterance's client-side record."""

    __slots__ = ("scenario", "lat_ms", "ok", "stages", "expected", "itype",
                 "degraded")

    def __init__(self, scenario: str, lat_ms: float, ok: bool,
                 stages: dict | None, expected: str | None = None,
                 itype: str | None = None, degraded: bool = False):
        self.scenario = scenario
        self.lat_ms = lat_ms
        self.ok = ok
        self.stages = stages or {}
        # quality mining (typed scenarios): the command's designed primary
        # intent type vs what the intent event actually carried, plus the
        # degraded tag riding the event
        self.expected = expected
        self.itype = itype
        self.degraded = degraded


class EventLog:
    """Accumulated WS events for one connection, with arrival times —
    intent arrivals give the latency clock, latency_budget events give the
    server-side stage splits."""

    def __init__(self):
        self.events: list[dict] = []
        self.arrived: list[float] = []

    def count(self, type_: str) -> int:
        return sum(1 for e in self.events if e["type"] == type_)

    def terminals(self) -> int:
        """Utterances answered, one way or the other: an ``intent`` is the
        happy path, a terminal ``error`` is how the voice service ends an
        utterance whose parse failed server-side — waiting on intents alone
        would stall a probe for the full timeout on every overload-induced
        failure (exactly when capacity probes care most)."""
        return sum(1 for e in self.events if e["type"] in ("intent", "error"))

    async def wait(self, ws, done, timeout_s: float) -> bool:
        """Read events until ``done()`` (over this log) or timeout; True on
        done. Non-TEXT frames (close/error) end the wait."""
        import aiohttp

        end = time.monotonic() + timeout_s
        while not done(self):
            left = end - time.monotonic()
            if left <= 0:
                return False
            try:
                msg = await ws.receive(timeout=left)
            except asyncio.TimeoutError:
                return False
            if msg.type != aiohttp.WSMsgType.TEXT:
                return False
            self.events.append(json.loads(msg.data))
            self.arrived.append(time.monotonic())
        return True

    def mine(self, scenario: str, t0s: list[float],
             texts: list[str] | None = None) -> list[Utt]:
        """Pair the i-th terminal event (intent OR error) with the i-th
        utterance start; stage splits ride the latency_budget events (same
        order — the error path emits one too). ``texts`` (typed scenarios)
        additionally mines per-utterance quality: the intent event's first
        type vs the command's designed primary type, plus the degraded tag."""
        terms = [(i, e) for i, e in enumerate(self.events)
                 if e["type"] in ("intent", "error")]
        budgets = [e for e in self.events if e["type"] == "latency_budget"]
        utts: list[Utt] = []
        for i, t0 in enumerate(t0s):
            expected = (EXPECTED_PRIMARY.get(texts[i])
                        if texts is not None and i < len(texts) else None)
            if i < len(terms):
                idx, ev = terms[i]
                # clamped at 0: keepalive frames can realign a scripted
                # endpoint so a final lands just before its nominal t0
                lat = max(0.0, (self.arrived[idx] - t0) * 1e3)
                stages = budgets[i]["stages"] if i < len(budgets) else {}
                ok = ev["type"] == "intent" and not bool(stages.get("error"))
                itype = None
                if ev["type"] == "intent":
                    intents = (ev.get("data") or {}).get("intents") or []
                    if intents:
                        itype = intents[0].get("type")
                utts.append(Utt(scenario, lat, ok, stages, expected=expected,
                                itype=itype, degraded=bool(ev.get("degraded"))))
            else:
                # never answered inside the timeout: an error sample at the
                # full wait — unanswered utterances must cost SLO budget
                utts.append(Utt(scenario, (time.monotonic() - t0) * 1e3,
                                False, None, expected=expected))
        return utts


async def _typed_round(ws, scenario: str, texts: list[str], think_s: float,
                       timeout_s: float, overlap: bool = False) -> list[Utt]:
    """Send typed commands; sequential await per command unless ``overlap``
    (barge-in: all sends first, then one combined wait)."""
    log = EventLog()
    t0s: list[float] = []
    if overlap:
        for text in texts:
            t0s.append(time.monotonic())
            await ws.send_json({"type": "text", "text": text})
        await log.wait(ws, lambda lg: lg.terminals() >= len(texts)
                       and lg.count("latency_budget") >= len(texts), timeout_s)
    else:
        for text in texts:
            t0s.append(time.monotonic())
            await ws.send_json({"type": "text", "text": text})
            want = len(t0s)
            await log.wait(ws, lambda lg, w=want: lg.terminals() >= w
                           and lg.count("latency_budget") >= w, timeout_s)
            if think_s:
                await asyncio.sleep(think_s)
    return log.mine(scenario, t0s, texts=texts)


async def _audio_round(ws, scenario: str, n_utts: int, frames_per_final: int,
                       frame_s: float, think_s: float, timeout_s: float) -> list[Utt]:
    """Feed silence frames until the stack's ScriptedSTT endpoints; paced
    (frame_s > 0) sleeps between frames like a live mic, unpaced firehoses.

    Like a live mic, the client KEEPS streaming if the endpoint doesn't
    fire: after a generous quiet window it feeds another silence frame.
    Without this, a single lost frame (network, or the chaos drill's
    ``drop_frame``) would wedge the frame-counted ScriptedSTT one short of
    its final forever — a harness artifact; in the real pipeline frame
    loss costs one frame of latency, and that is what capacity probes
    should measure."""
    log = EventLog()
    t0s: list[float] = []
    for _ in range(n_utts):
        for f in range(frames_per_final):
            await ws.send_bytes(SILENCE_FRAME)
            if frame_s and f < frames_per_final - 1:
                await asyncio.sleep(frame_s)
        # latency clock starts at the endpoint-triggering frame
        t0s.append(time.monotonic())
        want = len(t0s)
        done = (lambda lg, w=want: lg.terminals() >= w
                and lg.count("latency_budget") >= w)
        end = time.monotonic() + timeout_s
        while True:
            left = end - time.monotonic()
            if left <= 0 or await log.wait(ws, done, min(5.0, max(left, 0.1))):
                break
            await ws.send_bytes(SILENCE_FRAME)  # the mic never stops
        if think_s:
            await asyncio.sleep(think_s)
    return log.mine(scenario, t0s)


async def run_session(client, voice_url: str, scenario: str, cfg: dict) -> dict:
    """One WS connection running one scenario; returns its utterance
    records plus session-level counters."""
    n = cfg["utterances"]
    think = cfg["think_s"]
    timeout = cfg["timeout_s"]
    fpf = cfg["frames_per_final"]
    utts: list[Utt] = []
    warns = 0
    aborted = 0
    # tenant-tagged deal (ISSUE 18): a ``scenario@tenant`` mix key runs the
    # base scenario inside that QoS lane. The full key stays the Utt label,
    # so every per-scenario rollup splits per (scenario, tenant) for free.
    label = scenario
    scenario, _, tenant = scenario.partition("@")
    ws_url = voice_url.replace("http", "ws", 1) + "/stream"
    async with client.ws_connect(ws_url, max_msg_size=8 * 1024 * 1024) as ws:
        if tenant:
            await ws.send_json({"type": "tenant", "tenant": tenant})
        if scenario == "single_shot":
            for i in range(n):
                utts += await _typed_round(ws, label, [COMMANDS[i % len(COMMANDS)]],
                                           think, timeout)
        elif scenario == "multi_turn":
            # one conversation, n turns on the same convo_id (the connection)
            utts += await _typed_round(
                ws, label, [COMMANDS[i % len(COMMANDS)] for i in range(n)],
                think, timeout)
        elif scenario == "compound":
            utts += await _typed_round(
                ws, label,
                [COMPOUND_COMMANDS[i % len(COMPOUND_COMMANDS)] for i in range(n)],
                think, timeout)
        elif scenario == "barge_in":
            # fire pairs back-to-back: the second command lands while the
            # first one's execution/TTS is still in flight
            for i in range(0, n, 2):
                # the last "pair" is a singleton when n is odd — a session
                # must run exactly its configured utterance count
                pair = [COMMANDS[(i + j) % len(COMMANDS)]
                        for j in range(min(2, n - i))]
                utts += await _typed_round(ws, label, pair, think, timeout,
                                           overlap=True)
                if think:
                    await asyncio.sleep(think)
        elif scenario in ("paced_audio", "unpaced_audio"):
            frame_s = cfg["frame_s"] if scenario == "paced_audio" else 0.0
            utts += await _audio_round(ws, label, n, fpf, frame_s, think,
                                       timeout)
        elif scenario == "garbage":
            for i in range(n):
                # truncated PCM (odd byte count) + a bad control frame: the
                # session must warn and keep serving
                await ws.send_bytes(b"\x01")
                await ws.send_str("{not json")
                glog = EventLog()
                await glog.wait(ws, lambda lg: lg.count("warn") >= 2, timeout)
                warns += glog.count("warn")
                utts += await _typed_round(ws, label,
                                           [COMMANDS[i % len(COMMANDS)]],
                                           think, timeout)
        elif scenario == "abort":
            # arm an utterance (binary frames, no endpoint) then vanish:
            # the voice service must score it as an aborted error sample —
            # and so must the CLIENT verdict, or a churn-heavy mix would
            # report capacity the stack only holds when nobody hangs up
            t0 = time.monotonic()
            for _ in range(max(1, fpf - 1)):
                await ws.send_bytes(SILENCE_FRAME)
            await asyncio.sleep(min(0.05, think or 0.05))
            aborted += 1
            utts.append(Utt(label, (time.monotonic() - t0) * 1e3, False, None))
            # close without reading the backlog — a real client crash
        else:
            raise ValueError(f"unknown scenario {scenario!r}")
    return {"scenario": label, "utts": utts, "warns": warns,
            "aborted": aborted}


# --------------------------------------------------------------- the swarm


def _deal_scenarios(n_sessions: int, mix: dict[str, int]) -> list[str]:
    """Deterministic weighted deal with diversity at small N: apportion
    n_sessions across scenarios by largest remainder (every scenario with
    weight > 0 gets at least a look once n >= len(mix)), then interleave
    round-robin so a bisect probe at tiny N still mixes behaviors."""
    mix = {k: int(w) for k, w in mix.items() if int(w) > 0}
    for name in mix:
        # a mix key may carry a QoS lane suffix: ``scenario@tenant``
        if name.split("@", 1)[0] not in SCENARIOS:
            raise ValueError(f"unknown scenario {name!r} in mix")
    if not mix:
        raise ValueError("empty scenario mix")
    # every weighted scenario gets one guaranteed slot once n covers the
    # mix (plain largest-remainder dealt abort 0 sessions at n=8-10, so
    # the --quick gate never exercised the abort accounting); below that,
    # heavier scenarios win
    floor = 1 if n_sessions >= len(mix) else 0
    counts = {k: floor for k in mix}
    rest = n_sessions - sum(counts.values())
    total_w = sum(mix.values())
    shares = {k: rest * w / total_w for k, w in mix.items()}
    for k in mix:
        counts[k] += int(shares[k])
    # largest remainder tops up to n_sessions (ties: heavier weight first)
    leftovers = sorted(mix, key=lambda k: (shares[k] - int(shares[k]), mix[k]),
                       reverse=True)
    for i in range(n_sessions - sum(counts.values())):
        counts[leftovers[i % len(leftovers)]] += 1
    order = sorted(mix, key=mix.get, reverse=True)
    dealt: list[str] = []
    while len(dealt) < n_sessions:
        for k in order:
            if counts[k] > 0:
                counts[k] -= 1
                dealt.append(k)
    return dealt[:n_sessions]


SCENARIOS = ("single_shot", "multi_turn", "compound", "barge_in",
             "paced_audio", "unpaced_audio", "garbage", "abort")


def _pctl(xs: list[float], q: float) -> float | None:
    if not xs:
        return None
    from tpu_voice_agent.utils.tracing import nearest_rank

    return round(nearest_rank(sorted(xs), q), 3)


async def _run_swarm_async(voice_url: str, scenarios: list[str], cfg: dict) -> list[dict]:
    import aiohttp

    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(connector=conn) as client:
        tasks = [asyncio.create_task(run_session(client, voice_url, sc, cfg))
                 for sc in scenarios]
        out = await asyncio.gather(*tasks, return_exceptions=True)
    results = []
    for sc, r in zip(scenarios, out):
        if isinstance(r, BaseException):
            # a session that died whole counts every planned utterance as
            # an error — a crashed connection must not slim the denominator
            results.append({"scenario": sc, "utts": [
                Utt(sc, cfg["timeout_s"] * 1e3, False, None)
                for _ in range(cfg["utterances"])],
                "warns": 0, "aborted": 0, "crashed": str(r)})
        else:
            results.append(r)
    return results


def run_swarm(voice_url: str, n_sessions: int, *, utterances: int = 4,
              mix: dict[str, int] | None = None, think_s: float = 0.05,
              timeout_s: float = 30.0, frames_per_final: int = 4,
              frame_s: float = 0.02, sample_urls: list[str] | None = None) -> dict:
    """One swarm run at fixed N. Returns the swarm verdict dict: client-side
    SLO evaluation (fresh tracker, utils/slo.py thresholds), per-scenario
    latency + stage splits, and the saturation-gauge attribution."""
    from tpu_voice_agent.utils import SLOTracker

    scenarios = _deal_scenarios(n_sessions, dict(mix or DEFAULT_MIX))
    cfg = {"utterances": utterances, "think_s": think_s, "timeout_s": timeout_s,
           "frames_per_final": frames_per_final, "frame_s": frame_s}
    with MetricsSampler(sample_urls or [voice_url]) as sampler:
        t0 = time.monotonic()
        results = asyncio.run(_run_swarm_async(voice_url, scenarios, cfg))
        wall_s = time.monotonic() - t0

    # the verdict tracker: a big fixed window so nothing ages out mid-eval;
    # every OTHER threshold comes from the environment exactly like the
    # services' own trackers (that is the "same SLO" contract). PASSIVE:
    # the scoring loop must not export slo.swarm.* gauges into the system
    # under test or freeze the shared flight recorder — the dump belongs
    # to the genuine server-side incident, not the client's bookkeeping.
    slo = SLOTracker("swarm", window_s=86_400.0, passive=True)
    per_scenario: dict[str, dict] = {}
    crashed = 0
    total_warns = 0
    total_aborted = 0
    for r in results:
        sc = r["scenario"]
        agg = per_scenario.setdefault(sc, {"sessions": 0, "utts": [], "stages": []})
        agg["sessions"] += 1
        agg["utts"] += r["utts"]
        agg["stages"] += [u.stages for u in r["utts"] if u.stages]
        total_warns += r["warns"]
        total_aborted += r["aborted"]
        crashed += 1 if "crashed" in r else 0
        for u in r["utts"]:
            slo.record(u.lat_ms, ok=u.ok)

    scen_out: dict[str, dict] = {}
    for sc, agg in sorted(per_scenario.items()):
        lats = [u.lat_ms for u in agg["utts"]]
        entry = {
            "sessions": agg["sessions"],
            "utterances": len(agg["utts"]),
            "errors": sum(1 for u in agg["utts"] if not u.ok),
            "lat_p50_ms": _pctl(lats, 0.50),
            "lat_p99_ms": _pctl(lats, 0.99),
        }
        stage_split: dict[str, dict] = {}
        for key in ("stt_finalize_ms", "parse_ms", "execute_ms", "total_ms"):
            xs = [s[key] for s in agg["stages"] if key in s]
            if xs:
                stage_split[key] = {"p50": _pctl(xs, 0.50), "p99": _pctl(xs, 0.99)}
        entry["stages"] = stage_split
        # per-scenario quality mining (ISSUE 15): of the utterances whose
        # command has a designed primary intent type, what fraction came
        # back right — and what fraction of intent events were degraded.
        # A capacity number that silently traded accuracy for latency now
        # shows it in the same verdict.
        scored = [u for u in agg["utts"] if u.expected is not None
                  and u.itype is not None]
        answered = [u for u in agg["utts"] if u.itype is not None]
        if scored or answered:
            entry["quality"] = {
                "scored": len(scored),
                "type_match": (round(sum(u.itype == u.expected
                                         for u in scored) / len(scored), 4)
                               if scored else None),
                "degraded": (round(sum(u.degraded for u in answered)
                                   / len(answered), 4) if answered else None),
            }
        scen_out[sc] = entry

    all_utts = [u for a in per_scenario.values() for u in a["utts"]]
    all_scored = [u for u in all_utts
                  if u.expected is not None and u.itype is not None]
    all_answered = [u for u in all_utts if u.itype is not None]
    return {
        "n_sessions": n_sessions,
        "utterances": sum(len(a["utts"]) for a in per_scenario.values()),
        "wall_s": round(wall_s, 3),
        "sessions_crashed": crashed,
        "client_warns": total_warns,
        "aborted_sessions": total_aborted,
        "slo": slo.evaluate(),
        "scenarios": scen_out,
        # run-level quality roll-up (ISSUE 15): mined from the typed
        # scenarios' intent events against their designed primary types
        "quality": {
            "scored": len(all_scored),
            "type_match": (round(sum(u.itype == u.expected
                                     for u in all_scored) / len(all_scored), 4)
                           if all_scored else None),
            "degraded": (round(sum(u.degraded for u in all_answered)
                               / len(all_answered), 4)
                         if all_answered else None),
        },
        "saturation": attribute_saturation(sampler.samples),
    }


def binary_search_capacity(voice_url: str, *, max_n: int = 32,
                           sample_urls: list[str] | None = None,
                           **run_kw) -> dict:
    """Capacity = max concurrent sessions with client-side SLO ``ok``.
    Protocol: probe max_n first (cheap when the stack holds it — one run);
    on failure bisect [1, max_n). Every probe's verdict is kept; the knee
    (first failing N) carries the saturation attribution that names the
    bottleneck resource."""
    probes: list[dict] = []
    by_n: dict[int, dict] = {}

    def probe(n: int) -> bool:
        r = run_swarm(voice_url, n, sample_urls=sample_urls, **run_kw)
        ok = r["slo"]["state"] == "ok"
        probes.append({"n": n, "state": r["slo"]["state"],
                       "p50_ms": r["slo"]["p50_ms"], "p99_ms": r["slo"]["p99_ms"],
                       "error_rate": r["slo"]["error_rate"]})
        by_n[n] = r
        print(f"[swarm] probe n={n}: slo={r['slo']['state']} "
              f"p50={r['slo']['p50_ms']} p99={r['slo']['p99_ms']} "
              f"err={r['slo']['error_rate']}", file=sys.stderr, flush=True)
        return ok

    if probe(max_n):
        capacity, knee_n = max_n, None
    else:
        lo, hi = 0, max_n  # invariant: lo ok (0 trivially), hi failed
        while hi - lo > 1:
            mid = (lo + hi) // 2
            if probe(mid):
                lo = mid
            else:
                hi = mid
        capacity, knee_n = lo, hi
    return {
        "max_n": max_n,
        "capacity_sessions": capacity,
        "saturated": knee_n is not None,
        "probes": probes,
        "at_capacity": by_n.get(capacity),
        "knee": by_n.get(knee_n) if knee_n is not None else None,
    }


def run_ramp(voice_url: str, stages: list[int], *,
             sample_urls: list[str] | None = None,
             stage_hook=None, **run_kw) -> dict:
    """Sequential swarm stages at varying N — the load SHAPE elastic-
    capacity drills need (ramp up, hold the plateau, ramp down), where the
    capacity bisect only needs a point. Each stage is one full
    ``run_swarm`` at that N; ``stage_hook(i, n, verdict)``, when given,
    runs between stages (the autopilot bench snapshots replica counts
    there). The roll-up verdict is the zero-drop contract's shape: every
    stage's SLO state, total crashed sessions, total utterance errors —
    a scale-down that dropped anything shows up as a non-ok stage or a
    non-zero loss count, never silently."""
    out: list[dict] = []
    for i, n in enumerate(stages):
        r = run_swarm(voice_url, n, sample_urls=sample_urls, **run_kw)
        errors = sum(s["errors"] for s in r["scenarios"].values())
        stage = {"stage": i, "n": n, "slo": r["slo"],
                 "utterances": r["utterances"], "errors": errors,
                 "sessions_crashed": r["sessions_crashed"],
                 "wall_s": r["wall_s"], "quality": r.get("quality")}
        out.append(stage)
        print(f"[ramp] stage {i} n={n}: slo={r['slo']['state']} "
              f"p99={r['slo']['p99_ms']} errors={errors} "
              f"crashed={r['sessions_crashed']}", file=sys.stderr, flush=True)
        if stage_hook is not None:
            stage_hook(i, n, stage)
    return {
        "stages": out,
        "all_slo_ok": all(s["slo"]["state"] == "ok" for s in out),
        "total_errors": sum(s["errors"] for s in out),
        "total_crashed": sum(s["sessions_crashed"] for s in out),
    }


# --------------------------------------------------------------- local stack


def build_local_stack(tmp_dir: str, *, brain_inflight: int = 8,
                      exec_inflight: int = 8, frames_per_final: int = 4,
                      parser=None, chaos_spec: str | None = None,
                      chaos_seed: int = 0, parse_timeout_s: float = 10.0,
                      brain_replicas: int = 1, router_kw: dict | None = None,
                      prefill_replicas: int = 0):
    """voice + brain + executor on real sockets, wired for swarm runs:
    rule-based brain (or the given parser), fake-page executor, ScriptedSTT
    audio path. ``chaos_spec`` arms the in-process deterministic fault
    layer (tpu_voice_agent.utils.chaos — NaN logits, prefill exceptions,
    alloc failures, stalled steps, dropped WS frames, replica kill/hang/
    slow) so the SAME swarm that measures clean capacity drills the
    fault-containment claims; None leaves chaos at its env-derived
    default (off).

    ``brain_replicas > 1`` boots N brain replicas behind the session-affine
    router (tpu_voice_agent.services.router, ISSUE 10) and points voice at
    the router — the replicated tier bench_router drills. ``parser`` may
    then be a zero-arg FACTORY (each replica needs its own instance) or
    None for per-replica rule parsers; ``router_kw`` passes through to
    ``BrainRouter``. The urls dict gains ``router`` and ``replicas`` keys.

    ``prefill_replicas > 0`` (ISSUE 20) boots that many EXTRA brains as a
    disaggregated prefill pool: their urls reach the router role-tagged
    (``url#prefill``) and ``disagg=True`` is implied unless ``router_kw``
    says otherwise. The urls dict gains ``prefill_replicas``.

    Returns (urls dict, servers list) — callers __exit__ the servers.
    Shared by benches/bench_swarm.py, benches/bench_chaos.py,
    benches/bench_router.py and tests."""
    import os

    from tpu_voice_agent.services.brain import RuleBasedParser
    from tpu_voice_agent.services.brain import build_app as build_brain
    from tpu_voice_agent.services.executor import SessionManager
    from tpu_voice_agent.services.executor.page import FakePage
    from tpu_voice_agent.services.stack import AppServer, serve_stack
    from tpu_voice_agent.utils import chaos as chaos_mod

    servers: list = []
    urls: dict = {}
    brain_url = None  # a routed tier's url; None = serve_stack hosts the brain
    if brain_replicas > 1:
        from tpu_voice_agent.services.router import BrainRouter
        from tpu_voice_agent.services.router import build_app as build_router

        def make_parser():
            if parser is None:
                return RuleBasedParser()
            return parser() if callable(parser) and not hasattr(parser, "parse") \
                else parser

        replicas = [AppServer(build_brain(make_parser(),
                                          max_inflight=brain_inflight)).__enter__()
                    for _ in range(brain_replicas)]
        pf_replicas = [AppServer(build_brain(make_parser(),
                                             max_inflight=brain_inflight)
                                 ).__enter__()
                       for _ in range(prefill_replicas)]
        kw = dict(router_kw or {})
        if pf_replicas:
            kw.setdefault("disagg", True)
        robj = BrainRouter([b.url for b in replicas]
                           + [b.url + "#prefill" for b in pf_replicas], **kw)
        router = AppServer(build_router(robj)).__enter__()
        # the live router OBJECT rides on its server (ISSUE 16): elastic-
        # capacity drills attach an AutopilotController to it on the
        # router's own loop (router_server.router / router_server._loop)
        router.router = robj
        brain_url = router.url
        urls["router"] = router.url
        urls["replicas"] = [b.url for b in replicas]
        if pf_replicas:
            urls["prefill_replicas"] = [b.url for b in pf_replicas]
        servers += [router] + replicas + pf_replicas
    stack = serve_stack(
        None if brain_url else (parser or RuleBasedParser()),
        brain_url=brain_url,
        brain_kw={"max_inflight": brain_inflight},
        executor_kw={"max_inflight": exec_inflight},
        manager=SessionManager(page_factory=FakePage.demo,
                               artifacts_root=os.path.join(tmp_dir, "art"),
                               uploads_dir=os.path.join(tmp_dir, "up")),
        voice_cfg=dict(
            stt_factory=lambda: ScriptedSTT(frames_per_final=frames_per_final),
            parse_timeout_s=parse_timeout_s, retry_attempts=2))
    urls.update(stack.urls)
    if chaos_spec is not None:
        # armed only now: the stack's warm-up (serve_stack) must neither
        # trip an injected fault nor consume the drill's seeded draws
        chaos_mod.configure(chaos_spec, seed=chaos_seed)
    return urls, stack.servers + servers


# --------------------------------------------------------------- CLI


def _parse_mix(spec: str) -> dict[str, int]:
    mix = {}
    for part in spec.split(","):
        name, _, w = part.partition("=")
        mix[name.strip()] = int(w or 1)
    return mix


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--voice", default=DEFAULT_URLS["voice"])
    ap.add_argument("--brain", default=DEFAULT_URLS["brain"])
    ap.add_argument("--executor", default=DEFAULT_URLS["executor"])
    ap.add_argument("--n", type=int, default=8, help="concurrent sessions")
    ap.add_argument("--utterances", type=int, default=4, help="per session")
    ap.add_argument("--mix", type=_parse_mix, default=None,
                    help="scenario=weight,... (default: the full mix)")
    ap.add_argument("--think-s", type=float, default=0.05)
    ap.add_argument("--frames-per-final", type=int, default=4)
    ap.add_argument("--search", action="store_true",
                    help="binary-search capacity instead of one fixed-N run")
    ap.add_argument("--max-n", type=int, default=32)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    sample_urls = [args.voice, args.brain, args.executor]
    kw = dict(utterances=args.utterances, mix=args.mix, think_s=args.think_s,
              frames_per_final=args.frames_per_final)
    if args.search:
        out = binary_search_capacity(args.voice, max_n=args.max_n,
                                     sample_urls=sample_urls, **kw)
        headline = (f"capacity {out['capacity_sessions']} sessions at SLO "
                    f"(max probed {out['max_n']}, "
                    f"{'saturated' if out['saturated'] else 'NOT saturated'})")
    else:
        out = run_swarm(args.voice, args.n, sample_urls=sample_urls, **kw)
        headline = (f"n={out['n_sessions']}: slo {out['slo']['state']} "
                    f"p50 {out['slo']['p50_ms']} ms p99 {out['slo']['p99_ms']} ms")
    if args.json:
        print(json.dumps(out, indent=1))
    else:
        print(headline)
        sat = (out.get("knee") or out.get("at_capacity") or out).get("saturation", {})
        if sat:
            print(f"first saturated: {sat.get('first_saturated') or '(none crossed)'} "
                  f"peaks {sat.get('peak_fractions')}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
