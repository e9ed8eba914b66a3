"""Quality observatory: online per-utterance quality signals (ISSUE 15).

The observability plane could say how FAST every stage is (traces/SLO,
step ledger, fleet telemetry) but nothing in production could say how GOOD
the output is — WER and intent accuracy existed only as offline evals
(``evals/golden.py``, ``benches/bench_quality.py``), so a quality
regression (a drifting quantized KV tier, a degraded-mode fallback storm,
a replica transcribing garbage after a warm restart) was invisible until
someone reran a bench. This module turns quality into a live, windowed,
SLO-gated signal on every utterance:

- **STT confidence** — the Whisper decode loops return per-token logprob
  lanes (mean/min logprob, first-token logprob) on the same combined
  readback as the tokens; a host-side repetition heuristic rides along.
  Exported as ``stt.confidence_mean`` / ``stt.confidence_min`` /
  ``stt.confidence_repetition`` and fed here by the voice service per
  final transcript.
- **Intent confidence** — the grammar-constrained decode tail (the dense
  and paged planes share one readback contract, ``ChunkResult.conf``)
  reports masked-logit margin and entropy per accepted decision plus the grammar-forced-token fraction; the brain feeds them
  here per parse, with degraded/downgraded parses counted structurally.
- **Execution feedback** — executor action verdicts become weak labels
  per intent type (``quality.exec_success_rate``), closing the loop the
  reference never had.
- **Golden-replay canary** — ``GoldenCanary`` replays a rotating slice of
  the held-out golden cases through the LIVE parser during idle cycles
  (admission-gated on occupancy — it must never steal decode steps from
  real traffic), scoring type_match/args_score online into
  ``quality.golden_accuracy``.

The windowed floors live in ``utils.slo.QualityTracker``: an ok→violated
edge freezes a flight dump carrying the failing utterances' quality
vectors, and the PR 14 fleet detector reads the same gauges off the
per-replica time-series rings — a replica that is *fast but wrong* gets
demoted exactly like one that is slow.

All knobs are ``QUALITY_*`` (utils/knobs.py; docs/OBSERVABILITY.md
"Quality observatory"). ``QUALITY_ENABLE=0`` removes the device readback
lanes entirely — generated tokens are identical either way (the
differential tests/test_quality.py proves it per plane).
"""

from __future__ import annotations

import threading
import time
from collections import deque

from .knobs import knob_bool, knob_float, knob_int
from .slo import QualityTracker
from .tracing import get_metrics


def quality_lanes_enabled() -> bool:
    """THE one read of the device-lane switch (engines consult it at
    construction; a static jit argument, so each mode is its own compiled
    program and neither perturbs sampling)."""
    return knob_bool("QUALITY_ENABLE")


def repetition_score(ids: list[int]) -> float:
    """Host-side repetition heuristic over a final's token ids in [0, 1]:
    1 - distinct/total. Healthy speech sits low; the classic garbage
    signature (one token looped to the budget) sits near 1. Cheap enough
    to run on every final."""
    if not ids:
        return 0.0
    return 1.0 - len(set(ids)) / len(ids)


class QualityMonitor:
    """Per-service quality signal aggregation: bounded per-signal windows,
    gauges on every record, and the quality-SLO verdict.

    ``metrics`` should be the service's TRACER-LOCAL registry where one
    exists (``tracer.metrics``): in production each service is its own
    process so the distinction is invisible, but the in-process test/bench
    stacks share one global registry across replicas, and per-replica
    quality gauges are exactly what the fleet detector compares — a
    last-writer-wins global gauge would blind it (the PR 14 timeseries
    ring already samples the tracer-local registry per service).
    """

    def __init__(self, service: str, metrics=None,
                 window: int | None = None, tracker: QualityTracker | None = None):
        self.service = service
        self.metrics = metrics if metrics is not None else get_metrics()
        self.window = window if window is not None \
            else knob_int("QUALITY_WINDOW", 64)
        self.slo = tracker if tracker is not None else QualityTracker(
            "quality",
            floors={
                "golden_accuracy": knob_float("QUALITY_SLO_GOLDEN_MIN", 0.7),
                "exec_success_rate": knob_float("QUALITY_SLO_EXEC_MIN", 0.5),
                "intent_margin": knob_float("QUALITY_SLO_MARGIN_MIN", 0),
            },
            ceilings={
                "stt_repetition": knob_float("QUALITY_SLO_REPETITION_MAX", 0.9),
            },
            window=self.window, metrics=self.metrics)
        self._lock = threading.Lock()
        self._win: dict[str, deque] = {}
        # per-intent-type executor weak labels (ok counts / totals)
        self._exec_by_type: dict[str, list[int]] = {}
        # structural counters mirrored into state() (the registry keeps the
        # authoritative monotonic copies)
        self._counts: dict[str, int] = {}
        # the contract counters exist from construction (the breaker-gauge
        # discipline: scrape-visible at zero, never an absent series) —
        # these literals are also what tools/metrics_lint.py pins and the
        # OBSERVABILITY.md catalog vouches for, since _count increments
        # through a parameter
        m = self.metrics
        m.inc("quality.parses", 0.0)
        m.inc("quality.stt_finals", 0.0)
        m.inc("quality.degraded_parses", 0.0)
        m.inc("quality.rule_fallbacks", 0.0)
        m.inc("quality.exec_ok", 0.0)
        m.inc("quality.exec_failed", 0.0)
        m.inc("quality.canary_runs", 0.0)
        m.inc("quality.canary_errors", 0.0)
        m.inc("quality.canary_skipped_busy", 0.0)

    # ------------------------------------------------------------ windows

    def _push(self, signal: str, value: float) -> float:
        """Append to the signal's window; returns the window mean."""
        with self._lock:
            dq = self._win.get(signal)
            if dq is None:
                dq = self._win[signal] = deque(maxlen=self.window)
            dq.append(float(value))
            return sum(dq) / len(dq)

    def _count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + n
        self.metrics.inc(name, float(n))

    # ------------------------------------------------------------ signals

    def record_stt(self, logp_mean: float | None, logp_min: float | None,
                   repetition: float, text: str = "",
                   logp_first: float | None = None) -> None:
        """One final transcript's confidence vector (voice service)."""
        detail = {"signal": "stt", "text": text[:60],
                  "repetition": round(repetition, 4)}
        if logp_mean is not None:
            detail["logp_mean"] = round(logp_mean, 4)
            self.metrics.set_gauge("stt.confidence_mean",
                                   self._push("stt_logp_mean", logp_mean))
        if logp_min is not None:
            self.metrics.set_gauge("stt.confidence_min",
                                   self._push("stt_logp_min", logp_min))
        if logp_first is not None:
            # the no-speech margin proxy: how sure the decoder was about
            # its very first content token (real Whisper checkpoints add
            # the <|nospeech|> mass here; the lane generalizes)
            self.metrics.set_gauge("stt.confidence_first",
                                   self._push("stt_logp_first", logp_first))
        self.metrics.set_gauge("stt.confidence_repetition",
                               self._push("stt_repetition", repetition))
        self._count("quality.stt_finals")
        self.slo.record("stt_repetition", repetition, detail)

    def record_intent(self, margin: float | None = None,
                      entropy: float | None = None,
                      forced_frac: float | None = None,
                      degraded: bool = False, downgraded: bool = False,
                      rule_fallback: bool = False, text: str = "") -> None:
        """One parse's confidence/structural vector (brain or voice)."""
        detail = {"signal": "intent", "text": text[:60]}
        if margin is not None:
            detail["margin"] = round(margin, 4)
            self.metrics.set_gauge("quality.intent_margin",
                                   self._push("intent_margin", margin))
            self.slo.record("intent_margin", margin, detail)
        if entropy is not None:
            self.metrics.set_gauge("quality.intent_entropy",
                                   self._push("intent_entropy", entropy))
        if forced_frac is not None:
            self.metrics.set_gauge("quality.intent_forced_frac",
                                   self._push("intent_forced_frac", forced_frac))
        drate = self._push("degraded", 1.0 if (degraded or downgraded) else 0.0)
        self.metrics.set_gauge("quality.degraded_rate", drate)
        self._count("quality.parses")
        if degraded:
            self._count("quality.degraded_parses")
        if rule_fallback:
            self._count("quality.rule_fallbacks")

    def record_exec(self, intent_type: str, ok: bool) -> None:
        """One executor action verdict — the weak label per intent type."""
        rate = self._push("exec_ok", 1.0 if ok else 0.0)
        self.metrics.set_gauge("quality.exec_success_rate", rate)
        with self._lock:
            acc = self._exec_by_type.setdefault(intent_type, [0, 0])
            acc[0] += int(ok)
            acc[1] += 1
        self._count("quality.exec_ok" if ok else "quality.exec_failed")
        self.slo.record("exec_success_rate", 1.0 if ok else 0.0,
                        {"signal": "exec", "intent": intent_type, "ok": ok})

    def record_golden(self, type_match: bool, args_score: float,
                      text: str = "") -> None:
        """One golden-replay canary case scored against the live parser."""
        score = (0.5 if type_match else 0.0) + 0.5 * float(args_score)
        self.metrics.set_gauge("quality.golden_accuracy",
                               self._push("golden", score))
        trate = self._push("golden_type", 1.0 if type_match else 0.0)
        self.metrics.set_gauge("quality.golden_type_accuracy", trate)
        self.slo.record("golden_accuracy", score,
                        {"signal": "golden", "text": text[:60],
                         "type_match": type_match,
                         "args_score": round(float(args_score), 4)})

    # ------------------------------------------------------------ surface

    def state(self) -> dict:
        """The ``GET /debug/quality`` body."""
        with self._lock:
            windows = {sig: {"n": len(dq),
                             "mean": round(sum(dq) / len(dq), 4)}
                       for sig, dq in self._win.items() if dq}
            exec_by_type = {t: {"ok": a[0], "total": a[1],
                                "rate": round(a[0] / a[1], 4)}
                            for t, a in self._exec_by_type.items() if a[1]}
            counts = dict(self._counts)
        return {"service": self.service,
                "lanes_enabled": quality_lanes_enabled(),
                "windows": windows,
                "exec_by_type": exec_by_type,
                "counts": counts,
                "slo": self.slo.evaluate()}

    def health(self) -> dict:
        """The compact block /health carries (HUD badge food)."""
        means = {}
        with self._lock:
            for sig in ("golden", "intent_margin", "stt_logp_mean",
                        "stt_repetition", "exec_ok", "degraded"):
                dq = self._win.get(sig)
                if dq:
                    means[sig] = round(sum(dq) / len(dq), 4)
        out = {"slo": self.slo.state()}
        out.update(means)
        return out


class GoldenCanary:
    """Per-replica golden-replay canary: a daemon loop replaying a small
    rotating slice of the held-out golden cases through the LIVE parser
    during idle cycles.

    Admission-gated: ``busy_fn()`` (the replica's live occupancy — batch
    occupancy / admission inflight) is consulted before every round, and a
    busy replica's round is skipped (``quality.canary_skipped_busy``) —
    the canary must never steal decode steps from real traffic. Rotation
    is deterministic (case index advances per case scored), so every case
    is exercised on a bounded cadence and two replicas at the same round
    count have scored the same slice.
    """

    def __init__(self, parse_fn, monitor: QualityMonitor, *,
                 interval_s: float | None = None,
                 slice_n: int | None = None,
                 busy_fn=None, cases=None):
        from ..evals.golden import GOLDEN_INTENT_CASES

        self.parse_fn = parse_fn  # (text, context) -> ParseResponse-like
        self.monitor = monitor
        self.interval_s = interval_s if interval_s is not None \
            else knob_float("QUALITY_CANARY_S", 0)
        self.slice_n = slice_n if slice_n is not None \
            else knob_int("QUALITY_CANARY_SLICE", 3)
        self.busy_fn = busy_fn
        self.cases = list(cases if cases is not None else GOLDEN_INTENT_CASES)
        self._idx = 0
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self.rounds = 0

    @property
    def enabled(self) -> bool:
        return self.interval_s > 0 and bool(self.cases)

    def run_once(self) -> int:
        """One canary round (also the deterministic test surface): score
        the next ``slice_n`` cases unless the replica is busy. Returns
        cases scored this round."""
        from ..evals.golden import score_case

        if self.busy_fn is not None and self.busy_fn():
            self.monitor._count("quality.canary_skipped_busy")
            return 0
        scored = 0
        for _ in range(self.slice_n):
            case = self.cases[self._idx % len(self.cases)]
            self._idx += 1
            try:
                resp = self.parse_fn(case.text, dict(case.context))
                tm, ascore = score_case(case, resp)
            except Exception:
                # a parser error is a quality miss, not a canary crash —
                # the eval measures the served surface (evals.golden
                # discipline), and a replica erroring on golden inputs is
                # exactly what the floor should see
                tm, ascore = False, 0.0
                self.monitor._count("quality.canary_errors")
            self.monitor.record_golden(tm, ascore, text=case.text)
            scored += 1
        self.rounds += 1
        self.monitor._count("quality.canary_runs")
        return scored

    def start(self) -> None:
        if not self.enabled or (self._thread is not None
                                and self._thread.is_alive()):
            return
        self._stop.clear()

        def _run() -> None:
            while not self._stop.wait(self.interval_s):
                try:
                    self.run_once()
                except Exception:  # pragma: no cover - canary never kills
                    pass

        self._thread = threading.Thread(
            target=_run, daemon=True,
            name=f"quality-canary-{self.monitor.service}")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=5)
            self._thread = None


def make_quality_handler(monitor: QualityMonitor):
    """aiohttp ``GET /debug/quality``: the monitor's full state."""
    from aiohttp import web

    async def quality_ep(_req) -> web.Response:
        return web.json_response(monitor.state())

    return quality_ep


def conf_summary(conf_h, steps: int) -> dict | None:
    """Host-side reduction of one request's confidence lanes: the engines
    read back per-row ``(margin_sum, margin_min, entropy_sum, forced,
    decisions)`` accumulated over chunks; this folds one row's totals into
    the per-request quality dict GenerationResult carries. ``None`` when
    the lanes were off or the request made no decisions."""
    margin_sum, margin_min, ent_sum, forced, cnt = conf_h
    cnt = int(cnt)
    if cnt <= 0:
        return None
    mmin = float(margin_min)
    return {
        "margin_mean": round(float(margin_sum) / cnt, 4),
        "margin_min": round(mmin, 4) if mmin != float("inf") else None,
        "entropy_mean": round(float(ent_sum) / cnt, 4),
        "forced_frac": round(float(forced) / max(1, steps), 4),
        "decisions": cnt,
    }
