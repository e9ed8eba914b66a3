"""Replicated brain tier: a session-affine router over N brain replicas.

Everything before this PR was one brain process — a single point of failure
holding every piece of warm state (radix chains, session transcripts).
This service is the *replica* fault domain: an HTTP tier
that exposes the existing brain contract (``POST /parse``, ``GET /health``,
``GET /metrics``, ``/debug/*`` fan-out, ``POST /admin/drain``) in front of
``BRAIN_REPLICAS=url,url,...``, so the voice service just points
``BRAIN_URL`` at it and a replica crash, hang, or rolling restart costs a
cold re-prefill — never a session, never the SLO. The same "keep the stream
alive while a stage restarts" discipline WhisperFlow applies to real-time
speech serving, applied to the LLM side of the pipeline (PAPERS.md).

Design:

- **Session affinity by rendezvous hashing.** ``session_id`` → replica via
  highest-random-weight over the *admitting* set, so each replica's radix
  tree / transcript LRU stays hot for its own sessions. Placement is
  rendezvous; residence is sticky: a placed session stays on its home while
  that home remains servable (warmth built after a failover is not thrown
  away when the old home recovers — re-homing costs a cold re-prefill, so
  it is paid only when forced). When a home dies, the session deterministically
  re-homes to its next-highest-weight replica; every forced move counts
  ``router.sessions_rehomed`` (the observable cost = one cold re-prefill).

- **Health = active probe + passive breaker.** A prober polls each
  replica's ``/health`` every ``ROUTER_PROBE_S``; ``ROUTER_PROBE_FAILS``
  consecutive failures (or a 503 body) ejects the replica from the ring.
  Passively, every transport failure feeds a per-replica PR 1
  ``CircuitBreaker`` — a replica that hangs on /parse while answering
  probes trips it and leaves the ring anyway. Both recover automatically.

- **Failover inside the budget.** A parse whose home fails mid-flight is
  retried ONCE on the session's new home, inside the original
  ``x-deadline-ms`` budget (the first attempt is capped at half the
  remaining budget whenever a retry is still possible, so the retry always
  fits; a mid-flight probe ejection cancels the attempt early rather than
  waiting out the cap). Speculative parses are NEVER replayed on the new
  home — the final re-routes and parses fresh; a replayed speculation could
  interleave with that re-routed final on the new replica (the voice
  service's spec machinery already treats the resulting 503 as a miss).

- **Graceful drain.** ``POST /admin/drain {"replica": url}`` forwards the
  drain to the replica (whose serve layer latches ``ColocatedServing.
  begin_drain``) and stops placing NEW sessions there; existing sessions
  keep hitting it until the router-side in-flight count reaches zero, then
  the replica is ejected (``drained`` state) and its sessions re-home — a
  rolling restart with zero dropped requests. A drained replica that then
  goes down and comes back (the restart) rejoins as ``up``; a restart too
  fast for the probe to see it go down is detected by the serve-layer
  drain latch disappearing from /health (only a fresh process drops it);
  ``POST /admin/admit`` forces a rejoin.

- **Hedged parses.** ``ROUTER_HEDGE_MS > 0`` fires a second attempt at the
  next-best replica for idempotent parses (speculative or session-less)
  still unanswered after the hedge delay; first usable answer wins, the
  loser's HTTP request is cancelled — which cancels the replica's handler
  and, through the PR 7 chain, evicts its decode slot at the next chunk
  boundary. Session-committing parses are never hedged (two replicas must
  not both record the turn).

- **Warm-state handoff (ISSUE 13).** ``HANDOFF_ENABLE=1``: when a forced
  move re-homes a session and its OLD home is still reachable (a drain,
  not a crash), the router ships the session's warm state — transcript
  token ids plus the radix chain's paged KV block bytes, serialized by
  ``serve.handoff`` — from the old home to the new one before forwarding
  the parse, so the re-homed turn costs ~transfer bookkeeping instead of
  a cold re-prefill (AND keeps its multi-turn context, which a cold
  re-home loses). ``router.sessions_rehomed`` splits into ``_warm`` (KV
  adopted on the new home) and ``_cold`` (crash, handoff off, donor had
  no warm state, or the recipient fell back — always clean: the new home
  just cold-prefills).

- **Gauge-driven shedding (ISSUE 13).** Each probe carries the replica's
  ``pressure.score`` (max of batch occupancy, KV pressure net of
  evictable radix cache, admission inflight fraction, forced high by a
  non-ok SLO — the observatory's saturation signals, read live). NEW sessions
  avoid replicas at/over ``ROUTER_SHED_PRESSURE`` while any replica is
  under it (``router.shed_pressure`` counts the redirects); sticky
  sessions never move for pressure, and all-over falls back to plain
  rendezvous — overload degrades placement quality instead of erroring.

- **Full outage.** Every replica out of the ring → ``503 + Retry-After``,
  which the voice service already maps to the RuleBasedParser degraded
  mode: quality degrades, sessions survive.

- **Prefill/decode disaggregation (ISSUE 20).** ``ROUTER_DISAGG=1`` splits
  the ring into a *prefill pool* (members tagged ``url#prefill`` in
  ``BRAIN_REPLICAS``, listed in ``ROUTER_PREFILL_REPLICAS``, or self-
  reporting ``BRAIN_ROLE=prefill`` through /health) and a *decode pool*
  (everyone else). Sessions place only on decode members; a parse whose
  uncached-prompt estimate clears ``DISAGG_MIN_TOKENS`` first runs a
  prefill-only export on a prefill member and pumps the resulting KV
  frames — chunk-pipelined, ``DISAGG_STREAM_BLOCKS`` per segment — into
  the decode home's stream adopter, so the home admits warm and its decode
  step loop never eats a barrier prefill. Prefix feeds ride the same wire
  (a feed IS a prefill-only admission; the fed chain lands on the session's
  decode home), and speculative parses forward to the prefill pool — their
  decode burst stays off the latency-critical replicas and their prefill
  warms the pool's cache for the final's export. EVERY failure (prefill
  death mid-stream, adopt refusal, tier mismatch, budget overrun) falls
  back to the plain forward — clean-or-cold, counted ``disagg.fallbacks``,
  never an error. With ``ROUTER_DISAGG`` unset every path here is
  byte-identical to the pre-disagg build.
"""

from __future__ import annotations

import asyncio
import json
import os
import time
import urllib.parse
from collections import deque

from aiohttp import web

from ..utils import SLOTracker, Tracer, get_metrics, load_env_cascade, new_trace_id
from ..utils.resilience import (
    DEADLINE_HEADER,
    Deadline,
    shed_response,
)
from .replicaset import Replica, ReplicaSet
from .replicaset import rendezvous_weight as _weight  # noqa: F401 - test surface

# response headers forwarded back to the caller verbatim (the brain's
# decode-split contract the voice service folds into latency_budget, plus
# the two-phase speculation marker and the shed backoff hint)
_PASS_HEADERS = ("x-trace-id", "x-prefill-ms", "x-decode-ms", "x-queue-ms",
                 "x-cached-tokens", "x-prompt-tokens", "x-intent-margin",
                 "x-speculation-pending", "retry-after")


class ReplicaFailed(RuntimeError):
    """One forward attempt failed at the transport level (connect error,
    reset, attempt timeout, or mid-flight ejection) — retryable on the
    session's next home; NOT raised for HTTP answers (those are the
    replica's own semantics and pass through)."""


class BrainRouter(ReplicaSet):
    """Routing state + forwarding logic; ``build_app`` wires it to HTTP.
    The ring state machine itself (placement, drain, probe verdicts) is
    the shared ``services.replicaset.ReplicaSet`` core — the STT tier
    (``serve.stt_replicas``) runs the same one — and this class owns the
    HTTP half: probing, forwarding, hedging, failover, warm handoff.

    Every mutation of routing state happens between awaits on the event
    loop (route selection + session-table update + inflight accounting are
    single, await-free critical sections), so the racy surface the hammer
    test drives — concurrent submits vs. a probing eject vs. a drain — is
    serialized by the loop itself, no locks needed.
    """

    def __init__(self, replica_urls: list[str], *,
                 probe_s: float | None = None,
                 probe_timeout_s: float | None = None,
                 probe_fails: int | None = None,
                 hedge_ms: float | None = None,
                 parse_timeout_s: float | None = None,
                 max_sessions: int | None = None,
                 breaker_threshold: int | None = None,
                 breaker_reset_s: float | None = None,
                 handoff_enable: bool | None = None,
                 handoff_timeout_s: float | None = None,
                 shed_pressure: float | None = None,
                 fleet_detect: bool | None = None,
                 fleet_mad: float | None = None,
                 fleet_windows: int | None = None,
                 fleet_min_peers: int | None = None,
                 fleet_hold_s: float | None = None,
                 disagg: bool | None = None,
                 disagg_min_tokens: int | None = None,
                 disagg_stream_blocks: int | None = None,
                 prefill_urls: list[str] | None = None):
        if not replica_urls:
            raise ValueError("BRAIN_REPLICAS must name at least one replica")
        env = os.environ.get
        # prefill/decode disaggregation (ISSUE 20): members may carry a
        # ``url#role`` tag in the replica list; ROUTER_PREFILL_REPLICAS
        # appends prefill-tagged members. The ring's keys stay bare urls —
        # roles land on the Replica objects after construction.
        roles: dict[str, str] = {}
        keys: list[str] = []
        for u in replica_urls:
            base, _, tag = str(u).strip().partition("#")
            base = base.rstrip("/")
            if not base:
                continue
            keys.append(base)
            if tag in ("prefill", "decode", "both"):
                roles[base] = tag
        if prefill_urls is None:
            prefill_urls = [u.strip() for u in
                            env("ROUTER_PREFILL_REPLICAS", "").split(",")
                            if u.strip()]
        for u in prefill_urls:
            base = str(u).partition("#")[0].rstrip("/")
            if not base:
                continue
            if base not in keys:
                keys.append(base)
            roles[base] = "prefill"
        self.disagg = disagg if disagg is not None \
            else env("ROUTER_DISAGG") == "1"
        self.disagg_min_tokens = disagg_min_tokens \
            if disagg_min_tokens is not None \
            else int(env("DISAGG_MIN_TOKENS", "256"))
        self.disagg_stream_blocks = disagg_stream_blocks \
            if disagg_stream_blocks is not None \
            else int(env("DISAGG_STREAM_BLOCKS", "4"))
        self.handoff_framed = env("HANDOFF_FRAMED", "0") == "1"
        # fleet gray-failure detection (ISSUE 14): the prober additionally
        # scrapes each member's /debug/timeseries deltas and demotes
        # sustained peer-relative outliers (services/replicaset.py)
        if fleet_detect is None:
            fleet_detect = env("FLEET_DETECT", "1") != "0"
        fleet_mad = fleet_mad if fleet_mad is not None \
            else float(env("FLEET_GRAY_MAD", "4.0"))
        self.probe_s = probe_s if probe_s is not None else \
            float(env("ROUTER_PROBE_S", "0.5"))
        self.probe_timeout_s = probe_timeout_s if probe_timeout_s is not None \
            else float(env("ROUTER_PROBE_TIMEOUT_S", "2.0"))
        self.hedge_ms = hedge_ms if hedge_ms is not None else \
            float(env("ROUTER_HEDGE_MS", "0"))
        self.parse_timeout_s = parse_timeout_s if parse_timeout_s is not None \
            else float(env("ROUTER_PARSE_TIMEOUT_S", "60"))
        self.handoff_enable = handoff_enable if handoff_enable is not None \
            else env("HANDOFF_ENABLE") == "1"
        self.handoff_timeout_s = handoff_timeout_s \
            if handoff_timeout_s is not None \
            else float(env("HANDOFF_TIMEOUT_S", "5.0"))
        super().__init__(
            keys,
            probe_fails_limit=(probe_fails if probe_fails is not None
                               else int(env("ROUTER_PROBE_FAILS", "2"))),
            breaker_threshold=(breaker_threshold
                               if breaker_threshold is not None
                               else int(env("ROUTER_BREAKER_THRESHOLD", "3"))),
            breaker_reset_s=(breaker_reset_s if breaker_reset_s is not None
                             else float(env("ROUTER_BREAKER_RESET_S", "2.0"))),
            max_sessions=(max_sessions if max_sessions is not None
                          else int(env("ROUTER_SESSIONS", "4096"))),
            shed_pressure=(shed_pressure if shed_pressure is not None
                           else float(env("ROUTER_SHED_PRESSURE", "0.9"))),
            gray_mad=(fleet_mad if fleet_detect else None),
            gray_windows=(fleet_windows if fleet_windows is not None
                          else int(env("FLEET_GRAY_WINDOWS", "3"))),
            gray_min_peers=(fleet_min_peers if fleet_min_peers is not None
                            else int(env("FLEET_MIN_PEERS", "3"))),
            gray_hold_s=(fleet_hold_s if fleet_hold_s is not None
                         else float(env("FLEET_GRAY_HOLD_S", "300"))),
            log_name="tpu_voice_agent.router")
        for base, role in roles.items():
            member = self._by_url.get(base)
            if member is not None:
                member.role = role
        if self.disagg:
            # general placement avoids the prefill pool (falls back to the
            # whole ring if that would empty it — replicaset contract)
            self.exclude_roles = {"prefill"}
        # disagg orchestration state: per-session (home, prompt, cached)
        # token history from response headers — the uncached-prompt
        # estimator's memory; a rolling (monotonic t, blocks) window
        # feeding the /health streamed-blocks/s roll-up; and the live
        # export count behind the prefill-queue gauge
        self._session_tokens: "dict[str, tuple[str, int, int]]" = {}
        self._stream_win: "deque[tuple[float, int]]" = deque()
        self._disagg_inflight = 0
        self._http = None  # httpx.AsyncClient, created on the app's loop
        self._probe_task: asyncio.Task | None = None
        # the contract counters/gauges exist from construction (the breaker
        # gauge discipline: scrape-visible at zero, never an absent series)
        m = get_metrics()
        m.inc("router.sessions_rehomed", 0.0)
        m.inc("router.sessions_rehomed_warm", 0.0)
        m.inc("router.sessions_rehomed_cold", 0.0)
        m.inc("router.shed_pressure", 0.0)
        m.inc("router.hedges_fired", 0.0)
        m.inc("router.hedges_won", 0.0)
        m.inc("router.drains", 0.0)
        m.inc("router.retries", 0.0)
        m.inc("router.spec_discarded", 0.0)
        m.inc("fleet.scrapes", 0.0)
        m.inc("fleet.gray_entered", 0.0)
        m.inc("fleet.gray_recovered", 0.0)
        m.inc("fleet.shed_gray", 0.0)
        m.inc("router.replicas_added", 0.0)
        m.inc("router.replicas_removed", 0.0)
        m.inc("disagg.admissions", 0.0)
        m.inc("disagg.fallbacks", 0.0)
        m.inc("disagg.feeds_routed", 0.0)
        m.inc("disagg.spec_routed", 0.0)
        m.inc("disagg.frames_streamed", 0.0)
        m.inc("disagg.tokens_prewarmed", 0.0)
        m.set_gauge("fleet.gray_replicas", 0.0)
        m.set_gauge("fleet.outlier_score_max", 0.0)
        m.set_gauge("disagg.prefill_replicas", 0.0)
        m.set_gauge("disagg.decode_replicas", 0.0)
        m.set_gauge("disagg.prefill_queue", 0.0)
        self._update_health_gauge()

    # ---------------------------------------------- replica-set hooks
    # literal metric names on purpose: tools/metrics_lint.py pins them, so
    # the shared core routes accounting through these instead of f-strings

    def _update_health_gauge(self) -> None:
        m = get_metrics()
        # total rides the same hook so elastic membership (ISSUE 16)
        # keeps it honest — the ring is no longer fixed at construction
        m.set_gauge("router.replicas_total", float(len(self.replicas)))
        m.set_gauge("router.replicas_healthy",
                    sum(1 for r in self.replicas if r.servable()))

    def _on_member_added(self, replica: Replica) -> None:
        get_metrics().inc("router.replicas_added")

    def _on_member_removed(self, replica: Replica) -> None:
        get_metrics().inc("router.replicas_removed")
        # the retired member's per-idx outlier gauge must not linger on
        # dashboards as if the member still reported
        get_metrics().set_gauge(f"fleet.outlier.{replica.idx}", 0.0)

    def _on_rehome(self) -> None:
        get_metrics().inc("router.sessions_rehomed")

    def _on_shed_pressure(self) -> None:
        get_metrics().inc("router.shed_pressure")

    def _on_drain(self) -> None:
        get_metrics().inc("router.drains")

    def _on_drain_completed(self) -> None:
        get_metrics().inc("router.drains_completed")

    def _on_ejected(self, replica: Replica) -> None:
        get_metrics().inc("router.replicas_ejected")

    def _on_recovered(self, replica: Replica) -> None:
        get_metrics().inc("router.replicas_recovered")

    def _on_shed_gray(self) -> None:
        get_metrics().inc("fleet.shed_gray")

    def _on_gray_entered(self, replica: Replica, evidence: dict) -> None:
        from ..utils.tracing import get_flight_recorder, log_event

        get_metrics().inc("fleet.gray_entered")
        log_event("router", "replica_gray", replica=replica.url,
                  signal=evidence.get("signal"),
                  score=evidence.get("score"))
        # the incident autopsy: freeze the flight recorder WITH the
        # peer-comparison evidence that justified the demotion — the dump
        # answers "why did the fleet demote this replica" from the moment
        # of detection, not from a re-run
        get_flight_recorder().trigger("fleet.gray", detail=replica.url,
                                      extra={"fleet": evidence})

    def _on_gray_cleared(self, replica: Replica) -> None:
        get_metrics().inc("fleet.gray_recovered")

    def _update_gray_gauge(self) -> None:
        m = get_metrics()
        m.set_gauge("fleet.gray_replicas",
                    sum(1 for r in self.replicas if r.gray))
        m.set_gauge("fleet.outlier_score_max",
                    max((r.outlier_score for r in self.replicas),
                        default=0.0))
        for r in self.replicas:
            m.set_gauge(f"fleet.outlier.{r.idx}", r.outlier_score)

    # ------------------------------------------------------------ probing

    async def probe_once(self) -> None:
        """One active-probe sweep: every replica's /health, concurrently.
        With fleet detection armed, the sweep additionally scrapes each
        member's time-series deltas and applies the gray-failure verdict
        (ISSUE 14) — health says *alive*, the fleet window says *right*."""
        await asyncio.gather(*(self._probe_replica(r) for r in self.replicas))
        for r in self.replicas:
            self._maybe_finish_drain(r)
        self._update_health_gauge()
        if self.disagg:
            m = get_metrics()
            m.set_gauge("disagg.prefill_replicas",
                        sum(1 for r in self.replicas
                            if r.role == "prefill" and r.servable()))
            m.set_gauge("disagg.decode_replicas",
                        sum(1 for r in self.replicas
                            if r.role != "prefill" and r.servable()))
        if self.gray_mad is not None:
            await self._fleet_scrape()

    async def _fleet_scrape(self) -> None:
        """One fleet telemetry window: pull every servable member's new
        time-series samples (``?since=`` delta cursor per member), reduce
        them to signal vectors, and hand the window to the shared gray
        state machine. Also records the per-member wall-clock skew
        estimate the multi-service dump merge needs."""
        targets = [r for r in self.replicas if r.servable()]
        readings_list = await asyncio.gather(
            *(self._scrape_timeseries(r) for r in targets))
        readings = {r.url: sig for r, sig in zip(targets, readings_list)
                    if sig}
        for r in targets:
            # the router-observed forward wall rides the window as the
            # "observed" fwd_ms signal (mean since the last window)
            if r.fwd_acc:
                sig = readings.setdefault(r.url, {})
                sig["fwd_ms"] = sum(r.fwd_acc) / len(r.fwd_acc)
                r.fwd_acc = []
        self.apply_fleet_window(readings)
        get_metrics().inc("fleet.scrapes")

    async def _scrape_timeseries(self, r: Replica) -> dict | None:
        """GET one member's timeseries delta; returns the window's reduced
        signal vector (None on error / nothing new). Updates the member's
        delta cursor and its NTP-style clock-skew estimate (server ``now_s``
        minus the request's local midpoint)."""
        import httpx

        from .replicaset import reduce_window

        try:
            t0 = time.time()
            resp = await self._http.get(
                r.url + f"/debug/timeseries?since={r.ts_seq}",
                timeout=self.probe_timeout_s)
            t1 = time.time()
            if resp.status_code != 200:
                return None
            body = resp.json()
        except (httpx.HTTPError, OSError, ValueError, asyncio.TimeoutError):
            return None
        if not isinstance(body, dict):
            return None
        now_s = body.get("now_s")
        if isinstance(now_s, (int, float)):
            r.clock_skew_s = float(now_s) - (t0 + t1) / 2
        next_seq = body.get("next_seq")
        if isinstance(next_seq, int):
            r.ts_seq = next_seq
        samples = body.get("samples") or []
        return reduce_window([s for s in samples if isinstance(s, dict)])

    async def _probe_replica(self, r: Replica) -> None:
        import httpx

        try:
            resp = await self._http.get(r.url + "/health",
                                        timeout=self.probe_timeout_s)
            body = resp.json()
            ok = resp.status_code == 200 and bool(body.get("ok", True))
        except (httpx.HTTPError, OSError, ValueError, asyncio.TimeoutError):
            ok, body = False, None
        if ok and isinstance(body, dict):
            # the shed signal rides the probe: the replica's own saturation
            # score (brain /health ``pressure`` block — the gauges the
            # observatory already exports, folded to one fraction)
            p = body.get("pressure")
            try:
                r.pressure = float(p.get("score", 0.0)) if isinstance(p, dict) \
                    else 0.0
            except (TypeError, ValueError):
                r.pressure = 0.0
        # the verdict state machine (eject/rejoin/drain latch) is the
        # shared replica-set core's, unchanged from PR 10
        self.apply_probe(r, ok, body)

    async def _probe_loop(self) -> None:
        while True:
            try:
                await self.probe_once()
            except asyncio.CancelledError:
                raise
            except Exception:  # pragma: no cover - probe must never die
                import logging

                logging.getLogger("tpu_voice_agent.router").exception(
                    "probe sweep failed")
            await asyncio.sleep(self.probe_s)

    # --------------------------------------------------------- forwarding

    async def _forward(self, replica: Replica, raw: bytes, headers: dict,
                       deadline: Deadline):
        replica.inflight += 1
        t0 = time.perf_counter()
        try:
            resp = await self._http.post(
                replica.url + "/parse", content=raw,
                headers={**headers, "Content-Type": "application/json",
                         DEADLINE_HEADER: deadline.header_value()},
                timeout=max(0.05, deadline.remaining_s()))
            # the router-observed forward wall feeds the fleet detector's
            # ``fwd_ms`` signal: measured on OUR clock, so a replica slow
            # anywhere on its serving path (middleware, network, GC) is
            # visible even when its self-reported spans look healthy
            replica.fwd_acc.append((time.perf_counter() - t0) * 1e3)
            if len(replica.fwd_acc) > 512:
                del replica.fwd_acc[:256]
            return resp
        finally:
            # atomic-section: router.inflight-release -- the inflight decrement and the drain-completion check must be one step: a suspension between them can eject a draining replica while this request still counts against it
            replica.inflight -= 1
            self._maybe_finish_drain(replica)
            # end-atomic-section

    async def _guarded(self, replica: Replica, raw: bytes, headers: dict,
                       deadline: Deadline, budget_s: float):
        """One forward attempt bounded by ``budget_s`` wall clock and
        cancelled EARLY when the prober/breaker ejects the replica
        mid-flight (a dead replica's in-flight parses must not wait out
        their budget before failing over). Records the attempt's outcome
        on the replica's breaker."""
        import httpx

        task = asyncio.ensure_future(
            self._forward(replica, raw, headers, deadline))
        end = time.monotonic() + budget_s
        try:
            while True:
                left = end - time.monotonic()
                if left <= 0:
                    task.cancel()
                    replica.breaker.record_failure()
                    raise ReplicaFailed(
                        f"{replica.url}: attempt exceeded its budget")
                done, _ = await asyncio.wait({task},
                                             timeout=min(0.25, left))
                if done:
                    break
                if not replica.servable():
                    task.cancel()
                    # the prober already ejected it; no extra breaker count
                    raise ReplicaFailed(f"{replica.url}: ejected mid-flight")
        except asyncio.CancelledError:
            task.cancel()  # our caller was torn down: drop the forward too
            raise
        try:
            resp = task.result()  # analyze: ok[async-blocking] -- asyncio.Task just surfaced in asyncio.wait's done set — .result() is a non-blocking readback
        except asyncio.CancelledError:
            replica.breaker.record_failure()
            raise ReplicaFailed(f"{replica.url}: forward cancelled")
        except (httpx.HTTPError, OSError) as e:
            replica.breaker.record_failure()
            raise ReplicaFailed(f"{replica.url}: {type(e).__name__}: {e}")
        # any HTTP answer is transport health; 5xx is dependency-health
        # evidence (the PR 1 kit's discipline) EXCEPT 503, which is a
        # healthy replica shedding load
        if resp.status_code >= 500 and resp.status_code != 503:
            replica.breaker.record_failure()
        else:
            replica.breaker.record_success()
        return resp

    async def _attempt(self, home: Replica, session_id: str | None,
                       raw: bytes, headers: dict, deadline: Deadline,
                       budget_s: float, idempotent: bool):
        """Primary forward, optionally hedged: for idempotent parses still
        unanswered after ``ROUTER_HEDGE_MS``, a second attempt fires at the
        next-best replica; first usable answer wins and the loser is
        cancelled (→ the replica's handler cancels → the PR 7 chain evicts
        its decode slot). Returns (response, served_replica, hedged)."""
        primary = asyncio.ensure_future(
            self._guarded(home, raw, headers, deadline, budget_s))
        try:
            return await self._attempt_inner(primary, home, session_id, raw,
                                             headers, deadline, idempotent)
        except asyncio.CancelledError:
            # our caller (the router handler) was torn down — the voice
            # client vanished. Cancelling the _guarded task cancels its
            # forward, which cancels the replica's handler, which evicts
            # the decode slot at the next chunk boundary (the PR 7 chain,
            # now crossing one more hop).
            primary.cancel()
            raise

    async def _attempt_inner(self, primary, home: Replica,
                             session_id: str | None, raw: bytes,
                             headers: dict, deadline: Deadline,
                             idempotent: bool):
        if not (self.hedge_ms > 0 and idempotent):
            return await primary, home, False
        done, _ = await asyncio.wait({primary},
                                     timeout=self.hedge_ms / 1e3)
        if done:
            # analyze: ok[async-blocking] -- asyncio.Task just surfaced in asyncio.wait's done set — .result() is a non-blocking readback (may raise ReplicaFailed)
            return primary.result(), home, False
        alt = self._pick(session_id, exclude={home.url})
        if alt is None:
            return await primary, home, False
        get_metrics().inc("router.hedges_fired")
        secondary = asyncio.ensure_future(
            self._guarded(alt, raw, headers, deadline,
                          max(0.05, deadline.remaining_s())))
        tasks = {primary: home, secondary: alt}
        pending = set(tasks)
        winner = None
        fallback = None
        last_exc: Exception | None = None
        try:
            while pending and winner is None:
                done, pending = await asyncio.wait(
                    pending, return_when=asyncio.FIRST_COMPLETED)
                for t in done:
                    try:
                        resp = t.result()  # analyze: ok[async-blocking] -- asyncio.Task just surfaced in asyncio.wait's done set — .result() is a non-blocking readback
                    except ReplicaFailed as e:
                        last_exc = e
                        continue
                    if resp.status_code >= 500 and pending:
                        # "first USABLE answer wins": a shed 503 (or 5xx)
                        # from one replica must not beat an attempt that is
                        # still running and may yet succeed — hold it as
                        # the fallback and let the race continue
                        if fallback is None:
                            fallback = (resp, tasks[t], True)
                        continue
                    winner = (resp, tasks[t], True)
                    break
        finally:
            for t in pending:
                t.cancel()  # the losing attempt: cancelled, not abandoned
        if winner is None:
            winner = fallback
        if winner is None:
            raise last_exc or ReplicaFailed("all hedged attempts failed")
        if winner[1] is alt:
            get_metrics().inc("router.hedges_won")
        return winner

    # ------------------------------------------------------------ handoff

    async def _rehome_handoff(self, session_id: str, old_url: str,
                              new: Replica, deadline: Deadline) -> bool:
        """A forced move just happened: try to ship the session's warm
        state (transcript ids + radix-chain KV bytes, serve.handoff) from
        the old home to the new one, and split the re-home accounting into
        warm/cold. Always best-effort — every failure mode (handoff off,
        dead donor, no warm state, recipient under pool pressure, replica
        without the endpoints) just leaves the cold re-prefill PR 10
        already paid, never an error."""
        warm = False
        if self.handoff_enable:
            warm = await self._ship_warm_state(session_id, old_url, new.url,
                                               deadline)
        get_metrics().inc("router.sessions_rehomed_warm" if warm
                          else "router.sessions_rehomed_cold")
        return warm

    async def _ship_warm_state(self, session_id: str, old_url: str,
                               new_url: str, deadline: Deadline) -> bool:
        """GET the donor's serialized session state, POST it to the new
        home. Bounded by HANDOFF_TIMEOUT_S and a third of the remaining
        parse budget per hop (a hung donor must not eat the deadline the
        failover exists to honor). True only when the recipient adopted
        actual KV (``adopted_tokens > 0``) — a transcript-only adoption
        keeps the turn token-identical but still pays a cold prefill."""
        import httpx

        budget = min(self.handoff_timeout_s,
                     max(0.05, deadline.remaining_s() / 3))
        sid = urllib.parse.quote(session_id, safe="")
        try:
            resp = await self._http.get(old_url + "/admin/handoff/" + sid,
                                        timeout=budget)
            if resp.status_code != 200 or not resp.content:
                return False
            content = resp.content
            if self.handoff_framed:
                # HANDOFF_FRAMED=1 (ISSUE 20): the warm re-home rides the
                # same sequence-numbered, CRC-checked multi-part frame the
                # disagg KV stream uses; the adopt endpoint sniffs the
                # frame magic and reassembles (a torn/reordered body maps
                # to the clean cold fallback there, never a bad install)
                from ..serve.handoff import frame_split

                content = b"".join(frame_split(content, 256 << 10))
            resp2 = await self._http.post(
                new_url + "/admin/handoff", content=content,
                headers={"Content-Type": "application/octet-stream"},
                timeout=budget)
            if resp2.status_code != 200:
                return False
            return int(resp2.json().get("adopted_tokens", 0)) > 0
        except (httpx.HTTPError, OSError, ValueError, asyncio.TimeoutError):
            return False

    async def prewarm_member(self, replica: Replica, budget_s: float) -> int:
        """Pre-warm a JOINING member's radix root before it takes traffic
        (ISSUE 16): ship the most recently active sticky session's warm
        state — transcript ids + radix-chain KV bytes, the same
        ``serve.handoff`` pack/adopt wire the re-home path uses — from an
        admitting donor to the joining member. Adoption threads the
        session's chain into the member's radix tree, so the shared
        prompt root (and the donor session, should it ever re-home here)
        is hot before the first placed session prefills. Returns the
        adopted token count; 0 means nothing shippable (empty fleet, no
        sessions yet, or handoff-less replicas — rule parsers 404 the
        endpoints) and the CALLER decides whether a cold admit is
        acceptable. Best-effort and bounded by ``budget_s`` per hop: a
        wedged donor or recipient must surface as a slow join the
        autopilot's join timeout can retire, never a hung control loop."""
        import httpx

        donor_sid = donor_url = None
        for sid, url in reversed(self._sessions.items()):
            d = self._by_url.get(url)
            if d is not None and d is not replica and d.servable():
                donor_sid, donor_url = sid, url
                break
        if donor_sid is None:
            return 0
        sid_q = urllib.parse.quote(donor_sid, safe="")
        try:
            resp = await self._http.get(
                donor_url + "/admin/handoff/" + sid_q, timeout=budget_s)
            if resp.status_code != 200 or not resp.content:
                return 0
            resp2 = await self._http.post(
                replica.url + "/admin/handoff", content=resp.content,
                headers={"Content-Type": "application/octet-stream"},
                timeout=budget_s)
            if resp2.status_code != 200:
                return 0
            return int(resp2.json().get("adopted_tokens", 0))
        except (httpx.HTTPError, OSError, ValueError, asyncio.TimeoutError):
            return 0

    # ------------------------------------------- disagg orchestration
    # (ISSUE 20; every method below is a no-op surface when self.disagg
    # is False — forward_parse never calls them, keeping the unset build
    # byte-identical)

    def _pick_prefill(self, exclude=()) -> Replica | None:
        """Least-inflight admitting prefill-pool member (prefill work is
        anonymous from the ring's view: no session should ever stick to a
        prefill replica, so placement is pure load balancing)."""
        pool = [r for r in self.replicas
                if r.role == "prefill" and r.admitting()
                and r.url not in exclude]
        if not pool:
            return None
        return min(pool, key=lambda r: r.inflight)

    def _note_session_tokens(self, session_id: str | None, served_url: str,
                             resp) -> None:
        """Record a served parse's (home, prompt, cached) token headers —
        the uncached-prompt estimator's per-session memory. Rides the
        session table's own LRU budget."""
        if not session_id or resp is None:
            return
        try:
            pt = int(resp.headers.get("x-prompt-tokens", ""))
        except (TypeError, ValueError):
            return
        try:
            ct = int(resp.headers.get("x-cached-tokens", "0") or 0)
        except (TypeError, ValueError):
            ct = 0
        self._session_tokens[session_id] = (served_url, pt, ct)
        while len(self._session_tokens) > self.max_sessions:
            self._session_tokens.pop(next(iter(self._session_tokens)))

    def _uncached_estimate(self, session_id: str | None, body: dict) -> int:
        """How many UNCACHED prompt tokens this parse will likely admit on
        its decode home — the disagg placement signal. A session's last
        ``x-prompt-tokens``/``x-cached-tokens`` answer anchors the known
        part; the new utterance adds ~len/4 tokens. A session with no
        history (cold: the long-prompt admission disagg exists for) is
        estimated from its text alone, and a session whose home moved
        since that answer counts the WHOLE last prompt as uncached — the
        new home has none of it."""
        text = str(body.get("text") or "")
        ctx = body.get("context")
        est_new = (len(text) + (len(str(ctx)) if ctx else 0)) // 4 + 8
        if not session_id:
            return est_new
        rec = self._session_tokens.get(session_id)
        if rec is None:
            return est_new
        url, prompt_toks, cached_toks = rec
        if self._sessions.get(session_id) != url:
            return prompt_toks + est_new
        return max(0, prompt_toks - cached_toks) + est_new

    async def _adopt_one(self, home: Replica, stream_id: str,
                         blob: bytes) -> dict | None:
        """POST one stream blob to the decode home's adopter. None on any
        transport/HTTP failure (→ the caller aborts the stream)."""
        import httpx

        try:
            resp = await self._http.post(
                home.url + "/admin/disagg/adopt", content=blob,
                headers={"Content-Type": "application/octet-stream",
                         "x-disagg-stream": stream_id},
                timeout=self.handoff_timeout_s)
            if resp.status_code != 200:
                return None
            return resp.json()
        except (httpx.HTTPError, OSError, ValueError):
            return None

    async def _disagg_stream(self, pf: Replica, home: Replica, body: dict,
                             deadline: Deadline) -> dict | None:
        """Run one prefill-pool export and pump its KV frames into the
        decode home's stream adopter as they arrive (chunk-pipelined:
        early blocks install on the home while later chunks still prefill
        on ``pf``). Returns the FINAL adopt summary (``adopted_tokens``)
        or None on ANY failure — prefill death mid-stream, a torn tail, a
        refused adopt, budget overrun — and the caller's fallback is
        always the plain forward: clean-or-cold, never an error. The
        home-side adopter is zero-leak on every abort path (partial
        commit + LRU abandon, serve.handoff.StreamAdopter)."""
        import httpx

        from ..serve.handoff import frame_feed

        m = get_metrics()
        stream_id = new_trace_id()
        # the stream must leave room for the actual forward behind it: cap
        # it at 60% of the remaining budget — an overrun falls back and
        # the home still has >⅓ of the deadline to cold-prefill
        budget = max(0.05, deadline.remaining_s() * 0.6)
        t_end = time.monotonic() + budget
        payload = {"text": str(body.get("text") or ""),
                   "context": body.get("context") or {},
                   "session_id": body.get("session_id") or None,
                   "stream": stream_id,
                   "stream_blocks": self.disagg_stream_blocks}
        pf.inflight += 1
        self._disagg_inflight += 1
        m.set_gauge("disagg.prefill_queue", float(self._disagg_inflight))
        final_out: dict | None = None
        adopted_any = False
        try:
            async with self._http.stream(
                    "POST", pf.url + "/admin/disagg/prefill",
                    json=payload, timeout=budget) as resp:
                if resp.status_code != 200:
                    return None
                if "x-disagg-stream" not in resp.headers:
                    # shed before any segment (busy/no-slot/too-long):
                    # plain JSON body, nothing streamed, nothing to abort
                    await resp.aread()
                    return None
                buf = b""
                saw_final = False
                async for chunk in resp.aiter_bytes():
                    buf += chunk
                    frames, buf = frame_feed(buf)
                    for _seq, blob, final in frames:
                        if time.monotonic() > t_end:
                            return None
                        out = await self._adopt_one(home, stream_id, blob)
                        if out is None or not out.get("ok", False):
                            return None
                        adopted_any = True
                        m.inc("disagg.frames_streamed")
                        self._stream_win.append(
                            (time.monotonic(), int(out.get("blocks", 0))))
                        if final:
                            saw_final = True
                            final_out = out
                if buf or not saw_final:
                    return None  # torn tail / stream died before FINAL
        except (httpx.HTTPError, OSError, asyncio.TimeoutError):
            # prefill replica died mid-stream: transport evidence feeds
            # its breaker like any failed forward; the home keeps the
            # partial frontier its adopter already committed
            pf.breaker.record_failure()
            return None
        except ValueError:
            return None  # corrupt frame (bad magic/CRC): abort clean
        finally:
            # atomic-section: router.disagg-release -- the prefill member's inflight decrement and its drain-completion check must be one step, same contract as router.inflight-release
            pf.inflight -= 1
            self._maybe_finish_drain(pf)
            self._disagg_inflight -= 1
            m.set_gauge("disagg.prefill_queue", float(self._disagg_inflight))
            # end-atomic-section
            if adopted_any and final_out is None:
                # the stream died after segments landed: close the home's
                # adopter NOW with an end-of-stream abort — it commits the
                # partial frontier as ordinary warm cache and frees every
                # held block ref (zero-leak), instead of lingering in the
                # home's LRU until cap pressure evicts it
                try:
                    from ..serve.handoff import pack_kv_end
                    await self._adopt_one(
                        home, stream_id,
                        pack_kv_end(stream_id, {"ok": False,
                                                "aborted": True}))
                except Exception:
                    pass
        pf.breaker.record_success()
        adopted = int(final_out.get("adopted_tokens", 0) or 0)
        if adopted > 0:
            m.inc("disagg.tokens_prewarmed", float(adopted))
        return final_out

    def disagg_stats(self) -> dict:
        """The /health per-pool roll-up: member counts per role, the live
        export queue depth, and streamed KV blocks/s over a 30 s window
        (fleetview renders exactly this block)."""
        now = time.monotonic()
        while self._stream_win and now - self._stream_win[0][0] > 30.0:
            self._stream_win.popleft()
        blocks = sum(n for _, n in self._stream_win)
        pf = [r for r in self.replicas if r.role == "prefill"]
        dec = [r for r in self.replicas if r.role != "prefill"]
        return {
            "enabled": self.disagg,
            "min_tokens": self.disagg_min_tokens,
            "stream_blocks": self.disagg_stream_blocks,
            "prefill": {"total": len(pf),
                        "admitting": sum(1 for r in pf if r.admitting()),
                        "queue_depth": self._disagg_inflight,
                        "urls": [r.url for r in pf]},
            "decode": {"total": len(dec),
                       "admitting": sum(1 for r in dec if r.admitting())},
            "streamed_blocks_per_s": round(blocks / 30.0, 3),
        }

    async def forward_parse(self, raw: bytes, body: dict,
                            headers: dict) -> tuple:
        """The full /parse policy: route → (on a forced move, warm-state
        handoff) → (hedged) attempt → on transport failure, retry ONCE on
        the session's new home inside the original deadline (speculative
        parses are discarded instead — satellite 6).
        Returns (httpx response | None, served replica | None, error str)."""
        session_id = body.get("session_id") or None
        speculative = bool(body.get("speculative"))
        # prefix feed (ISSUE 19): best-effort cache warming. It follows
        # session affinity (the warmed chain must live on the session's
        # home) but is never hedged — a hedge would prefill a replica the
        # final will never visit — and never retried/replayed (below)
        feed = bool(body.get("prefix_feed"))
        deadline = (Deadline.from_headers(headers)
                    or Deadline.after(self.parse_timeout_s))
        idempotent = (speculative or not session_id) and not feed
        home, rehomed_from = self.route_ex(session_id)
        if home is None:
            return None, None, "no_replicas"
        if rehomed_from is not None and session_id:
            # drain/eject path of the warm handoff: the old home may still
            # be alive (drained, awaiting restart) — ship before forwarding
            # so the new home's very first turn admits against warm state
            await self._rehome_handoff(session_id, rehomed_from, home,
                                       deadline)
        if self.disagg:
            pf = self._pick_prefill(exclude={home.url})
            if pf is not None and speculative:
                # a speculative parse is throwaway work whose latency
                # nobody awaits: run it on the prefill pool, keeping its
                # decode burst off the latency-critical replicas — and its
                # prefill warms the pool's radix for the final's export.
                # Never replayed on failure: the 409 discard contract.
                get_metrics().inc("disagg.spec_routed")
                try:
                    resp = await self._guarded(
                        pf, raw, headers, deadline,
                        max(0.05, deadline.remaining_s()))
                    return resp, pf, None
                except ReplicaFailed:
                    get_metrics().inc("router.spec_discarded")
                    return None, None, "spec_discarded"
            if pf is not None and feed:
                # a prefix feed IS a prefill-only admission: export it on
                # the prefill pool and ship the chain to the session's
                # decode home, which is where the final will land warm
                out = await self._disagg_stream(pf, home, body, deadline)
                if out is not None:
                    import httpx

                    get_metrics().inc("disagg.feeds_routed")
                    resp = httpx.Response(200, json={
                        "prefix_feed": True, "ok": True, "disagg": True,
                        "adopted_tokens":
                            int(out.get("adopted_tokens", 0) or 0)})
                    return resp, home, None
                get_metrics().inc("disagg.fallbacks")
                # fall through: the home runs the feed locally, as before
            elif pf is not None and not speculative \
                    and self._uncached_estimate(session_id, body) \
                    >= self.disagg_min_tokens:
                # a long/cold admission: prefill it on the pool and stream
                # the KV in; whether or not the stream lands, the forward
                # below proceeds — warm on success, cold on fallback
                get_metrics().inc("disagg.admissions")
                if await self._disagg_stream(pf, home, body,
                                             deadline) is None:
                    get_metrics().inc("disagg.fallbacks")
        # a retry can only follow a non-speculative attempt with somewhere
        # else to go; cap the first attempt at half the remaining budget in
        # that case so the retry is guaranteed to fit (mid-flight ejection
        # usually fails over much faster than this cap)
        can_retry = (not speculative and not feed
                     and any(r.admitting() and r.url != home.url
                             for r in self.replicas))
        remaining = deadline.remaining_s()
        budget = remaining * 0.5 if can_retry else remaining
        try:
            resp, served, _hedged = await self._attempt(
                home, session_id, raw, headers, deadline,
                max(0.05, budget), idempotent)
            if self.disagg:
                self._note_session_tokens(session_id, served.url, resp)
            return resp, served, None
        except ReplicaFailed as e:
            if speculative:
                # satellite-6 bugfix: a speculative parse whose replica
                # died is DISCARDED, never replayed — the final re-routes
                # to the new home and parses fresh; replaying the spec
                # here could interleave with that re-routed final
                get_metrics().inc("router.spec_discarded")
                return None, None, "spec_discarded"
            if feed:
                # a feed whose home died is worthless on any other replica
                # (the warmed chain must live where the final will land) —
                # discard, never replay; the final just cold-prefills
                get_metrics().inc("router.feeds_discarded")
                return None, None, "feed_discarded"
            if deadline.expired:
                return None, None, f"deadline_expired: {e}"
            home2, rehomed2 = self.route_ex(session_id, exclude={home.url})
            if home2 is None:
                return None, None, "no_replicas"
            if rehomed2 is not None and session_id:
                # failover path of the warm handoff: the old home usually
                # just crashed, so the GET fails fast and the move counts
                # cold — but a hung-yet-alive donor can still ship
                await self._rehome_handoff(session_id, rehomed2, home2,
                                           deadline)
            get_metrics().inc("router.retries")
            try:
                resp, served, _h = await self._attempt(
                    home2, session_id, raw, headers, deadline,
                    max(0.05, deadline.remaining_s()), idempotent=False)
                if self.disagg:
                    self._note_session_tokens(session_id, served.url, resp)
                return resp, served, None
            except ReplicaFailed as e2:
                return None, None, f"retry_failed: {e2}"

    # ------------------------------------------------------------- fanout

    async def fan_out_get(self, path: str, query: str = "") -> dict:
        """GET ``path`` on every replica; per-replica bodies keyed by url
        (unreachable replicas report an ``error`` entry instead)."""
        import httpx

        async def one(r: Replica):
            try:
                resp = await self._http.get(
                    r.url + path + (f"?{query}" if query else ""),
                    timeout=self.probe_timeout_s)
                return r.url, resp.json()
            except (httpx.HTTPError, OSError, ValueError) as e:
                return r.url, {"error": f"{type(e).__name__}: {e}"}

        out = await asyncio.gather(*(one(r) for r in self.replicas))
        return dict(out)

    # ---------------------------------------------------------- lifecycle

    async def start(self) -> None:
        import httpx

        if self._http is None:
            self._http = httpx.AsyncClient()
        if self._probe_task is None:
            await self.probe_once()  # first routing decision sees real state
            self._probe_task = asyncio.ensure_future(self._probe_loop())

    async def stop(self) -> None:
        if self._probe_task is not None:
            self._probe_task.cancel()
            try:
                await self._probe_task
            except (asyncio.CancelledError, Exception):
                pass
            self._probe_task = None
        if self._http is not None:
            await self._http.aclose()
            self._http = None


# ------------------------------------------------------------------- app


def build_app(router: BrainRouter, tracer: Tracer | None = None) -> web.Application:
    tracer = tracer or Tracer("router", emit=False)
    app = web.Application(client_max_size=8 * 1024 * 1024)
    # a vanished caller must cancel the in-flight forward (aiohttp >= 3.9
    # opt-in): the cancellation crosses the router hop into the replica's
    # handler and from there evicts the decode slot (the PR 7 chain)
    from . import HANDLER_CANCELLATION

    app[HANDLER_CANCELLATION] = True
    slo = SLOTracker("router")

    async def on_startup(_app):
        await router.start()

    async def on_cleanup(_app):
        await router.stop()

    app.on_startup.append(on_startup)
    app.on_cleanup.append(on_cleanup)

    async def parse(req: web.Request) -> web.Response:
        t0 = time.perf_counter()
        resp = await _parse_inner(req)
        slo.record((time.perf_counter() - t0) * 1e3, ok=resp.status < 500)
        return resp

    async def _parse_inner(req: web.Request) -> web.Response:
        trace_id = req.headers.get("x-trace-id", new_trace_id())
        headers = {"x-trace-id": trace_id}
        raw = await req.read()
        try:
            body = json.loads(raw)
        except (json.JSONDecodeError, UnicodeDecodeError):
            return web.json_response(
                {"error": "invalid_request", "detail": "body must be JSON"},
                status=400, headers=headers)
        fwd_headers = dict(headers)
        if DEADLINE_HEADER in req.headers:
            fwd_headers[DEADLINE_HEADER] = req.headers[DEADLINE_HEADER]
        if "x-tenant" in req.headers:
            # tenant QoS tag (ISSUE 18): the body field rides the raw bytes
            # automatically; the header fallback must be forwarded by hand
            fwd_headers["x-tenant"] = req.headers["x-tenant"]
        with tracer.span("route_parse", trace_id=trace_id) as sp:
            resp, served, err = await router.forward_parse(
                raw, body if isinstance(body, dict) else {}, fwd_headers)
            if served is not None:
                sp.attrs["replica"] = served.url
            if err is not None:
                sp.attrs["error"] = err
        if resp is None:
            if err == "spec_discarded":
                # a speculative parse whose replica died: a SEMANTIC
                # answer, not dependency-health evidence — 409 so the
                # voice-side breaker/retry kit ignores it (the final is
                # about to re-route and parse fresh; burning breaker
                # budget on a lost optimization would open the circuit
                # exactly when the failover needs it closed)
                return web.json_response(
                    {"error": "speculation_discarded",
                     "detail": "home replica failed mid-speculation; "
                               "parse at final"},
                    status=409, headers=headers)
            if err == "feed_discarded":
                # same contract for a lost prefix feed (ISSUE 19): a lost
                # optimization, not an outage — 409 keeps the voice-side
                # breaker closed for the real parses that still work
                return web.json_response(
                    {"error": "prefix_feed_discarded",
                     "detail": "home replica failed mid-feed; "
                               "final will cold-prefill"},
                    status=409, headers=headers)
            # full outage / failed failover: the one 503 + Retry-After
            # shed contract — voice degrades to the rule parser and the
            # session survives
            return shed_response(
                "router",
                "no_replicas" if err == "no_replicas" else "replica_failed",
                headers=headers,
                retry_after_s=max(1.0, 2 * router.probe_s))
        out_headers = {k: v for k, v in resp.headers.items()
                       if k.lower() in _PASS_HEADERS}
        out_headers["x-trace-id"] = trace_id
        out_headers["x-router-replica"] = served.url
        out_headers["Content-Type"] = resp.headers.get(
            "Content-Type", "application/json")
        return web.Response(body=resp.content, status=resp.status_code,
                            headers=out_headers)

    async def health(_req: web.Request) -> web.Response:
        total = len(router.replicas)
        healthy = sum(1 for r in router.replicas if r.servable())
        draining = sum(1 for r in router.replicas if r.state == "draining")
        gray = sum(1 for r in router.replicas if r.gray)
        status = ("ok" if healthy == total
                  else "unhealthy" if healthy == 0 else "degraded")
        body = {
            "ok": healthy > 0, "service": "router", "status": status,
            "replicas": {"total": total, "healthy": healthy,
                         "draining": draining, "gray": gray},
            "replica_detail": [r.describe() for r in router.replicas],
            "slo": slo.state(),
        }
        if router.last_fleet is not None:
            body["fleet"] = router.last_fleet
        if router.disagg:
            # the per-pool roll-up (ISSUE 20): prefill vs decode member
            # counts, live export queue depth, streamed KV blocks/s —
            # fleetview's disagg line reads exactly this block
            body["disagg"] = router.disagg_stats()
        # the engine microscope rides along from a representative healthy
        # replica's last probe body, so the voice /health forward (and the
        # web HUD behind it) keeps its compile-sentinel / step-ledger / HBM
        # view when BRAIN_URL points at the router instead of one brain
        for r in router.replicas:
            if r.servable() and r.last_health:
                for k in ("compile_sentinel", "last_step", "hbm",
                          "quarantine", "quality"):
                    if r.last_health.get(k) is not None:
                        body[k] = r.last_health[k]
                body["home_replica"] = r.url
                break
        return web.json_response(body, status=200 if body["ok"] else 503)

    async def admin_drain(req: web.Request) -> web.Response:
        try:
            body = await req.json()
        except json.JSONDecodeError:
            body = {}
        target = body.get("replica")
        r = router._by_url.get(str(target).rstrip("/")) if target else None
        if r is None and isinstance(target, int) and \
                0 <= target < len(router.replicas):
            r = router.replicas[target]
        if r is None:
            return web.json_response(
                {"error": "unknown_replica", "detail": str(target),
                 "replicas": [x.url for x in router.replicas]}, status=404)
        started = router.start_drain(r)
        # forward the drain to the replica itself (best-effort): its serve
        # layer flips ColocatedServing.begin_drain so /health can report
        # drained once both lanes are empty
        import httpx

        try:
            await router._http.post(r.url + "/admin/drain",
                                    timeout=router.probe_timeout_s)
        except (httpx.HTTPError, OSError):
            pass
        return web.json_response({"ok": True, "replica": r.url,
                                  "state": r.state, "started": started})

    async def admin_admit(req: web.Request) -> web.Response:
        try:
            body = await req.json()
        except json.JSONDecodeError:
            body = {}
        r = router._by_url.get(str(body.get("replica", "")).rstrip("/"))
        if r is None:
            return web.json_response({"error": "unknown_replica"}, status=404)
        router.admit(r)
        return web.json_response({"ok": True, "replica": r.url,
                                  "state": r.state})

    async def admin_autopilot(_req: web.Request) -> web.Response:
        """The autopilot's control-loop state (ISSUE 16): target vs actual
        per tier plus the decision log — the fleetview panel and the bench
        assertions read this one surface. The controller registers itself
        on the router object (``router.autopilot``); without one the
        endpoint answers 404 so a static fleet scrapes nothing stale."""
        ap = getattr(router, "autopilot", None)
        if ap is None:
            return web.json_response(
                {"enabled": False, "detail": "no autopilot attached"},
                status=404)
        return web.json_response(ap.describe())

    def fan_out(path: str):
        async def handler(req: web.Request) -> web.Response:
            return web.json_response({
                "service": "router",
                "replicas": await router.fan_out_get(
                    path.format(**req.match_info), req.query_string),
            })

        return handler

    app.router.add_post("/parse", parse)
    app.router.add_get("/health", health)
    app.router.add_post("/admin/drain", admin_drain)
    app.router.add_post("/admin/admit", admin_admit)
    app.router.add_get("/admin/autopilot", admin_autopilot)
    from ..utils.tracing import make_metrics_handler, make_trace_handler

    app.router.add_get("/metrics", make_metrics_handler("router", tracer,
                                                        slo=slo))
    # the router's OWN trace ring (route_parse spans) lives at /debug/trace
    # like every other service; the replica fan-outs live under
    # /debug/replicas/* so traceview can merge either view
    app.router.add_get("/debug/trace/{trace_id}",
                       make_trace_handler("router", tracer))
    app.router.add_get("/debug/replicas/trace/{trace_id}",
                       fan_out("/debug/trace/{trace_id}"))
    app.router.add_get("/debug/replicas/steplog", fan_out("/debug/steplog"))
    app.router.add_get("/debug/replicas/timeseries",
                       fan_out("/debug/timeseries"))
    # the quality observatory fan-out (ISSUE 15): each replica's windowed
    # quality state, so "which replica is wrong" is one scrape
    app.router.add_get("/debug/replicas/quality", fan_out("/debug/quality"))
    # the cost observatory fan-out (ISSUE 17): each replica's engine meter
    # + per-session attribution, so "who is burning the fleet" is one scrape
    app.router.add_get("/debug/replicas/costs", fan_out("/debug/costs"))

    async def replicas_flight(req: web.Request) -> web.Response:
        """The flight-recorder fan-out, with each member's dump annotated
        with the router's latest wall-clock-skew estimate for it — every
        service's dump timestamps are its own wall clock, and the skew is
        what lets ``traceview --flight`` merge multi-service dumps onto
        ONE timeline (ISSUE 14 satellite)."""
        bodies = await router.fan_out_get("/debug/flightrecorder",
                                          req.query_string)
        for r in router.replicas:
            body = bodies.get(r.url)
            if isinstance(body, dict):
                body["clock_skew_s"] = round(r.clock_skew_s, 6)
        return web.json_response({"service": "router", "replicas": bodies})

    app.router.add_get("/debug/replicas/flightrecorder", replicas_flight)
    from ..utils.timeseries import attach_timeseries
    from ..utils.tracing import make_flightrecorder_handler

    app.router.add_get("/debug/flightrecorder",
                       make_flightrecorder_handler("router"))
    attach_timeseries(app, "router", tracer)
    return app


def replicas_from_env() -> list[str]:
    spec = os.environ.get("BRAIN_REPLICAS", "")
    return [u.strip() for u in spec.split(",") if u.strip()]


def main() -> None:
    load_env_cascade()
    urls = replicas_from_env()
    if not urls:
        raise SystemExit("BRAIN_REPLICAS=url,url,... is required")
    port = int(os.environ.get("ROUTER_PORT", "8095"))
    app = build_app(BrainRouter(urls), Tracer("router"))
    web.run_app(app, port=port, handler_cancellation=True)


if __name__ == "__main__":
    main()
