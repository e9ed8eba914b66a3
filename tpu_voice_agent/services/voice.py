"""Voice orchestrator: WS /stream — audio in, typed events out.

Capability parity with the reference voice service (apps/voice/src/server.ts:
60-304): binary WS frames carry PCM16 @ 16 kHz mono; JSON frames carry
control messages; the server emits the same typed event vocabulary —
``transcript_partial/transcript_final/intent/tts/execution_result/
execution_error/confirmation_required/info/warn/error``. What changed:

- Deepgram (deepgram.ts) -> in-tree streaming Whisper (serve.stt); the
  null-STT mode mirrors the reference's null-API-key passthrough
- the fixed 1 s final-transcript debounce (server.ts:229) -> energy
  endpointing inside StreamingSTT (SURVEY.md §6's biggest latency constant)
- safety gating: intents that are risky (requires_confirmation or the
  server-side floor, schemas.RISKY_INTENT_TYPES) emit confirmation_required;
  safe intents auto-execute against the executor, and the returned
  session_id is threaded into subsequent executions (server.ts:173-211)
"""

from __future__ import annotations

import asyncio
import json
import os
import threading
import time

import httpx
import numpy as np
from aiohttp import WSMsgType, web

from ..audio.mel import pcm16_to_float
from ..schemas import Intent, ParseResponse
from ..utils import SLOTracker, Tracer, get_metrics, load_env_cascade, new_trace_id
from ..utils.resilience import (
    BreakerOpenError,
    CircuitBreaker,
    Deadline,
    ResilienceError,
    RetryPolicy,
    post_with_resilience,
)


class VoiceConfig:
    def __init__(
        self,
        brain_url: str | None = None,
        executor_url: str | None = None,
        stt_factory=None,
        parse_timeout_s: float | None = None,
        exec_timeout_s: float | None = None,
        retry_attempts: int | None = None,
        breaker_threshold: int | None = None,
        breaker_reset_s: float | None = None,
    ):
        self.brain_url = brain_url or os.environ.get("BRAIN_URL", "http://127.0.0.1:8090")
        self.executor_url = executor_url or os.environ.get("EXECUTOR_URL", "http://127.0.0.1:7081")
        self.stt_factory = stt_factory or stt_factory_from_env()
        # per-hop time budgets (the old hardcoded 60/120 s stay the defaults);
        # each budget is the WHOLE deadline for that hop — retries included —
        # and propagates downstream via the x-deadline-ms header
        self.parse_timeout_s = parse_timeout_s if parse_timeout_s is not None \
            else float(os.environ.get("VOICE_PARSE_TIMEOUT_S", "60"))
        self.exec_timeout_s = exec_timeout_s if exec_timeout_s is not None \
            else float(os.environ.get("VOICE_EXEC_TIMEOUT_S", "120"))
        # resilience knobs (shared by the brain and executor hops)
        self.retry_attempts = retry_attempts if retry_attempts is not None \
            else int(os.environ.get("VOICE_RETRY_ATTEMPTS", "3"))
        self.breaker_threshold = breaker_threshold if breaker_threshold is not None \
            else int(os.environ.get("VOICE_BREAKER_THRESHOLD", "3"))
        self.breaker_reset_s = breaker_reset_s if breaker_reset_s is not None \
            else float(os.environ.get("VOICE_BREAKER_RESET_S", "2.0"))


def stt_factory_from_env():
    """VOICE_STT=null (default, no model), whisper:<preset> (random init),
    whisper-hf:<checkpoint dir> (real weights + real tokenizer), or
    whisper-ckpt:<dir> (an in-tree trained checkpoint from
    train.distill — e.g. checkpoints/whisper-tiny-heldout — for the
    zero-egress neural pipeline, VERDICT round-4 next #5)."""
    spec = os.environ.get("VOICE_STT", "null")
    if spec == "null":
        from ..serve.stt import NullSTT

        return lambda: NullSTT()
    if spec.startswith("whisper"):
        from ..audio.endpoint import EnergyEndpointer
        from ..serve.stt import SpeechEngine, StreamingSTT

        if spec.startswith("whisper-hf:"):
            engine = SpeechEngine.from_hf(spec.split(":", 1)[1])
        elif spec.startswith("whisper-ckpt:"):
            from ..models.whisper import WhisperConfig
            from ..train import distill

            path = spec.split(":", 1)[1]
            loaded = distill.load_ckpt_path(path, WhisperConfig)
            if loaded is None:
                raise ValueError(f"no trained whisper checkpoint at {path} "
                                 "(run python -m tpu_voice_agent.train.make_tiny_ckpts)")
            engine = distill.whisper_engine_from(*loaded)
        else:
            preset = spec.split(":", 1)[1] if ":" in spec else "whisper-tiny"
            engine = SpeechEngine(preset=preset)
        lock = threading.Lock()

        # adaptive endpointing knobs (same tuning as bench.py; see the
        # StreamingSTT docstring for the stability/hysteresis design):
        # VOICE_SPEC_SILENCE_MS — silence before the speculative final
        #   fires (default 120: on the web client's 60 ms frame boundary);
        # VOICE_EARLY_CLOSE_MS — stable-silence floor for the adaptive
        #   early close once the speculative parse lands grammar-complete
        #   (default 240; 0 disables and restores the fixed window).
        spec_ms = int(os.environ.get("VOICE_SPEC_SILENCE_MS", "120"))
        early_ms = float(os.environ.get("VOICE_EARLY_CLOSE_MS", "240"))

        def make_endpointer():
            return EnergyEndpointer(sample_rate=engine.mel_cfg.sample_rate,
                                    spec_silence_ms=spec_ms)

        # multi-stream batched serving plane (STT_BATCH_ENABLE=1): ONE
        # process-wide engine + batcher multiplexes every connection's
        # transcription work into batched dispatches (docs/PERF.md
        # "Multi-stream STT batching"); STT_BATCH_SLOTS bounds concurrent
        # decode width. STT_REPLICAS>1 (ISSUE 13) runs N batcher replicas
        # over the one loaded engine behind the connection-affine replica
        # tier (serve.stt_replicas): a wedged/crashed Whisper worker is
        # warm-restarted and failed over instead of taking every live
        # microphone down. Unset keeps the historical per-connection path
        # (shared engine, one lock, B=1 dispatches) byte-identical.
        if os.environ.get("STT_BATCH_ENABLE", "") == "1":
            from ..serve.stt_batch import BatchedStreamingSTT, STTBatcher

            slots = int(os.environ.get("STT_BATCH_SLOTS", "4"))
            n_replicas = int(os.environ.get("STT_REPLICAS", "1"))
            if n_replicas > 1:
                from ..serve.stt_replicas import STTReplicaTier

                batcher = STTReplicaTier(engine, replicas=n_replicas,
                                         slots=slots)
            else:
                batcher = STTBatcher(engine, slots=slots)
            def batched_factory():
                return BatchedStreamingSTT(
                    engine, batcher,
                    endpointer=make_endpointer(),
                    early_close_ms=early_ms if early_ms > 0 else None,
                )

            batched_factory.warmup = engine.warmup
            return batched_factory

        class LockedStreaming(StreamingSTT):
            def feed(self, samples):
                with lock:
                    return super().feed(samples)

        def factory():
            return LockedStreaming(
                engine,
                endpointer=make_endpointer(),
                early_close_ms=early_ms if early_ms > 0 else None,
            )

        # services.warm_up(factory) compiles the engine's programs before
        # the service listens
        factory.warmup = engine.warmup
        return factory
    raise ValueError(f"unknown VOICE_STT {spec!r}")


class ClientState:
    def __init__(self, stt):
        self.stt = stt
        self.context: dict = {}
        self.session_id: str | None = None
        # stable per-connection conversation key for /parse: the executor's
        # session_id above only exists after the first /execute, and a
        # session-keyed brain backend (PlannerParser) must never see turn 1
        # under one key and turn 2 under another — or, worse, share a
        # default key across clients
        self.convo_id = new_trace_id()
        # per-UTTERANCE trace id (rotated when a new utterance starts) so
        # /debug/trace assembles one utterance's waterfall, not a whole
        # connection's history under a single id
        self.trace_id = new_trace_id()
        # per-utterance stage accounting for the latency_budget event:
        # utt_t0 = perf_counter at the utterance's first audio frame;
        # stages = the split dict accumulated capture -> final -> parse
        self.utt_t0: float | None = None
        self.stages: dict = {}
        # perf_counter at the start of any utterance whose SLO sample has
        # not been recorded yet (speech onset OR typed command); cleared
        # wherever slo.record runs. A connection torn down while this is
        # set aborted an utterance mid-flight — that must cost SLO error
        # budget, not silently vanish (swarm churn would otherwise inflate
        # the capacity verdict)
        self.slo_open_t0: float | None = None
        # trace id of the utterance whose risky plan awaits confirmation:
        # the user's confirm click arrives AFTER later audio frames have
        # rotated trace_id, and the confirmed execution belongs to the
        # utterance that proposed it, not whatever is being spoken now
        self.confirm_trace_id: str | None = None
        # serializes executor calls per client so the first execution's
        # session_id is threaded into the next (back-to-back commands must
        # share one browser session)
        self.exec_lock = asyncio.Lock()
        # in-flight speculative parse: (provisional transcript, task). Set
        # when STT emits spec_final (speaker paused, endpoint not yet
        # confirmed); consumed by the matching transcript_final, dropped by
        # anything that changes what the final parse would see (new spec
        # text, context_update, reset)
        self.spec: tuple[str, asyncio.Task] | None = None
        # tenant QoS tag (ISSUE 18): set by the `tenant` control frame (or
        # a context_update carrying one) and dealt into every /parse this
        # connection makes, plus the STT batcher's fair lanes. None = the
        # default class.
        self.tenant: str | None = None
        # incremental streaming prefill (ISSUE 19, PREFIX_FEED_ENABLE=1):
        # the stability tracker over STT partials (attached by the stream
        # handler when the knob is on) plus the single in-flight feed task.
        # At most ONE feed per connection is ever in flight; a newer
        # committed prefix supersedes a queued one (feed_pending).
        self.feed_tracker = None
        self.feed_task: asyncio.Task | None = None
        self.feed_pending: str | None = None

    def drop_spec(self) -> None:
        if self.spec is not None:
            task = self.spec[1]
            self.spec = None
            _reap(task)

    def drop_feed(self) -> None:
        """Reap the in-flight prefix feed (ISSUE 19 satellite): WS
        teardown / reset / context change cancels the feed task, and the
        cancellation rides the PR 7 RequestContext chain into the brain —
        a not-yet-admitted feed is dropped there; one already prefilling
        completes and its chain stays as plain reusable cache (nothing
        holds a slot or a refcount past the call)."""
        if self.feed_pending is not None:
            self.feed_pending = None
        if self.feed_task is not None:
            task = self.feed_task
            self.feed_task = None
            _reap(task)
            get_metrics().inc("voice.feeds_reaped")


def _feed_now(feed, samples, t_recv: float):
    """Runs where the STT's ``feed`` runs (an executor thread, or inline on
    the batched plane): receipt of the frame -> here is the histogram
    ``voice.stt_feed_lag``, the wait for a thread that ``stt.feed_lag_s``
    (audio buffered behind the model) does not see."""
    get_metrics().observe_ms("voice.stt_feed_lag", (time.perf_counter() - t_recv) * 1e3)
    return feed(samples)


def _reap(task: "asyncio.Task") -> None:
    """Cancel/abandon a speculative task without 'Task exception was never
    retrieved' ERROR-log spam on GC: a dropped speculation's failure is
    expected and must be swallowed, not surfaced."""
    if task.done():
        if not task.cancelled():
            task.exception()
    else:
        task.add_done_callback(lambda t: t.cancelled() or t.exception())
        task.cancel()


class _PrefixFeedTracker:
    """Longest-stable-prefix commit over a stream of STT partials
    (ISSUE 19). ``observe(partial)`` returns the newly committable prefix,
    or None when nothing new stabilized. A prefix commits once it has
    survived K consecutive partials character-identically, trimmed back to
    the last whitespace boundary (a mid-word prefix tokenizes differently
    from the final's full word, wasting the fed KV), and only when it grew
    by >= min_chars since the last commit (each commit costs a /parse
    roundtrip + a prefill-only admission). A RETRACTION — STT revising
    text already committed — resets the baseline: the fed chain stays in
    the radix tree as cache for whatever prefix still matches, and the
    re-stabilized transcript simply re-commits; the brain-side radix match
    falls back to the longest still-valid cached prefix token-identically.
    """

    def __init__(self, k: int = 3, min_chars: int = 8):
        self.k = max(1, int(k))
        self.min_chars = max(1, int(min_chars))
        self._recent: list[str] = []
        self.committed = ""

    def observe(self, partial: str) -> str | None:
        self._recent.append(partial)
        if len(self._recent) > self.k:
            self._recent.pop(0)
        if len(self._recent) < self.k:
            return None
        stable = self._recent[0]
        for p in self._recent[1:]:
            n = min(len(stable), len(p))
            i = 0
            while i < n and stable[i] == p[i]:
                i += 1
            stable = stable[:i]
        # word-boundary trim: a prefix the NEWEST partial continues without
        # a space ends mid-word — drop the fragment (it would tokenize
        # differently from the final's full word). One the newest partial
        # follows with whitespace (or ends at) is word-complete as-is.
        latest = self._recent[-1]
        if (len(stable) < len(latest) and not latest[len(stable)].isspace()
                and not stable[-1:].isspace()):
            cut = stable.rfind(" ")
            if cut <= 0:
                return None
            stable = stable[:cut]
        stable = stable.rstrip()
        if not stable:
            return None
        if not stable.startswith(self.committed):
            self.committed = ""  # retraction: re-baseline, see docstring
        if len(stable) - len(self.committed) < self.min_chars:
            return None
        self.committed = stable
        return stable

    def reset(self) -> None:
        self._recent.clear()
        self.committed = ""


def _prefill_remaining(stages: dict, spec_pre_parsed: bool,
                       degraded: bool) -> float:
    """Outstanding un-prefilled prompt tokens when the endpoint fired —
    the scoreboard ISSUE 19 gates on, computed for EVERY utterance:
    a speculative parse that finished before the endpoint left nothing
    outstanding (0); an engine parse reports prompt_tokens minus whatever
    the KV cache absorbed; a degraded/headerless parse (rule fallback,
    planner backend) had no engine prefill pending at the endpoint by
    definition (0, not unrecorded — the old gauge skipped exactly the
    cold utterances this measurement exists to expose)."""
    if spec_pre_parsed:
        return 0.0
    pt = stages.get("prompt_tokens")
    if degraded or pt is None:
        return 0.0
    return max(0.0, float(pt) - float(stages.get("cached_tokens", 0.0)))


def build_app(cfg: VoiceConfig | None = None, tracer: Tracer | None = None) -> web.Application:
    cfg = cfg or VoiceConfig()
    tracer = tracer or Tracer("voice", emit=False)
    app = web.Application()
    # abrupt WS teardown must cancel the stream handler mid-await (aiohttp
    # >= 3.9 opt-in): that cancellation aborts the in-flight /parse httpx
    # call, which cancels the brain handler, which evicts the decode slot —
    # the full disconnect -> mid-decode-cancellation chain (ISSUE 7). The
    # teardown finallys (abort SLO sample, STT close) run either way.
    from . import HANDLER_CANCELLATION

    app[HANDLER_CANCELLATION] = True

    # per-dependency circuits, shared across WS connections: one client's
    # timeouts must warn the next client's calls. An open brain circuit is
    # NOT terminal — handle_final degrades to the local rule-based parser
    # and the half-open probe re-discovers a recovered brain automatically.
    brain_breaker = CircuitBreaker(
        "brain", failure_threshold=cfg.breaker_threshold,
        reset_after_s=cfg.breaker_reset_s)
    exec_breaker = CircuitBreaker(
        "executor", failure_threshold=cfg.breaker_threshold,
        reset_after_s=cfg.breaker_reset_s)
    retry_policy = RetryPolicy(max_attempts=max(1, cfg.retry_attempts))
    # the degraded-mode parser: zero model deps, same intent vocabulary —
    # a brain outage downgrades parse quality instead of dropping sessions
    from .brain import RuleBasedParser

    fallback_parser = RuleBasedParser()
    # the north-star SLO: voice->intent (end-of-speech processing cost —
    # STT finalize + parse; the speaker's own talking time is not latency)
    slo = SLOTracker("voice")
    # quality observatory (ISSUE 15): STT confidence per final transcript,
    # degraded-parse structure, and the voice-side quality-SLO verdict
    # (tracer-local registry: per-process in production, per-app in the
    # in-process harnesses)
    from ..utils.quality import QualityMonitor, make_quality_handler

    qmon = QualityMonitor("voice", metrics=tracer.metrics)
    # live WS session count + the measured capacity ceiling (the swarm
    # bench's max-sessions-at-SLO number, operator-pinned): the web HUD
    # renders occupancy/headroom from /health
    live_sessions = {"n": 0}
    capacity_sessions = int(os.environ.get("VOICE_CAPACITY_SESSIONS", "0"))
    get_metrics().set_gauge("voice.live_sessions", 0)

    # engine-microscope forward (ISSUE 9): the web HUD polls voice /health
    # only, so the brain's compile-sentinel verdict (post-fence recompiles
    # = the alertable shape-churn event), its last step-ledger entry, and
    # the live HBM gauges ride along — refreshed in the BACKGROUND at most
    # every VOICE_BRAIN_HEALTH_S seconds (fetch budgeted to 1 s), so a slow
    # or overloaded brain costs this handler staleness, never latency.
    # Only the very first scrape awaits the fetch (nothing cached yet).
    brain_fwd = {"t": 0.0, "body": None, "task": None, "fetched": False}
    brain_fwd_s = float(os.environ.get("VOICE_BRAIN_HEALTH_S", "3.0"))

    async def _refresh_brain_fwd() -> None:
        try:
            async with httpx.AsyncClient(timeout=1.0) as http:
                r = await http.get(cfg.brain_url + "/health")
                h = r.json()
            # the router's aggregated shape (ISSUE 10) forwards alongside
            # the single-brain microscope keys: ``replicas`` {total,
            # healthy, draining} drives the HUD's red replica badge, and
            # the engine/compile-sentinel block the router lifted from a
            # healthy home replica keeps the engine line rendering when
            # BRAIN_URL points at the tier instead of one process
            brain_fwd["body"] = {
                k: h[k] for k in ("compile_sentinel", "last_step", "hbm",
                                  "replicas", "home_replica", "quality")
                if h.get(k) is not None
            } or None
        except Exception:
            brain_fwd["body"] = None
        finally:
            brain_fwd["fetched"] = True
            brain_fwd["task"] = None

    async def _brain_engine_health() -> dict | None:
        now = time.monotonic()
        if now - brain_fwd["t"] >= brain_fwd_s and brain_fwd["task"] is None:
            brain_fwd["t"] = now
            brain_fwd["task"] = asyncio.create_task(_refresh_brain_fwd())
            if not brain_fwd["fetched"]:
                await brain_fwd["task"]
        return brain_fwd["body"]

    async def health(_req: web.Request) -> web.Response:
        breakers = {"brain": brain_breaker.state, "executor": exec_breaker.state}
        status = "ok" if all(s == "closed" for s in breakers.values()) else "degraded"
        body = {
            "ok": status == "ok", "status": status, "service": "voice",
            "breakers": breakers,
            "slo": slo.state(),
            "sessions": live_sessions["n"],
            "capacity_sessions": capacity_sessions,
            # the voice-side quality block (STT confidence windows +
            # quality-SLO verdict); the brain's own block rides the
            # ``brain`` forward below — the HUD badge reads both
            "quality": qmon.health(),
        }
        fwd = await _brain_engine_health()
        if fwd is not None:
            body["brain"] = fwd
        # the STT replica ring (ISSUE 13): healthy/total (+draining) for
        # the HUD's STT badge, beside the brain replica badge it mirrors
        from ..serve.stt_replicas import current_tier

        tier = current_tier()
        if tier is not None:
            body["stt_replicas"] = tier.tier_health()
        # degraded still serves (that is the point) — 200 either way
        return web.json_response(body)

    async def send(ws: web.WebSocketResponse, type_: str, **payload) -> None:
        if not ws.closed:
            await ws.send_json({"type": type_, **payload})

    async def post_parse(state: ClientState, text: str, http,
                         speculative: bool = False, deadline: Deadline | None = None):
        """One budgeted /parse roundtrip (no events, no side effects —
        callable speculatively). Returns the httpx response; raises
        BreakerOpenError/DeadlineExpired/transport errors."""
        json_body = {"text": text, "session_id": state.convo_id,
                     "context": state.context, "speculative": speculative}
        headers = {"x-trace-id": state.trace_id}
        if state.tenant:
            # tenant QoS tag (ISSUE 18): body field for the brain, header
            # for router placement — both only when the client set one
            json_body["tenant"] = state.tenant
            headers["x-tenant"] = state.tenant
        return await post_with_resilience(
            http, cfg.brain_url + "/parse",
            json_body=json_body,
            headers=headers,
            deadline=deadline or Deadline.after(cfg.parse_timeout_s),
            policy=retry_policy,
            breaker=brain_breaker,
        )

    # sticky across the app: a 409 with the specific speculation_unsupported
    # error body means the brain backend is session-keyed — every
    # speculative request would be refused, so stop paying a wasted
    # roundtrip per utterance. The latch is NOT permanent: after
    # RESPEC_AFTER skipped utterances one speculation re-probes, so a brain
    # restarted into a speculation-capable backend recovers without a voice
    # restart (round-4 advisor finding). Any OTHER 409 (proxy, transient)
    # never latches.
    RESPEC_AFTER = int(os.environ.get("VOICE_RESPEC_AFTER", "25"))
    spec_supported = {"ok": True, "skips": 0}

    # incremental streaming prefill (ISSUE 19, PREFIX_FEED_ENABLE=1):
    # stream stabilized partial prefixes to the brain as prefill-only
    # feeds WHILE the user is still speaking, so the endpoint fires
    # against an already-warm radix chain and the gauge above reads ~0
    # even for cold (non-speculative) utterances. Unset keeps every
    # touched path byte-identical: no tracker, no tasks, no requests.
    feed_enable = os.environ.get("PREFIX_FEED_ENABLE", "") == "1"
    feed_k = int(os.environ.get("PREFIX_FEED_STABLE_K", "3"))
    feed_min_chars = int(os.environ.get("PREFIX_FEED_MIN_CHARS", "8"))
    # sticky across the app like spec_supported, but with no re-probe: a
    # backend that answered prefix_feed_unsupported will not grow a
    # prefill-only admission path mid-run
    feed_supported = {"ok": True}
    if feed_enable:
        get_metrics().inc("voice.feeds_sent", 0.0)
        get_metrics().inc("voice.feeds_reaped", 0.0)

    async def feed_prefix_send(state: ClientState, text: str, http) -> None:
        """Fire one coalesced prefill-only feed. Deliberately a raw post,
        NOT post_with_resilience: a feed is a lost optimization on any
        failure — it must never retry, never burn the brain breaker's
        budget (that budget belongs to the real parses), and never surface
        an error to the user. It still refuses to fire while the circuit
        is anything but closed: a struggling brain gets real work only."""
        if not feed_enable or not feed_supported["ok"]:
            return
        if brain_breaker.state != "closed":
            return
        if state.feed_task is not None:
            state.feed_pending = text  # coalesce: newest commit wins
            return

        async def run(text: str) -> None:
            json_body = {"text": text, "session_id": state.convo_id,
                         "context": state.context, "prefix_feed": True}
            headers = {"x-trace-id": state.trace_id}
            if state.tenant:
                json_body["tenant"] = state.tenant
                headers["x-tenant"] = state.tenant
            get_metrics().inc("voice.feeds_sent")
            try:
                r = await http.post(cfg.brain_url + "/parse", json=json_body,
                                    headers=headers,
                                    timeout=cfg.parse_timeout_s)
                if r.status_code == 409:
                    # only the brain's own refusal latches; the router's
                    # feed_discarded 409 (home died mid-feed) is transient
                    try:
                        latch = (r.json().get("error")
                                 == "prefix_feed_unsupported")
                    except Exception:
                        latch = False
                    if latch:
                        feed_supported["ok"] = False
            except asyncio.CancelledError:
                raise
            except (httpx.HTTPError, OSError, RuntimeError):
                pass  # best-effort: the final will just cold-prefill
            finally:
                if state.feed_task is asyncio.current_task():
                    state.feed_task = None
                # chain the coalesced commit (drop_feed cleared it if the
                # connection is tearing down, so a cancelled feed never
                # respawns)
                nxt, state.feed_pending = state.feed_pending, None
                if nxt is not None:
                    await feed_prefix_send(state, nxt, http)

        state.feed_task = asyncio.ensure_future(run(text))

    async def speculate(state: ClientState, text: str, http) -> None:
        """Start parsing the provisional transcript inside the endpoint's
        trailing-silence window (VERDICT round-3 next #3). The result is
        only ever DELIVERED by a matching transcript_final — nothing is
        emitted or executed from here, so the risky-intent confirmation
        gate is untouched; a mismatched final discards the work."""
        if not spec_supported["ok"]:
            # the skip counter advances per UTTERANCE (handle_final), not
            # here: with the eager spec threshold a single utterance can
            # fire several spec_final events and would burn through the
            # re-probe budget in a couple of commands
            return
        if brain_breaker.state != "closed":
            # a tripped (or probing) brain circuit must not spend its
            # half-open probe on speculative work — the final's parse is
            # the probe that matters, and it has a local fallback
            return
        if state.spec is not None and state.spec[0] == text:
            return  # already in flight for this exact transcript
        state.drop_spec()

        async def run():
            r = await post_parse(state, text, http, speculative=True)
            if r.status_code == 409:
                # flip the sticky flag HERE, not only on the consumed-hit
                # path: a speculation superseded by a different final is
                # reaped without inspection, and against a session-keyed
                # brain every utterance would otherwise keep paying the
                # wasted roundtrip. Only the brain's own refusal latches;
                # a transient 409 from anything else just loses this one.
                try:
                    latch = r.json().get("error") == "speculation_unsupported"
                except Exception:
                    latch = False
                if latch:
                    spec_supported["ok"] = False
                    spec_supported["skips"] = 0
            elif r.status_code == 200:
                # grammar-complete speculative parse: let the streaming STT
                # close the endpoint window early once the transcript has
                # also stayed stable (adaptive endpointing — the fixed
                # window was 97% of the measured e2e). feed() re-validates
                # everything; a stale notification is inert.
                notify = getattr(state.stt, "parse_complete", None)
                if notify is not None:
                    notify(text)
            return r

        get_metrics().inc("voice.spec_parse_started")
        state.spec = (text, asyncio.ensure_future(run()))

    async def emit_budget(ws, state: ClientState, stages: dict | None = None) -> None:
        """The per-utterance latency_budget event: the stage-split dict the
        web HUD renders next to the degraded badge. total_ms is the
        voice->intent(+execute) PROCESSING cost — audio_ingest_ms (which
        includes the speaker's own talking time) is reported but not
        summed."""
        stages = dict(stages if stages is not None else state.stages)
        stages["total_ms"] = round(sum(
            stages.get(k, 0.0)
            for k in ("stt_finalize_ms", "parse_ms", "execute_ms")), 3)
        await send(ws, "latency_budget", trace_id=stages.pop("trace_id", state.trace_id),
                   stages=stages)

    async def handle_final(ws, state: ClientState, text: str, http: httpx.AsyncClient) -> None:
        """transcript final -> brain -> gate -> executor (the hot path)."""
        t_final0 = time.perf_counter()
        if not spec_supported["ok"]:
            # one skipped UTTERANCE per final; after RESPEC_AFTER of them
            # the next utterance re-probes speculation (a brain restarted
            # into a speculation-capable backend recovers without a voice
            # restart — round-4 advisor finding)
            spec_supported["skips"] += 1
            if spec_supported["skips"] > RESPEC_AFTER:
                spec_supported["ok"] = True
                spec_supported["skips"] = 0
        r = None
        # True when the parse finished (or was fully decoded server-side)
        # BEFORE the endpoint fired — the case where the prompt's prefill
        # cost left the endpoint->intent path entirely (the gauge below)
        spec_pre_parsed = False
        spec, state.spec = state.spec, None
        if spec is not None:
            stext, task = spec
            if stext == text:
                # hit: the parse has been running since the speaker paused —
                # usually it is already done and this await is free.
                # done-ness is captured BEFORE the await: a spec parse still
                # mid-prefill when the endpoint fired must NOT report 0
                # outstanding prefill below (the await would always finish
                # by the time the flag is read, biasing the gauge to 0)
                was_done_at_endpoint = task.done()
                try:
                    maybe = await task
                except asyncio.CancelledError:
                    if not task.cancelled():
                        raise  # WE were cancelled, not the spec task
                    maybe = None
                except Exception:
                    maybe = None
                if (maybe is not None and maybe.status_code == 200
                        and maybe.headers.get("x-speculation-pending") == "1"):
                    # two-phase backend: the speculative turn is PENDING on
                    # the server session — fall through to the normal parse,
                    # which COMMITS it (zero decode, the cached plan comes
                    # back; one local roundtrip, no model latency). Using
                    # the speculative body directly would leave the pending
                    # marker set and the NEXT turn would roll back a plan
                    # we already delivered.
                    get_metrics().inc("voice.spec_parse_hit")
                    get_metrics().inc("voice.spec_parse_commit")
                    spec_pre_parsed = was_done_at_endpoint
                elif maybe is not None and maybe.status_code == 200:
                    r = maybe
                    get_metrics().inc("voice.spec_parse_hit")
                    spec_pre_parsed = was_done_at_endpoint
                elif maybe is not None and maybe.status_code == 409:
                    # stateful backend refused speculation (run() already
                    # flipped the sticky flag); parse normally
                    get_metrics().inc("voice.spec_parse_unsupported")
                else:
                    get_metrics().inc("voice.spec_parse_failed")
            else:
                _reap(task)
                get_metrics().inc("voice.spec_parse_stale")
        degraded_reason = None
        if r is None:
            with tracer.span("parse_roundtrip", trace_id=state.trace_id, chars=len(text)):
                try:
                    r = await post_parse(state, text, http)
                except asyncio.CancelledError:
                    # connection teardown mid-parse is not a brain fault —
                    # it must unwind the handler, not masquerade as
                    # "brain unreachable"
                    raise
                except (ResilienceError, httpx.HTTPError, OSError) as e:
                    degraded_reason = (f"circuit open" if isinstance(e, BreakerOpenError)
                                       else f"{type(e).__name__}: {e}")
        if degraded_reason is None and r.status_code >= 500:
            # the brain shed this request (503: overload / expired deadline)
            # or failed server-side (500: engine crash, llm_error): a local
            # degraded parse beats surfacing a terminal error either way.
            # 4xx stays terminal — those are semantic answers about THIS
            # request, not brain-health signals.
            degraded_reason = f"brain error {r.status_code}"
        if degraded_reason is not None:
            # graceful degradation: the session survives a dead or drowning
            # brain on the local rule-based parser; every event from this
            # utterance is tagged so the UI can show reduced quality, and
            # the breaker's half-open probe restores full parsing without
            # operator action
            get_metrics().inc("voice.degraded_parses")
            parsed = fallback_parser.parse(text, state.context)
            degraded = True
            # quality structure: a degraded-mode rule fallback is a quality
            # event even though the session survived (the observatory's
            # degraded-rate window and the fallback counter)
            qmon.record_intent(degraded=True, rule_fallback=True, text=text)
            await send(ws, "warn", degraded=True,
                       message=f"brain unavailable ({degraded_reason}); "
                               "serving rule-based parse")
        else:
            degraded = False
            if r.status_code != 200:
                await send(ws, "error", message=f"brain error {r.status_code}", detail=r.text[:300])
                await utterance_failed(ws, state, t_final0)
                return
            try:
                parsed = ParseResponse.model_validate(r.json())
            except Exception as e:
                await send(ws, "error", message=f"brain returned invalid payload: {e}")
                await utterance_failed(ws, state, t_final0)
                return

        # voice->intent is decided HERE: the stage split below feeds the SLO
        # tracker and the latency_budget event the web HUD renders
        state.stages["parse_ms"] = round((time.perf_counter() - t_final0) * 1e3, 3)
        if not degraded:
            # the brain's decode split rides back as response headers:
            # computed prefill / decode ms and the prompt tokens the KV
            # cache (static prefix or radix session chain) absorbed —
            # rendered by the HUD's stage breakdown under parse
            for header, key in (("x-prefill-ms", "parse_prefill_ms"),
                                ("x-decode-ms", "parse_decode_ms"),
                                ("x-cached-tokens", "cached_tokens"),
                                ("x-prompt-tokens", "prompt_tokens"),
                                ("x-intent-margin", "intent_margin")):
                v = r.headers.get(header)
                if v is not None:
                    try:
                        state.stages[key] = float(v)
                    except ValueError:
                        pass
            # healthy parses must feed the quality windows too — recording
            # only the fallback path would peg the degraded-rate window at
            # 1.0 forever after one transient blip
            qmon.record_intent(margin=state.stages.get("intent_margin"),
                               text=text)
        if degraded:
            state.stages["degraded"] = True
        # outstanding un-prefilled prompt tokens when the endpoint fired —
        # recorded for EVERY utterance (ISSUE 19 satellite: the old gauge
        # only fired on non-degraded engine parses that returned the
        # prompt-tokens header, under-reporting exactly the cold utterances
        # the streaming-prefill work targets); see _prefill_remaining
        get_metrics().set_gauge("engine.prefill_remaining_at_endpoint",
                                _prefill_remaining(state.stages,
                                                   spec_pre_parsed, degraded))
        slo.record(state.stages.get("stt_finalize_ms", 0.0) + state.stages["parse_ms"],
                   ok=True)
        state.slo_open_t0 = None

        tag = {"degraded": True} if degraded else {}
        await send(ws, "intent", data=parsed.model_dump(), **tag)
        if parsed.tts_summary:
            await send(ws, "tts", text=parsed.tts_summary, **tag)
        if parsed.follow_up_question:
            await send(ws, "tts", text=parsed.follow_up_question, **tag)
        # merge context updates (server.ts:162-170)
        state.context.update({k: v for k, v in parsed.context_updates.items()})

        safe = [i for i in parsed.intents if not i.is_risky() and i.type != "unknown"]
        risky = [i for i in parsed.intents if i.is_risky()]
        if risky:
            state.confirm_trace_id = state.trace_id
            await send(
                ws, "confirmation_required",
                intents=[i.model_dump() for i in risky],
                session_id=state.session_id,
                **tag,
            )
        if safe:
            # the latency_budget event follows the execution (execute_ms
            # rides along); a risky-only plan reports without it. Both the
            # stages dict AND the trace id are snapshotted NOW — the next
            # utterance rotates state.trace_id while this task runs
            asyncio.ensure_future(execute_and_report(
                ws, state, safe, http,
                stages=dict(state.stages, trace_id=state.trace_id),
                trace_id=state.trace_id))
        else:
            await emit_budget(ws, state)

    async def utterance_failed(ws, state: ClientState, t_final0: float) -> None:
        """Terminal parse failure: the utterance still costs SLO error
        budget and still reports its (partial) stage split."""
        state.stages["parse_ms"] = round((time.perf_counter() - t_final0) * 1e3, 3)
        state.stages["error"] = True
        slo.record(state.stages.get("stt_finalize_ms", 0.0) + state.stages["parse_ms"],
                   ok=False)
        state.slo_open_t0 = None
        await emit_budget(ws, state)

    async def execute_and_report(ws, state: ClientState, intents: list[Intent], http,
                                 stages: dict | None = None,
                                 trace_id: str | None = None) -> None:
        # trace_id is snapshotted by the CALLER (handle_final): this task is
        # fire-and-forget, and state.trace_id rotates per utterance — reading
        # it here would attribute a slow execution to the NEXT utterance
        trace_id = trace_id or state.trace_id
        t0 = time.perf_counter()
        async with state.exec_lock:
            await _execute_locked(ws, state, intents, http, trace_id)
        if stages is not None:
            stages["execute_ms"] = round((time.perf_counter() - t0) * 1e3, 3)
            await emit_budget(ws, state, stages)

    async def _execute_locked(ws, state: ClientState, intents: list[Intent], http,
                              trace_id: str) -> None:
        try:
            with tracer.span("execute_roundtrip", trace_id=trace_id,
                             intents=len(intents)):
                r = await post_with_resilience(
                    http, cfg.executor_url + "/execute",
                    json_body={
                        "session_id": state.session_id,
                        "intents": [i.model_dump() for i in intents],
                    },
                    headers={"x-trace-id": trace_id},
                    deadline=Deadline.after(cfg.exec_timeout_s),
                    policy=retry_policy,
                    breaker=exec_breaker,
                )
        except asyncio.CancelledError:
            raise
        except BreakerOpenError:
            get_metrics().inc("voice.exec_shed")
            await send(ws, "execution_error", degraded=True,
                       message="executor unavailable (circuit open); "
                               "command dropped — try again shortly")
            return
        except (ResilienceError, httpx.HTTPError, OSError, RuntimeError) as e:
            # RuntimeError: a fire-and-forget execute can outlive the WS
            # handler's AsyncClient ("client has been closed") — the session
            # is already gone, so report-and-return beats an orphan-task
            # traceback
            await send(ws, "execution_error", message=str(e))
            return
        if r.status_code != 200:
            await send(ws, "execution_error", message=f"executor {r.status_code}", detail=r.text[:300])
            return
        body = r.json()
        state.session_id = body.get("session_id") or state.session_id
        await send(ws, "execution_result", data=body)

    async def stream(req: web.Request) -> web.WebSocketResponse:
        ws = web.WebSocketResponse(max_msg_size=8 * 1024 * 1024)
        await ws.prepare(req)
        state = ClientState(cfg.stt_factory())
        if feed_enable:
            state.feed_tracker = _PrefixFeedTracker(k=feed_k,
                                                    min_chars=feed_min_chars)
        live_sessions["n"] += 1
        get_metrics().set_gauge("voice.live_sessions", live_sessions["n"])
        try:
            return await _stream_session(ws, state)
        finally:
            live_sessions["n"] = max(0, live_sessions["n"] - 1)
            get_metrics().set_gauge("voice.live_sessions", live_sessions["n"])
            if state.slo_open_t0 is not None:
                # client disconnected mid-utterance (speech started or a
                # final was being parsed, but no SLO sample ever landed):
                # an aborted utterance is an error sample — the latency is
                # the wall the speaker waited for nothing. Without this,
                # swarm/churn-induced teardown vanishes from slo.voice.*
                # and silently inflates capacity verdicts.
                slo.record((time.perf_counter() - state.slo_open_t0) * 1e3,
                           ok=False)
                state.slo_open_t0 = None
                get_metrics().inc("voice.utterances_aborted")

    async def _stream_session(ws, state: ClientState) -> web.WebSocketResponse:
        from ..serve.stt import NullSTT

        if isinstance(state.stt, NullSTT):
            await send(ws, "warn", message="no STT model loaded; running in null mode")
        else:
            await send(ws, "info", message="listening")

        loop = asyncio.get_running_loop()
        async with httpx.AsyncClient() as http:
            # the finally reaps any in-flight speculative task even
            # when the loop exits by exception (e.g. a send racing an
            # abrupt disconnect) - otherwise the orphan task logs
            # 'Task exception was never retrieved' on GC
            try:
                async for msg in ws:
                    if msg.type == WSMsgType.BINARY:
                        from ..utils.chaos import chaos_fire

                        if chaos_fire("drop_frame"):
                            # chaos drill: simulated network loss of an
                            # audio frame — the pipeline must degrade
                            # (later endpoint, shorter transcript), never
                            # wedge an utterance or kill the session
                            get_metrics().inc("voice.frames_dropped_chaos")
                            continue
                        t_feed0 = time.perf_counter()
                        try:
                            samples = pcm16_to_float(msg.data)
                            # batched STT plane: host-side feed runs inline
                            # and transcriptions are awaited batcher futures
                            # (no executor thread parks on a model call);
                            # otherwise STT may run a model inline — keep
                            # the event loop responsive via the executor
                            afeed = getattr(state.stt, "feed_async", None)
                            if afeed is not None:
                                events = await _feed_now(afeed, samples, t_feed0)
                            else:
                                events = await loop.run_in_executor(
                                    None, _feed_now, state.stt.feed, samples,
                                    t_feed0)
                        except Exception as e:
                            # a truncated PCM packet must not kill the session
                            await send(ws, "warn", message=f"bad audio frame: {e}")
                            continue
                        t_feed1 = time.perf_counter()
                        if state.utt_t0 is None:
                            # a NEW utterance starts at SPEECH ONSET (not at
                            # the first post-final frame — an open mic streams
                            # silence continuously, and counting idle time as
                            # audio_ingest would poison the histogram): fresh
                            # trace id so /debug/trace shows one utterance's
                            # waterfall (speculative parses fired
                            # mid-utterance share it). STT backends without
                            # an endpointer (NullSTT) arm on any frame.
                            ep = getattr(state.stt, "endpointer", None)
                            if ep is None or ep.in_speech or events:
                                state.utt_t0 = t_feed0
                                state.slo_open_t0 = t_feed0
                                state.trace_id = new_trace_id()
                                state.stages = {}
                        for kind, text in events:
                            if kind == "partial":
                                await send(ws, "transcript_partial", text=text)
                                if state.feed_tracker is not None:
                                    # ISSUE 19: a prefix that survived K
                                    # partials streams to the brain as a
                                    # prefill-only feed while the user is
                                    # still speaking
                                    commit = state.feed_tracker.observe(text)
                                    if commit:
                                        await feed_prefix_send(state, commit,
                                                               http)
                            elif kind == "spec_final":
                                # speaker paused: parse the provisional
                                # transcript while the endpoint window runs out
                                await speculate(state, text, http)
                            else:
                                # stage spans for the waterfall: the whole
                                # capture window and the feed call that
                                # finalized the transcript
                                tracer.record_span(
                                    "audio_ingest", state.trace_id,
                                    state.utt_t0, t_feed1)
                                tracer.record_span(
                                    "stt_finalize", state.trace_id,
                                    t_feed0, t_feed1, chars=len(text))
                                state.stages.update(
                                    audio_ingest_ms=round((t_feed1 - state.utt_t0) * 1e3, 3),
                                    stt_finalize_ms=round((t_feed1 - t_feed0) * 1e3, 3),
                                )
                                state.utt_t0 = None
                                if state.feed_tracker is not None:
                                    # utterance over: the next partial
                                    # stream is fresh text, and a feed
                                    # still in flight would only race the
                                    # real parse for engine time (its
                                    # already-committed chains stay as
                                    # cache the parse is about to hit)
                                    state.feed_tracker.reset()
                                    state.drop_feed()
                                # STT confidence rides the transcript_final
                                # event (ISSUE 15): the streaming wrapper
                                # published this final's full result —
                                # logprob lanes + repetition — on the same
                                # feed call that emitted the event
                                conf_payload = {}
                                lf = getattr(state.stt, "last_final", None)
                                if lf is not None and \
                                        getattr(lf, "repetition", None) is not None:
                                    conf = {k: getattr(lf, k) for k in
                                            ("logp_mean", "logp_min",
                                             "logp_first", "repetition")
                                            if getattr(lf, k) is not None}
                                    conf_payload["confidence"] = conf
                                    qmon.record_stt(
                                        lf.logp_mean, lf.logp_min,
                                        lf.repetition, text=text,
                                        logp_first=lf.logp_first)
                                await send(ws, "transcript_final", text=text,
                                           **conf_payload)
                                await handle_final(ws, state, text, http)
                    elif msg.type == WSMsgType.TEXT:
                        try:
                            ctrl = json.loads(msg.data)
                        except json.JSONDecodeError:
                            await send(ws, "warn", message="bad control frame")
                            continue
                        ctype = ctrl.get("type")
                        if ctype == "context_update":
                            state.context.update(ctrl.get("data") or {})
                            # an in-flight speculative parse saw the OLD context
                            state.drop_spec()
                            # so did an in-flight prefix feed — its prompt
                            # rendered the stale context dict (ISSUE 19)
                            state.drop_feed()
                            if state.feed_tracker is not None:
                                state.feed_tracker.reset()
                            await send(ws, "info", message="context updated")
                        elif ctype == "tenant":
                            # QoS lane tag (ISSUE 18): rides every /parse
                            # from here on and re-lanes this connection's
                            # STT work. Unknown names degrade to the
                            # default class at the plane, so no validation
                            # round-trip is needed here.
                            state.tenant = str(ctrl.get("tenant") or "") or None
                            if hasattr(state.stt, "tenant"):
                                state.stt.tenant = state.tenant
                            await send(ws, "info", message="tenant set")
                        elif ctype == "text":
                            # typed command path: same pipeline minus STT
                            text = str(ctrl.get("text") or "")
                            if text:
                                state.trace_id = new_trace_id()
                                state.stages = {}
                                state.utt_t0 = None
                                state.slo_open_t0 = time.perf_counter()
                                await send(ws, "transcript_final", text=text)
                                await handle_final(ws, state, text, http)
                        elif ctype == "confirm_execute":
                            # UI approved risky intents: execute them now
                            try:
                                intents = [Intent.model_validate(i) for i in ctrl.get("intents") or []]
                            except Exception as e:
                                await send(ws, "warn", message=f"bad intents: {e}")
                                continue
                            if intents:
                                # attribute to the utterance that PROPOSED
                                # the plan (frames spoken since the
                                # confirmation prompt rotated state.trace_id)
                                await execute_and_report(
                                    ws, state, intents, http,
                                    trace_id=state.confirm_trace_id)
                                state.confirm_trace_id = None
                        elif ctype == "reset":
                            state.stt.reset()
                            state.context = {}
                            # a client-initiated reset cleanly CANCELS any
                            # armed utterance — it must not be scored as an
                            # aborted-mid-flight error at teardown
                            state.utt_t0 = None
                            state.slo_open_t0 = None
                            state.drop_spec()
                            state.drop_feed()
                            if state.feed_tracker is not None:
                                state.feed_tracker.reset()
                            await send(ws, "info", message="state reset")
                        else:
                            await send(ws, "warn", message=f"unknown control type {ctype!r}")
                    elif msg.type in (WSMsgType.CLOSE, WSMsgType.ERROR):
                        break
            finally:
                state.drop_spec()
                state.drop_feed()  # WS teardown reaps the in-flight feed
                closer = getattr(state.stt, "close", None)
                if closer is not None:
                    closer()  # batched plane: free the utterance's slot
        return ws

    async def index(_req: web.Request) -> web.FileResponse:
        from ..web import static_dir

        return web.FileResponse(static_dir() / "index.html")


    app.router.add_get("/health", health)
    from ..utils.tracing import (
        make_flightrecorder_handler,
        make_metrics_handler,
        make_trace_handler,
    )

    app.router.add_get("/metrics", make_metrics_handler("voice", tracer, slo=slo))
    app.router.add_get("/debug/trace/{trace_id}", make_trace_handler("voice", tracer))
    app.router.add_get("/debug/flightrecorder", make_flightrecorder_handler("voice"))
    app.router.add_get("/debug/quality", make_quality_handler(qmon))

    async def debug_costs(_req: web.Request) -> web.Response:
        # the STT share of the cost observatory (ISSUE 17): summed
        # analytic encoder/decoder FLOPs across live SpeechEngines
        from ..utils.costmodel import cost_enabled, stt_cost_summary

        return web.json_response({"service": "voice",
                                  "enabled": cost_enabled(),
                                  "stt": stt_cost_summary()})

    app.router.add_get("/debug/costs", debug_costs)
    from ..utils.timeseries import attach_timeseries

    attach_timeseries(app, "voice", tracer)
    app.router.add_get("/stream", stream)
    app.router.add_get("/", index)
    from ..web import static_dir as _sd

    app.router.add_static("/static/", _sd())
    return app


def main() -> None:
    load_env_cascade()
    from ..utils.compilecache import place_compile_cache

    place_compile_cache()
    from ..parallel.multihost import init_multihost

    init_multihost()  # no-op single-host; DCN join for pod-sharded STT
    port = int(os.environ.get("VOICE_PORT", "7072"))
    cfg = VoiceConfig()
    from . import warm_up

    warm_up(cfg.stt_factory)
    app = build_app(cfg, tracer=Tracer("voice"))
    web.run_app(app, port=port, handler_cancellation=True)


if __name__ == "__main__":
    main()
