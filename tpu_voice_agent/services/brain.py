"""Brain service: text + context -> validated intent plan.

Capability parity with the reference brain (apps/brain/src/server.ts:84-142):
``POST /parse`` takes ``{text, session_id?, context}`` and returns a
``ParseResponse``; error envelopes match the reference contract —
400 ``invalid_request``, 422 ``schema_validation_failed``, 500 ``llm_error``
(server.ts:91-95, :122-136). What changed underneath: the OpenAI call
(llm.ts:19-30) is replaced by the in-tree grammar-constrained TPU decode, so
the reference's validate-then-repair loop (server.ts:110-121) is structurally
unnecessary — the only residual failure mode is token-budget truncation.

Parser backends (the test seam, mirroring the reference's mocked
``callLLMJSON``):
- ``EngineParser``   — DecodeEngine on TPU (or any jax backend)
- ``RuleBasedParser`` — deterministic keyword heuristics; offline mode and
  the fake backend for tests (reference analog: null-Deepgram-key mode)
"""

from __future__ import annotations

import asyncio
import json
import os
import re
import threading
import time
from typing import Protocol

from aiohttp import web

from ..schemas import Intent, ParseRequest, ParseResponse, Target, parse_response_from_json
from ..utils import SLOTracker, Tracer, get_metrics, load_env_cascade, new_trace_id
from ..utils.resilience import (
    AdmissionController,
    Deadline,
    DeadlineExpired,
    shed_response,
)
from .prompts import render_prompt


class IntentParser(Protocol):
    def parse(self, text: str, context: dict) -> ParseResponse: ...


class ParserError(Exception):
    def __init__(self, kind: str, detail: str):
        super().__init__(detail)
        self.kind = kind  # "schema_validation_failed" | "llm_error"
        self.detail = detail


# ---------------------------------------------------------------- backends


def _result_to_response(res) -> ParseResponse:
    """GenerationResult -> ParseResponse with the reference error mapping.
    Deposits the prefill/decode split as stage notes on the calling thread
    so the /parse span (and therefore the trace waterfall) carries the
    decode decomposition, not just the total. prefill_ms is COMPUTED
    prefill only; cached_tokens says how much KV the prefix/radix cache
    absorbed (the split the web HUD renders)."""
    from ..utils.tracing import note_stage

    note_stage("prefill_ms", round(res.prefill_ms, 3))
    note_stage("decode_ms", round(res.decode_ms, 3))
    note_stage("cached_tokens", int(getattr(res, "cached_tokens", 0)))
    note_stage("prompt_tokens", int(getattr(res, "prompt_tokens", 0)))
    # the wait for a batch slot (0 on the serialized backend): its own
    # number on the /parse span and the ``x-queue-ms`` header
    note_stage("queue_ms", round(getattr(res, "queue_ms", 0.0), 3))
    # the ISSUE 15 confidence vector rides the same stage-note channel the
    # prefill/decode split uses — the quality monitor and the response
    # headers both read it off this thread
    q = getattr(res, "quality", None)
    if q:
        note_stage("intent_margin", q["margin_mean"])
        note_stage("intent_entropy", q["entropy_mean"])
        note_stage("intent_forced_frac", q["forced_frac"])
    if res.error:
        # typed scheduler errors (serve.scheduler._err_result contract):
        # "shed: ..." is retryable overload -> 503 + Retry-After, so the
        # voice-side retry/degrade kit treats a KV-pool-exhausted or
        # queue-expired request exactly like an admission shed. Everything
        # else (poisoned/quarantined/cancelled/engine fault) is terminal
        # for these bytes -> llm_error.
        if res.error.startswith("shed:"):
            raise ParserError("overloaded", res.error)
        raise ParserError("llm_error", res.error)
    # ONE request and token count for both backends (BRAIN_BATCH 1 and >1):
    # a decode that ran to its end, EOS or truncation — what
    # ``scheduler.requests_completed`` counts on the batched one alone
    from ..utils import get_metrics

    get_metrics().inc("brain.parse_completed")
    get_metrics().inc("brain.parse_tokens", float(res.steps))
    if not res.finished:
        raise ParserError(
            "schema_validation_failed",
            f"decode truncated after {res.steps} tokens (no EOS)",
        )
    model, err = parse_response_from_json(res.text)
    if model is None:
        # unreachable under the grammar; kept as a hard backstop
        raise ParserError("schema_validation_failed", err or "invalid")
    return model


def install_prompt_prefix(engine) -> int:
    """Prefill the request-invariant prompt head (system + few-shots) into
    the engine's shared-prefix cache so per-request prefill covers only the
    user payload. Token-exact: two differing sample payloads locate the
    common token prefix."""
    from .prompts import render_prompt as rp

    return engine.set_prompt_prefix(
        rp("sample utterance alpha", {}),
        rp("a rather different beta payload", {"last_query": "gamma"}),
    )


class EngineParser:
    """Grammar-constrained decode on the in-tree engine (serialized).

    ``render`` maps (text, context) -> prompt string; the default is the
    few-shot prompt. Distilled checkpoints (train.distill) pass their short
    prompt instead — the task lives in the weights, so inference skips the
    ~880-token prefix entirely."""

    def __init__(self, engine, max_new_tokens: int = 512, render=None):
        self.engine = engine
        self.max_new_tokens = max_new_tokens
        self.render = render or render_prompt

    def parse(self, text: str, context: dict) -> ParseResponse:
        prompt = self.render(text, context)
        try:
            res = self.engine.generate(
                prompt, max_new_tokens=self.max_new_tokens, greedy=True, constrained=True
            )
        except ValueError as e:  # prompt too long etc.
            raise ParserError("llm_error", str(e)) from e
        return _result_to_response(res)

    def warmup(self) -> None:
        """One throwaway generation: compiles the short-utterance prefill
        bucket, the first-token pick and the whole-generation decode loop
        before the service listens (services.warm_up)."""
        self.engine.generate(self.render("warm up", {}),
                             max_new_tokens=self.max_new_tokens,
                             greedy=True, constrained=True)


class SessionTranscripts:
    """Deterministic multi-turn prompt rendering for the radix KV plane.

    Turn N's prompt is built in TOKEN-ID space: the literal turn N-1 prompt
    ids + the ids the model actually generated + one freshly encoded
    ``<|user|>``/``<|assistant|>`` frame — a STRICT token extension of what
    the engine already decoded, which the radix tree (serve.radix) turns
    into an O(new utterance) admission. Id space, not text space, because
    re-encoding generated text is not id-stable: grammar-constrained
    decoding may emit non-canonical BPE pieces, and one divergent id would
    cap every later turn's match at the first turn's prompt. Host-side ids
    only; the KV lives in the engine's paged pool — an evicted chain just
    re-prefills, nothing here has to be invalidated.

    Turn 1 renders through ``render_prompt`` unchanged (a session's first
    request is byte-identical to the stateless path); later frames
    serialize the user payload with SORTED keys (deterministic rendering:
    the same (text, context) must always produce the same bytes, or turn
    N's prompt would silently stop extending turn N-1's).
    """

    def __init__(self, tokenizer, max_sessions: int | None = None,
                 encode_prompt=None):
        from collections import OrderedDict

        self.tokenizer = tokenizer
        # how a rendered prompt becomes ids: the serving engine's
        # ``encode_prompt`` (the head's ids are kept there), else the
        # tokenizer's whole walk
        self._encode_prompt = encode_prompt or (
            lambda prompt: (tokenizer.encode(prompt, bos=True), 0))
        self.max_sessions = max_sessions if max_sessions is not None else int(
            os.environ.get("RADIX_SESSIONS", "256"))
        self._hist: "OrderedDict[str, list[int]]" = OrderedDict()
        self._lock = threading.Lock()

    @staticmethod
    def user_frame(text: str, context: dict) -> str:
        return json.dumps({"text": text, "context": context},
                          separators=(",", ":"), sort_keys=True)

    def prompt_for(self, session_id: str, text: str, context: dict):
        """This turn's prompt: a fresh stateless render (str) for turn 1,
        or the transcript ids + the new frame's ids (list[int]) — the
        batcher accepts both."""
        with self._lock:
            hist = self._hist.get(session_id)
            if hist is not None:
                self._hist.move_to_end(session_id)
                hist = list(hist)
        if hist is None:
            return render_prompt(text, context)
        frame = f"\n<|user|>\n{self.user_frame(text, context)}\n<|assistant|>\n"
        return hist + self.tokenizer.encode(frame, bos=False)

    def record(self, session_id: str, prompt, generated_ids: list[int]) -> None:
        """Commit a finished turn: the next prompt extends prompt+output."""
        ids = (self._encode_prompt(prompt)[0]
               if isinstance(prompt, str) else list(prompt))
        with self._lock:
            self._hist[session_id] = ids + [int(t) for t in generated_ids]
            self._hist.move_to_end(session_id)
            while len(self._hist) > self.max_sessions:
                self._hist.popitem(last=False)

    def peek(self, session_id: str) -> list[int] | None:
        """The session's committed transcript ids (a copy), without
        touching LRU order — the warm-state handoff's export read."""
        with self._lock:
            hist = self._hist.get(session_id)
            return list(hist) if hist is not None else None

    def adopt(self, session_id: str, ids: list[int]) -> None:
        """Install a transcript shipped from another replica (warm-state
        handoff): the donor is authoritative at re-home time, so an older
        local entry for the id is overwritten."""
        with self._lock:
            self._hist[session_id] = [int(t) for t in ids]
            self._hist.move_to_end(session_id)
            while len(self._hist) > self.max_sessions:
                self._hist.popitem(last=False)

    def forget(self, session_id: str) -> None:
        with self._lock:
            self._hist.pop(session_id, None)


class BatchedEngineParser:
    """Continuous-batched grammar-constrained decode behind /parse.

    N concurrent requests share chunked decode dispatches on ONE engine
    (slot-based continuous batching, serve.scheduler) — the TPU replacement
    for the reference voice/brain stack's Node event-loop concurrency
    (apps/voice/src/server.ts:97). Each request's future resolves when its
    slot finishes; admission happens at chunk boundaries.

    ``session_aware=True`` (the radix KV plane, RADIX_ENABLE=1 +
    BRAIN_PAGED=1) keeps a per-session transcript so turn N's prompt is a
    strict token extension of turn N-1's — the engine's radix tree then
    admits returning sessions with O(new utterance) prefill. Speculative
    turns run two-phase like the planner's: the provisional turn decodes
    normally but the transcript only advances when the matching final
    COMMITS it (returning the cached plan, zero decode); a superseded
    speculation just never gets recorded — there is no KV to roll back,
    the radix tree keeps whatever chains were decoded as reusable cache.
    """

    concurrent_safe = True  # build_app skips the serialization lock

    def __init__(self, engine, chunk_steps: int = 16, max_new_tokens: int = 512,
                 timeout_s: float = 120.0, session_aware: bool = False):
        from ..serve import ColocatedServing, ContinuousBatcher

        self.engine = engine
        self.max_new_tokens = max_new_tokens
        self.batcher = ContinuousBatcher(
            engine, chunk_steps=chunk_steps, max_new_tokens=max_new_tokens
        )
        self.runtime = ColocatedServing(None, self.batcher)
        self.timeout_s = timeout_s
        # session-keyed surface only when asked: wants_session makes
        # build_app thread session_id/speculative through; stateless mode
        # keeps the exact pre-radix parse(text, context) contract
        self.wants_session = session_aware
        self.supports_speculation = True
        self.transcripts = (SessionTranscripts(engine.tokenizer,
                                               encode_prompt=engine.encode_prompt)
                            if session_aware else None)
        # sid -> two-phase spec turn; LRU-capped like the transcripts — a
        # session that speculates and then disconnects must not leak its
        # pending plan (prompt ids + response) forever
        from collections import OrderedDict

        self._pending: "OrderedDict[str, dict]" = OrderedDict()
        self._pending_cap = (self.transcripts.max_sessions
                             if self.transcripts is not None else 64)
        self._plock = threading.Lock()
        # disagg adopt streams (ISSUE 20): stream_id -> StreamAdopter;
        # touched only on the serving-loop thread (adopt_stream submits)
        self._disagg_adopt: "OrderedDict[str, object]" = OrderedDict()
        # per-session resource attribution (ISSUE 17): every finished
        # request's cost ledger folds into a session-keyed LRU — the meter
        # /debug/costs names top-cost sessions from (and the fair-share
        # signal the multi-tenant QoS item needs)
        from ..utils.costmodel import SessionCostLedger

        self.session_costs = (SessionCostLedger()
                              if self.batcher.costs is not None else None)
        self.runtime.start()
        # liveness watchdog: a dead serving loop restarts with inflight
        # futures failed fast instead of silently queueing forever
        self.runtime.start_watchdog()

    def _decode(self, prompt: str):
        """Submit, wait, and hand back ``(result, deliver)``: ``deliver`` is the
        request's ``brain.deliver`` annotation, open since this thread WOKE
        with the result (attr ``rid``, as ``brain.submit`` and the batcher's
        ``sched.admit.request`` carry it); the caller leaves it when the
        answer is converted. How long the wake took after the serving loop
        resolved the future is counted (``brain.parse_deliver_ms``) and noted
        on the request (``deliver_ms``)."""
        from concurrent.futures import CancelledError

        from ..utils import get_metrics
        from ..utils.resilience import current_request_context
        from ..utils.steplog import annotation
        from ..utils.tracing import note_stage

        # the request context (set by build_app on this worker thread)
        # carries the propagated deadline INTO the scheduler — expired
        # requests shed at dequeue / cancel mid-decode — and registers the
        # disconnect canceller: a client that vanishes aborts its decode at
        # the next chunk boundary instead of burning the slot's budget
        ctx = current_request_context()
        fut = self.runtime.submit_parse(
            prompt, deadline=ctx.deadline if ctx is not None else None,
            tenant=getattr(ctx, "tenant", None))
        if ctx is not None:
            ctx.on_cancel(lambda: self.runtime.cancel_parse(fut))
        try:
            res = fut.result(timeout=self.timeout_s)
            woke = time.perf_counter_ns()
            deliver = annotation("brain.deliver", rid=getattr(fut, "request_id", -1))
            resolved = getattr(fut, "resolved_ns", None)  # none: refused at submit
            if resolved is not None:
                ms = (woke - resolved) / 1e6
                get_metrics().inc("brain.parse_deliver_ms", ms)
                note_stage("deliver_ms", round(ms, 3))
            return res, deliver
        except CancelledError as e:  # BaseException: the broad catch misses it
            raise ParserError("llm_error", "cancelled: client disconnected") from e
        except TimeoutError as e:
            # dequeue the abandoned request so overload can't pile up work
            # nobody will read (queued entries drop immediately; a slot
            # already decoding is evicted at the next chunk boundary)
            self.runtime.abandon_parse(fut)
            raise ParserError("llm_error", "batched decode timed out") from e
        except Exception as e:
            raise ParserError("llm_error", str(e)) from e

    def _answer(self, prompt, session_id: str | None):
        """One decode and its conversion, ``(result, response)``; the wake and
        the conversion lie under the request's ``brain.deliver``."""
        res, deliver = self._decode(prompt)
        with deliver:
            self._fold_cost(session_id, res)
            return res, _result_to_response(res)

    def parse(self, text: str, context: dict, session_id: str | None = None,
              speculative: bool = False) -> ParseResponse:
        if self.transcripts is None or not session_id:
            return self._answer(render_prompt(text, context), session_id)[1]
        user = SessionTranscripts.user_frame(text, context)
        with self._plock:
            pend = self._pending.pop(session_id, None)
        if pend is not None and not speculative and pend["user"] == user:
            # commit: the speculative turn IS this turn — advance the
            # transcript and deliver the cached plan without decoding
            from ..utils import get_metrics
            from ..utils.tracing import note_stage

            self.transcripts.record(session_id, pend["prompt"], pend["gen"])
            get_metrics().inc("brain.session_spec_commits")
            for k, v in pend["notes"].items():
                note_stage(k, v)
            return pend["resp"]
        # superseded speculation: nothing to roll back — the transcript
        # never advanced, and the decoded chain stays in the radix tree as
        # plain reusable cache
        prompt = self.transcripts.prompt_for(session_id, text, context)
        if self._too_long(prompt):
            # transcript outgrew the prefill/decode budget: cold-start the
            # session (the reference rolls its context dict forever; we
            # bound model context by the engine's real capacity)
            self.transcripts.forget(session_id)
            prompt = self.transcripts.prompt_for(session_id, text, context)
        res, resp = self._answer(prompt, session_id)  # raises on truncation:
        # transcript stays at the last committed turn (the session survives)
        if speculative:
            from ..utils.tracing import peek_stage_notes

            with self._plock:
                self._pending[session_id] = {
                    "user": user, "resp": resp, "prompt": prompt,
                    "gen": list(res.token_ids), "notes": dict(peek_stage_notes())}
                self._pending.move_to_end(session_id)
                while len(self._pending) > self._pending_cap:
                    self._pending.popitem(last=False)
        else:
            self.transcripts.record(session_id, prompt, res.token_ids)
        return resp

    # incremental streaming prefill (ISSUE 19): a prefix-feed request warms
    # the session's radix chain from a stabilized STT partial WITHOUT taking
    # a decode slot or advancing the transcript. The prompt renders through
    # the SAME prompt_for path a real parse uses, so the fed chain is a
    # token-exact prefix of the eventual final's prompt up to the point the
    # partial and final diverge — the radix tree's block-aligned match
    # absorbs exactly the shared part and ignores the rest. Best-effort by
    # contract: the scheduler sheds feeds whenever real work is waiting.
    supports_prefix_feed = True

    def feed_prefix(self, text: str, context: dict,
                    session_id: str | None = None) -> dict:
        from concurrent.futures import CancelledError

        from ..utils.resilience import current_request_context

        if self.transcripts is not None and session_id:
            prompt = self.transcripts.prompt_for(session_id, text, context)
        else:
            prompt = render_prompt(text, context)
        if self._too_long(prompt):
            return {"ok": False, "reason": "too_long"}
        ctx = current_request_context()
        tenant = getattr(ctx, "tenant", None)
        fut = self.runtime.submit_call(
            lambda: self.batcher.feed_prefix(prompt, tenant=tenant))
        if ctx is not None:
            # WS teardown / context reset fires the cancellation chain: a
            # not-yet-started feed is dropped on the floor (fut.cancel); one
            # already prefilling completes-and-commits, which is harmless —
            # the chain is plain reusable cache, nothing holds a slot
            ctx.on_cancel(fut.cancel)
        try:
            return fut.result(timeout=self.timeout_s)
        except CancelledError:
            return {"ok": False, "reason": "cancelled"}
        except TimeoutError:
            return {"ok": False, "reason": "timeout"}
        except Exception as e:
            return {"ok": False, "reason": f"{type(e).__name__}: {e}"}

    # prefill/decode disaggregation (ISSUE 20): a prefill-pool replica runs
    # the prefill-only EXPORT admission (feed_prefix generalized — the
    # chain is gathered and streamed out segment by segment while later
    # chunks still compute) and a decode-pool replica installs the stream
    # behind its pinned root via the per-stream adopter. Both halves run on
    # the serving-loop thread like every other allocator/radix touch.
    supports_disagg = True

    def disagg_prefill(self, text: str, context: dict,
                       session_id: str | None = None, *,
                       stream_blocks: int = 4, emit=None,
                       stream_id: str | None = None) -> dict:
        if self.transcripts is not None and session_id:
            # render through the same prompt_for path a real parse uses:
            # when this replica knows the session the export is token-exact
            # for it; an unknown session renders turn-1 style, which the
            # decode home's radix simply matches as far as it agrees
            prompt = self.transcripts.prompt_for(session_id, text, context)
        else:
            prompt = render_prompt(text, context)
        if self._too_long(prompt):
            return {"ok": False, "reason": "too_long"}
        fut = self.runtime.submit_call(
            lambda: self.batcher.prefill_export(
                prompt, stream_blocks=stream_blocks, emit=emit,
                stream_id=stream_id))
        try:
            return fut.result(timeout=self.timeout_s)
        except Exception as e:
            return {"ok": False, "reason": f"{type(e).__name__}: {e}"}

    _DISAGG_STREAMS_CAP = 4

    def adopt_stream(self, stream_id: str, blob: bytes) -> dict:
        """Install ONE disagg stream blob (kv_seg segment or kv_end
        commit) for ``stream_id``. Per-stream adopter state is LRU-capped:
        an abandoned stream's adopter is closed (partial commit + refs
        freed — zero leaked blocks) when newer streams push it out. All
        mutation happens on the serving-loop thread, so the dict needs no
        lock of its own."""
        from ..serve import handoff

        def run() -> dict:
            ad = self._disagg_adopt.get(stream_id)
            if ad is None:
                ad = handoff.StreamAdopter(self.engine)
                self._disagg_adopt[stream_id] = ad
                while len(self._disagg_adopt) > self._DISAGG_STREAMS_CAP:
                    _, old = self._disagg_adopt.popitem(last=False)
                    old.abandon()
            else:
                self._disagg_adopt.move_to_end(stream_id)
            try:
                out = ad.feed(blob)
            except ValueError as e:
                self._disagg_adopt.pop(stream_id, None)
                return {"ok": False, "reason": str(e)}
            if out.get("final"):
                self._disagg_adopt.pop(stream_id, None)
            return out

        fut = self.runtime.submit_call(run)
        try:
            return fut.result(timeout=self.timeout_s)
        except Exception as e:
            return {"ok": False, "reason": f"{type(e).__name__}: {e}"}

    def _fold_cost(self, session_id: str | None, res) -> None:
        """Fold a finished request's ledger into the session rollup —
        BEFORE response conversion, so errored results (which raise in
        _result_to_response) still attribute the cost they spent."""
        if self.session_costs is not None and getattr(res, "cost", None):
            self.session_costs.fold(session_id, res.cost)

    def _too_long(self, prompt) -> bool:
        """Token-length guard: the prompt must fit a prefill bucket AND
        leave the decode budget's headroom before max_len."""
        eng = self.engine
        limit = min(eng.prefill_buckets[-1], eng.max_len - self.max_new_tokens)
        n = (len(eng.encode_prompt(prompt)[0])
             if isinstance(prompt, str) else len(prompt))
        return n > limit

    def healthy(self) -> bool:
        return self.runtime.healthy()

    def warmup(self) -> None:
        """Compile the batcher's programs before the service listens
        (services.warm_up). Runs on the serving-loop thread like every
        other engine touch — as a call, so the compiles are not timed by
        the stall watchdog, which only arms around decode steps."""
        self.runtime.submit_call(self.batcher.warmup).result()

    # graceful drain (ISSUE 10): the serve-layer latch — the router stops
    # placing NEW sessions on this replica, in-flight work completes, and
    # /health's ``drained`` flip tells the router it is safe to eject
    def begin_drain(self) -> None:
        self.runtime.begin_drain()

    def drained(self) -> bool:
        return self.runtime.drained()

    def quarantine_info(self) -> list[dict]:
        """Active poison-quarantine entries (surfaced in /health): prompts
        whose repeated poison offenses got them refused at submit."""
        return self.batcher.quarantined()

    def pressure_fractions(self) -> dict:
        """LIVE saturation fractions for the /health ``pressure`` block
        (the router's shed signal). Read from current scheduler/allocator
        state, NOT the last-tick gauges: ``scheduler.batch_occupancy``
        only rewrites inside a processed chunk, so after a burst an IDLE
        replica's gauge stays pinned at its last busy value and the
        router would shed new sessions off an empty replica forever.
        Racy-but-monotone reads are fine for a shed signal."""
        b = self.batcher
        out = {"scheduler.batch_occupancy":
               sum(1 for s in b.slots if s.request_id >= 0) / max(1, b.B)}
        alloc = self.engine.allocator
        if alloc is not None:
            used = alloc.blocks_in_use
            radix = self.engine.radix
            if radix:
                # a warm radix cache drifts raw utilization toward 1.0 BY
                # DESIGN (released chains keep tree refs; _alloc reclaims
                # them under pressure) — counting reclaimable cache as
                # saturation would shed new sessions off exactly the
                # warmest replicas, inverting placement
                used -= sum(t.reclaimable_blocks() for t in radix)
            out["paged.kv_pressure"] = max(0, used) / max(1, alloc.usable_blocks)
        return out

    # warm-state handoff (ISSUE 13): the router ships a re-homed session's
    # transcript + radix-chain KV from its old home to its new one. Both
    # halves run on the serving-loop thread (ColocatedServing.submit_call)
    # — the allocator/radix/pool bookkeeping is single-threaded by
    # contract — and both are best-effort: any failure is a cold re-home,
    # never an error.
    def export_session(self, session_id: str) -> bytes | None:
        if self.transcripts is None:
            return None
        from ..serve import handoff

        fut = self.runtime.submit_call(
            lambda: handoff.export_session(self.engine, self.transcripts,
                                           session_id))
        try:
            return fut.result(timeout=self.timeout_s)
        except Exception:
            return None

    def adopt_session(self, blob: bytes) -> int:
        if self.transcripts is None:
            return 0
        from ..serve import handoff

        fut = self.runtime.submit_call(
            lambda: handoff.adopt_session(self.engine, self.transcripts, blob))
        try:
            return int(fut.result(timeout=self.timeout_s))
        except Exception:
            # malformed/truncated blob (or an install fault before the
            # per-cause counters): still a COUNTED cold fallback — an
            # operator debugging cold re-homes must see it move, not a
            # silently swallowed exception
            import logging

            from ..utils import get_metrics

            get_metrics().inc("handoff.adopt_fallbacks")
            logging.getLogger("tpu_voice_agent.brain").warning(
                "handoff adoption failed; session will cold-prefill",
                exc_info=True)
            return 0

    def close(self) -> None:
        self.runtime.stop()


class _PlanGather:
    """Batches concurrent plan() decodes onto one plan_many dispatch.

    Requests land on a queue; ONE worker thread drains whatever is queued
    at that moment and decodes the whole set in a single batched
    chunk_decode_loop (sessions in the same context bucket share every
    step's weight read). The worker is also the only caller of the
    planner's RNG-bearing decode path, so plan_many needs no lock of its
    own."""

    def __init__(self, planner, max_batch: int = 8):
        import queue

        self.planner = planner
        self.max_batch = max_batch
        self._q: "queue.Queue" = queue.Queue()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="planner-gather")
        self._thread.start()

    def plan(self, sess, max_new_tokens: int):
        from concurrent.futures import Future

        fut: Future = Future()
        self._q.put((sess, max_new_tokens, fut))
        return fut.result()

    def healthy(self) -> bool:
        return self._thread.is_alive()

    def _loop(self) -> None:
        import logging
        import queue

        log = logging.getLogger("tpu_voice_agent.planner")
        while True:
            batch = [self._q.get()]
            while len(batch) < self.max_batch:
                try:
                    batch.append(self._q.get_nowait())
                except queue.Empty:
                    break
            # group by token budget: co-batching requests with different
            # max_new_tokens under min() would silently truncate the larger
            # ask (PlannerParser happens to pass a constant today, but this
            # gatherer is public surface)
            groups: dict[int, list] = {}
            for b in batch:
                groups.setdefault(b[1], []).append(b)
            for max_new, group in groups.items():
                sessions = [b[0] for b in group]
                try:
                    outs = self.planner.plan_many(sessions, max_new_tokens=max_new)
                except Exception as e:
                    log.exception("batched plan decode failed")
                    for _, _, fut in group:
                        fut.set_exception(e)
                    continue
                for (_, _, fut), out in zip(group, outs):
                    fut.set_result(out)


class PlannerParser:
    """Long-session planner behind /parse (``BRAIN_BACKEND=planner[:preset]``).

    Unlike EngineParser — which re-renders a stateless prompt per request
    while the voice service carries a rolling context dict — this backend
    keeps each session's FULL transcript as model context: turn N sees
    every prior utterance AND every prior plan. New turns append with
    O(new-tokens) cached prefill; when a transcript outgrows its context
    bucket the planner re-anchors via the SP ring-attention prefill
    (parallel.longctx), so per-session context capacity scales with chips
    on the sp mesh axis. Reference capability replaced: the rolling
    context-dict merge at apps/voice/src/server.ts:162-170 — the part of
    the session the reference throws away is exactly what this keeps.

    Concurrency (round-2 VERDICT weak #2 fixed): turns serialize PER
    SESSION (a session's transcript is ordered), but different sessions
    run concurrently — their extend prefills dispatch independently and
    their plan decodes share batched decode steps via _PlanGather.

    Eviction is LRU and BYTE-AWARE (round-2 advisor): each live session
    pins its full KV cache in HBM, so the cap is a byte budget
    (BRAIN_PLANNER_HBM_MB, default 2048) checked with the planner's real
    per-session cache bytes — not just a session count. An evicted
    session simply cold-starts again on its next turn.
    """

    wants_session = True  # build_app passes ParseRequest.session_id through
    concurrent_safe = True  # build_app skips the global serialization lock
    supports_speculation = True  # two-phase turns (snapshot + commit/rollback)
    max_sessions = 32

    def __init__(self, planner, max_new_tokens: int | None = None,
                 hbm_budget_bytes: int | None = None, render=None):
        from collections import OrderedDict

        self.planner = planner
        # session-start prompt renderer: the few-shot prefix by default;
        # distilled checkpoints pass train.distill.distilled_prompt (the
        # task lives in their weights — the ~880-token prefix would be
        # out-of-distribution for them, not just wasted prefill)
        self.render = render or render_prompt
        # never exceed the planner's reserved headroom: its bucket
        # accounting guarantees max_new_tokens slots past the transcript,
        # so a larger request here would truncate mid-JSON at the bucket
        # wall on exactly the turns the accounting was supposed to protect
        self.max_new_tokens = min(max_new_tokens or planner.max_new_tokens,
                                  planner.max_new_tokens)
        if hbm_budget_bytes is None:
            hbm_budget_bytes = int(os.environ.get(
                "BRAIN_PLANNER_HBM_MB", "2048")) * (1 << 20)
        self.hbm_budget_bytes = hbm_budget_bytes
        # evicted sessions PARK to host RAM (one device_get) instead of
        # being dropped — resuming costs one upload, not an O(transcript)
        # re-anchor. BRAIN_PLANNER_PARK_MB caps host bytes (0 = drop only).
        self.park_budget_bytes = int(os.environ.get(
            "BRAIN_PLANNER_PARK_MB", "4096")) * (1 << 20)
        self._sessions: "OrderedDict[str, object]" = OrderedDict()
        self._parked: "OrderedDict[str, object]" = OrderedDict()  # host RAM
        self._busy: set[str] = set()  # sessions mid-turn: never evicted
        self._session_locks: dict[str, threading.Lock] = {}
        self._registry = threading.Lock()  # guards the maps above
        self._gather = _PlanGather(planner)

    def _checkout(self, session_id: str | None):
        """Claim a session for one turn (per-session ordering) or None for
        a one-shot parse. NEVER a shared default key for anonymous
        requests — that would bleed one client's transcript into
        another's context."""
        if not session_id:
            return None, None
        while True:
            with self._registry:
                lock = self._session_locks.setdefault(session_id, threading.Lock())
            lock.acquire()
            with self._registry:
                # re-check under the registry: the prune may have dropped
                # this lock's entry between our setdefault and acquire (we
                # held nothing in that window), and a later checkout may
                # have registered a FRESH lock for the id — holding the
                # stale one would let two turns of one session run
                # concurrently. Retry on the current object instead.
                if self._session_locks.get(session_id) is lock:
                    sess = self._sessions.pop(session_id, None)
                    if sess is None:
                        sess = self._parked.pop(session_id, None)
                    self._busy.add(session_id)
                    break
            lock.release()
        if sess is not None:
            # no-op for live sessions; parked ones re-upload their cache.
            # A failed upload (e.g. HBM RESOURCE_EXHAUSTED — the scarcity
            # that caused parking) must NOT leak the held lock: fall back
            # to a cold start and let the turn proceed.
            try:
                self.planner.unpark(sess)
            except Exception:
                import logging

                logging.getLogger("tpu_voice_agent.planner").warning(
                    "unpark failed for session %s; cold-starting", session_id,
                    exc_info=True)
                sess = None
        return sess, lock

    def _checkin(self, session_id: str | None, lock, sess) -> None:
        if lock is None:
            return
        # everything below runs with the per-session lock held; park() is a
        # blocking jax.device_get that can raise (e.g. TPU backend failure),
        # and _busy is already cleared by then — leaking the lock would
        # deadlock every future turn for this session_id, so release in a
        # finally (mirroring the unpark-failure care in _checkout).
        try:
            with self._registry:
                self._busy.discard(session_id)
                if sess is not None:
                    self._sessions[session_id] = sess
                victims = self._evict_locked()
            # park OUTSIDE the registry lock: jax.device_get of a large
            # session cache is a blocking D2H copy, and holding _registry
            # for it would stall every other session's checkout/checkin
            # (and /health)
            from ..utils import get_metrics

            parked_now = []
            for vid, vsess in victims:
                # park is best-effort offload of an ALREADY-evicted session:
                # a failure just means the victim cold-starts next turn, it
                # must not fail this request (whose plan already succeeded)
                try:
                    self.planner.park(vsess)
                except Exception:
                    import logging

                    logging.getLogger("tpu_voice_agent.planner").warning(
                        "park failed for evicted session %s; dropping "
                        "(will cold-start on its next turn)", vid,
                        exc_info=True)
                    get_metrics().inc("planner.sessions_park_failed")
                    continue
                get_metrics().inc("planner.sessions_parked")
                parked_now.append((vid, vsess))
            if parked_now:
                with self._registry:
                    for vid, vsess in parked_now:
                        # a checkout raced us and cold-started this id while
                        # we were parking: the parked copy is stale — drop it
                        if vid not in self._busy and vid not in self._sessions:
                            self._parked[vid] = vsess
                    self._drop_parked_overflow_locked()
        finally:
            lock.release()

    def _evict_locked(self) -> list[tuple[str, object]]:
        """LRU eviction by count AND by total KV-cache bytes (sessions
        mid-turn are skipped — their caches are in use on device). Returns
        the victims to PARK to host RAM; the caller runs the blocking D2H
        copies OUTSIDE the registry lock. A victim bigger than the whole
        park budget is dropped directly — paying the transfer only to
        immediately flush it (or everything else) would waste the copy."""
        from ..utils import get_metrics

        def total_bytes():
            return sum(self.planner.session_bytes(s) for s in self._sessions.values())

        victims: list[tuple[str, object]] = []
        while len(self._sessions) > self.max_sessions or (
            total_bytes() > self.hbm_budget_bytes and len(self._sessions) > 1
        ):
            victim = next((k for k in self._sessions if k not in self._busy), None)
            if victim is None:
                break  # everything live is mid-turn; nothing evictable
            sess = self._sessions.pop(victim)
            pend = getattr(sess, "pending_spec", None)
            if pend is not None:
                # evicting a session mid-speculation: undo the provisional
                # turn (its snapshot shadow-pins a second cache — parking
                # both would double the host copy, and the commit marker
                # cannot survive a cold restart anyway)
                sess.pending_spec = None
                if pend["snap"] is None:
                    # the session ONLY exists speculatively: drop it whole
                    # (parking it would preserve a turn the matching final
                    # would then record a second time)
                    get_metrics().inc("planner.sessions_evicted")
                    continue
                self._restore(sess, pend["snap"])
            get_metrics().inc("planner.sessions_evicted")
            if 0 < self.planner.session_bytes(sess) <= self.park_budget_bytes or (
                self.park_budget_bytes > 0 and self.planner.session_bytes(sess) == 0
            ):
                victims.append((victim, sess))
                # sessions_parked is counted in _checkin AFTER park()
                # succeeds — counting here would claim a park that a D2H
                # failure then silently turns into a drop
        # prune lock entries for dead sessions (never pop a HELD lock's
        # entry: a waiter still blocks on it and must reuse the same object
        # when it wakes, or two turns of one session could run concurrently)
        pending = {vid for vid, _ in victims}
        for k in list(self._session_locks):
            if (k not in self._sessions and k not in self._parked
                    and k not in self._busy and k not in pending
                    and not self._session_locks[k].locked()):
                del self._session_locks[k]
        return victims

    def _drop_parked_overflow_locked(self) -> None:
        """Oldest parked sessions drop entirely past the host budget."""
        from ..utils import get_metrics

        def parked_bytes():
            return sum(self.planner.parked_bytes(s) for s in self._parked.values())

        while self._parked and parked_bytes() > self.park_budget_bytes:
            self._parked.popitem(last=False)
            get_metrics().inc("planner.sessions_dropped")

    # ------------------------------------------------- speculative turns
    #
    # The voice service starts a /parse on the PROVISIONAL transcript while
    # the endpoint window runs out. For stateless parsers that is free; a
    # session-keyed planner COMMITS every turn, so speculation here is
    # two-phase: the speculative turn runs normally but records an undo
    # snapshot on the session. The matching final COMMITS (returns the
    # cached response, zero decode); anything else ROLLS BACK the
    # transcript first. Snapshots are host-side pointer copies — cache
    # arrays are immutable jax values (extend/plan REPLACE sess.cache, the
    # batched plan path even restores slot-0 K/V), so keeping the old refs
    # costs no copy; the shadowed old cache stays alive at most one
    # utterance window, and eviction rolls pending sessions back first.

    @staticmethod
    def _snapshot(sess) -> tuple:
        return (list(sess.ids), sess.cache, sess.pos, sess.last_logits,
                sess.anchors)

    @staticmethod
    def _restore(sess, snap) -> None:
        sess.ids, sess.cache, sess.pos, sess.last_logits, sess.anchors = (
            list(snap[0]), snap[1], snap[2], snap[3], snap[4])

    def parse(self, text: str, context: dict, session_id: str | None = None,
              speculative: bool = False) -> ParseResponse:
        from ..utils import get_metrics

        user = json.dumps({"text": text, "context": context}, separators=(",", ":"))
        sess, lock = self._checkout(session_id)
        keep = None
        try:
            pend = getattr(sess, "pending_spec", None) if sess is not None else None
            if pend is not None:
                sess.pending_spec = None
                if not speculative and pend["user"] == user:
                    # commit: the speculative turn IS this turn (same text
                    # AND same context — a context_update between spec and
                    # final must NOT deliver the old-context plan) — the
                    # session already carries it; deliver without decoding
                    get_metrics().inc("planner.spec_commits")
                    keep = sess
                    return pend["resp"]
                # superseded (speaker resumed / context changed): undo the
                # provisional turn before handling the real one
                get_metrics().inc("planner.spec_rollbacks")
                if pend["snap"] is None:
                    sess = None  # the session only existed speculatively
                else:
                    self._restore(sess, pend["snap"])
            snap = self._snapshot(sess) if (speculative and sess is not None) else None

            def fail(kind: str, detail: str, cause=None):
                # a FAILED speculative turn must never cost committed
                # history: restore the undo snapshot and keep the session
                # (the matching final re-parses from the clean transcript).
                # Failed REAL turns keep the pre-speculation semantics —
                # the session drops, because its transcript and cache may
                # be out of sync / end in malformed half-JSON.
                nonlocal keep, sess
                if speculative and snap is not None:
                    self._restore(sess, snap)
                    keep = sess
                raise ParserError(kind, detail) from cause

            try:
                if sess is None:
                    sess = self.planner.start(self.render(text, context))
                else:
                    self.planner.extend(sess, f"\n<|user|>\n{user}\n<|assistant|>\n")
                out_text, _ = self._gather.plan(sess, self.max_new_tokens)
            except ValueError as e:
                fail("llm_error", str(e), e)
            model, err = parse_response_from_json(out_text)
            if model is None:
                # truncation (token budget before EOS)
                fail("schema_validation_failed", err or "invalid")
            if speculative and session_id is not None:
                sess.pending_spec = {"user": user, "resp": model, "snap": snap}
            keep = sess
            return model
        finally:
            self._checkin(session_id, lock, keep)

    def healthy(self) -> bool:
        return self._gather.healthy()

    def session_count(self) -> int:
        with self._registry:
            return len(self._sessions)

    def session_hbm_bytes(self) -> int:
        with self._registry:
            return sum(self.planner.session_bytes(s) for s in self._sessions.values())


class RuleBasedParser:
    """Deterministic heuristic parser — offline mode + test fake.

    Covers the same command families as the prompt few-shots so the service
    contract can be exercised with zero model dependencies.
    """

    _URL = re.compile(r"(https?://\S+|\b[\w-]+\.(?:com|org|net|io|dev)\b)", re.I)

    def parse(self, text: str, context: dict) -> ParseResponse:
        t = text.strip().lower()
        intents: list[Intent] = []
        ctx_updates: dict = {}
        tts = None
        follow_up = None
        confidence = 0.9

        def add(type_: str, **kw):
            intents.append(Intent(type=type_, **kw))

        m = re.search(r"(?:search(?: for)?|find|look for)\s+(.+)", t)
        url = self._URL.search(text)
        if m:
            q = m.group(1).strip(" .!?")
            add("search", args={"query": q})
            ctx_updates["last_query"] = q
            tts = f"Searching for {q}"
        elif url and ("open" in t or "navigate" in t or "go to" in t):
            u = url.group(0)
            if not u.startswith("http"):
                u = "https://" + u
            add("navigate", args={"url": u})
            tts = f"Opening {u}"
        elif "upload" in t:
            add("upload", args={"fileRef": None}, requires_confirmation=True)
            if "submit" in t:
                add("click", target=Target(strategy="text", value="Submit"), requires_confirmation=True)
            tts = "I will upload after you confirm"
        elif (m := re.search(r"sort(?:ed)?(?: these)?(?: by)?\s+(\w+)", t)):
            direction = "desc" if ("high to low" in t or "descending" in t) else "asc"
            add("sort", args={"field": m.group(1), "direction": direction})
            tts = f"Sorting by {m.group(1)}"
        elif (m := re.search(r"open the (first|second|third|\d+\w*) (?:result|item|link)", t)):
            idx = {"first": 1, "second": 2, "third": 3}.get(m.group(1))
            if idx is None:
                idx = int(re.sub(r"\D", "", m.group(1)) or 1)
            add("click", target=Target(strategy="auto", role="link"), args={"index": idx})
            tts = f"Opening result {idx}"
        elif (m := re.search(r"click(?: on)?(?: the)?\s+(.+?)(?: button| link)?$", t)):
            add("click", target=Target(strategy="text", value=m.group(1).strip(" .!?")))
            tts = f"Clicking {m.group(1).strip(' .!?')}"
        elif "screenshot" in t:
            add("screenshot")
            tts = "Taking a screenshot"
        elif "scroll" in t:
            add("scroll", args={"direction": "up" if "up" in t else "down"})
        elif re.search(r"\bgo back\b|\bback\b", t):
            add("back")
        elif "extract" in t and "table" in t:
            add("extract_table", args={"format": "csv"})
            tts = "Extracting the table"
        elif "summarize" in t or "summary" in t:
            add("summarize")
        elif "cancel" in t:
            add("cancel")
        else:
            add("unknown")
            confidence = 0.3
            follow_up = "I did not catch a browser action - could you rephrase?"

        return ParseResponse(
            intents=intents,
            context_updates=ctx_updates,
            confidence=confidence,
            tts_summary=tts,
            follow_up_question=follow_up,
        )


# ---------------------------------------------------------------- app


def _chaos_replica_middleware():
    """Replica-level chaos points (ISSUE 10, drilled by bench_router):
    ``replica_kill`` latches this app dead — every later request on it
    (/parse AND the router's /health probes) gets an abrupt connection
    close, like a crashed process; ``replica_hang`` wedges one request for
    ``CHAOS_HANG_S``; ``replica_slow`` adds ``CHAOS_SLOW_S`` of latency to
    one request (the tail shape hedging cuts); ``replica_degrade`` (ISSUE
    14, drilled by bench_fleet) LATCHES this app persistently slow — every
    later /parse pays ``CHAOS_SLOW_S`` while /health keeps answering ok,
    the canonical gray failure the fleet detector must catch;
    ``replica_join_stall`` (ISSUE 16, drilled by bench_autopilot) wedges
    one POST /admin/handoff — the pre-warm adopt a joining replica
    receives — for ``CHAOS_HANG_S``, the stuck-join drill the autopilot's
    join timeout must contain. Parse-level points only DRAW on POST
    /parse (and the join stall only on its own route) so health probes
    never consume the deterministic ``@kth`` event counting. Chaos off
    (the default) is one dict-miss per request."""
    from ..utils.chaos import chaos_fire

    dead = {"dead": False}
    degraded = {"slow": False}

    def _drop(request: web.Request):
        # no HTTP response at all: close the TCP transport and unwind via
        # CancelledError (which aiohttp treats as a torn-down client, not
        # a handler error) — the caller sees a connection reset, exactly
        # what a killed process produces mid-request
        if request.transport is not None:
            request.transport.close()
        raise asyncio.CancelledError("chaos: replica killed")

    @web.middleware
    async def chaos_mw(request: web.Request, handler):
        if dead["dead"]:
            _drop(request)
        if request.method == "POST" and request.path == "/admin/handoff":
            # ISSUE 16, drilled by bench_autopilot: a JOINING replica
            # wedges during the pre-warm adopt — the autopilot's join
            # timeout must retire it and retry, never admit it cold
            if chaos_fire("replica_join_stall"):
                await asyncio.sleep(float(os.environ.get("CHAOS_HANG_S", "60")))
        if request.method == "POST" and request.path == "/parse":
            if chaos_fire("replica_kill"):
                dead["dead"] = True
                _drop(request)
            if chaos_fire("replica_degrade"):
                degraded["slow"] = True
            if chaos_fire("replica_hang"):
                await asyncio.sleep(float(os.environ.get("CHAOS_HANG_S", "60")))
            elif degraded["slow"] or chaos_fire("replica_slow"):
                await asyncio.sleep(float(os.environ.get("CHAOS_SLOW_S", "0.25")))
        return await handler(request)

    return chaos_mw


def build_app(parser: IntentParser, tracer: Tracer | None = None,
              max_inflight: int | None = None) -> web.Application:
    tracer = tracer or Tracer("brain", emit=False)
    app = web.Application(middlewares=[_chaos_replica_middleware()])
    # a client that disconnects must CANCEL its handler (aiohttp >= 3.9
    # made this opt-in): the CancelledError hook below is what aborts the
    # request's in-flight decode at the next chunk boundary — without
    # cancellation a dead socket burns the slot's whole token budget
    from . import HANDLER_CANCELLATION

    app[HANDLER_CANCELLATION] = True
    # admission control: past the inflight cap /parse answers 503 +
    # Retry-After instead of queueing unboundedly behind the decode (the
    # queue IS the tail latency; the voice service degrades on the 503)
    admission = AdmissionController(
        "brain",
        max_inflight if max_inflight is not None
        else int(os.environ.get("BRAIN_MAX_INFLIGHT", "32")))
    # A single-slot engine owns one KV cache and RNG, so concurrent parses
    # must serialize. A concurrent-safe parser (BatchedEngineParser) does
    # its own admission control — requests run truly concurrently, sharing
    # decode chunks on device.
    if getattr(parser, "concurrent_safe", False):
        locked_parse = parser.parse
        # aiohttp's default executor caps at min(32, cpus+4) threads; each
        # parse blocks a thread in fut.result(), so the pool must cover the
        # engine's batch width or the batcher never fills its slots
        slots = getattr(getattr(parser, "engine", None), "batch_slots", 8)
        from concurrent.futures import ThreadPoolExecutor

        parse_pool = ThreadPoolExecutor(
            max_workers=max(8, slots + 4), thread_name_prefix="parse"
        )
    else:
        parse_pool = None
        parse_lock = threading.Lock()

        def locked_parse(*args) -> ParseResponse:
            with parse_lock:
                return parser.parse(*args)

    # per-request /parse latency + error budget against the SLO targets
    slo = SLOTracker("brain")
    wants_session = getattr(parser, "wants_session", False)
    # stateless parsers are trivially speculation-safe (parse is pure);
    # session-keyed ones must OPT IN with two-phase turns (PlannerParser)
    spec_ok = getattr(parser, "supports_speculation", not wants_session)

    # quality observatory (ISSUE 15): the per-replica monitor is bound to
    # the TRACER-LOCAL registry so its gauges stay per-replica even in the
    # in-process multi-replica harnesses (the fleet detector compares them
    # across the ring via each replica's timeseries ring), plus the
    # ``intent_downgrade`` chaos latch — this replica answers a degraded
    # rule-fallback "unknown" plan from the firing parse on (fast, healthy-
    # looking, quality on the floor: the fault class only the quality SLO /
    # golden canary / gray detector can see)
    from ..utils.quality import (
        GoldenCanary,
        QualityMonitor,
        make_quality_handler,
    )

    qmon = QualityMonitor("brain", metrics=tracer.metrics)
    # the downgrade counter exists from construction (scrape-visible at
    # zero; this literal is what the metrics lint pins — the latch below
    # counts through the monitor's ledger)
    qmon.metrics.inc("quality.intent_downgrades", 0.0)
    downgraded = {"on": False}

    def do_parse(preq: ParseRequest) -> ParseResponse:
        from ..utils.chaos import chaos_fire

        if downgraded["on"] or chaos_fire("intent_downgrade"):
            downgraded["on"] = True
            qmon._count("quality.intent_downgrades")
            return ParseResponse(
                intents=[Intent(type="unknown")], confidence=0.1,
                follow_up_question="I did not catch a browser action - "
                                   "could you rephrase?")
        if wants_session:
            if spec_ok:
                return locked_parse(preq.text, preq.context, preq.session_id,
                                    preq.speculative)
            return locked_parse(preq.text, preq.context, preq.session_id)
        if getattr(parser, "session_costs", None) is not None:
            # stateless ENGINE parsers still attribute spend per session
            # (ISSUE 17): the id rides only into the cost-ledger fold —
            # decode keeps the pure stateless parse(text, context) contract
            return locked_parse(preq.text, preq.context, preq.session_id)
        return locked_parse(preq.text, preq.context)

    # golden-replay canary (ISSUE 15, QUALITY_CANARY_S > 0): replay a
    # rotating slice of the held-out golden cases through the LIVE parser
    # (the same do_parse the traffic and the downgrade latch go through)
    # during idle cycles — admission-gated on this replica's own occupancy
    # so it never steals decode steps from real traffic
    from ..utils.knobs import knob_float

    canary_occ = knob_float("QUALITY_CANARY_OCCUPANCY", 0.5)

    def _canary_busy() -> bool:
        if admission.inflight > 0:
            return True
        live = getattr(parser, "pressure_fractions", None)
        if live is not None:
            try:
                fr = live()
                return bool(fr) and max(fr.values()) >= canary_occ
            except Exception:
                return False
        return False

    canary = GoldenCanary(
        lambda text, ctx: do_parse(ParseRequest(text=text, context=ctx)),
        qmon, busy_fn=_canary_busy)

    async def _canary_start(_app) -> None:
        canary.start()

    async def _canary_stop(_app) -> None:
        canary.stop()

    app.on_startup.append(_canary_start)
    app.on_cleanup.append(_canary_stop)

    # graceful drain (ISSUE 10): POST /admin/drain latches this replica
    # draining; the router (services/router.py) sees the flag in /health,
    # stops placing NEW sessions here, and ejects once in-flight work is
    # done — a rolling restart with zero dropped requests. ``drained`` is
    # COMPUTED, not latched: the serve-layer hook (ColocatedServing) knows
    # when both lanes are empty; parsers without one fall back to the
    # admission inflight count.
    drain_state = {"draining": False}

    def _drained() -> bool:
        if not drain_state["draining"]:
            return False
        probe = getattr(parser, "drained", None)
        if probe is not None:
            return bool(probe())
        return admission.inflight == 0

    async def admin_drain(_req: web.Request) -> web.Response:
        if not drain_state["draining"]:
            drain_state["draining"] = True
            get_metrics().inc("brain.drains_received")
            hook = getattr(parser, "begin_drain", None)
            if hook is not None:
                hook()
        return web.json_response({"ok": True, "draining": True,
                                  "drained": _drained()})

    async def health(_req: web.Request) -> web.Response:
        """ok / degraded (saturated but serving) / unhealthy (dead worker)."""
        body = {"ok": True, "service": "brain",
                "inflight": admission.inflight,
                "max_inflight": admission.max_inflight,
                # disagg pool membership (ISSUE 20): BRAIN_ROLE tags this
                # replica prefill/decode/both; the router's prober reads it
                # off this field and places accordingly when ROUTER_DISAGG
                # is on (and ignores it entirely when off)
                "role": os.environ.get("BRAIN_ROLE", "both"),
                "disagg": bool(getattr(parser, "supports_disagg", False))}
        if drain_state["draining"]:
            body["draining"] = True
            body["drained"] = _drained()
        status = "ok"
        if admission.saturated:
            status = "degraded"  # shedding load, but alive
        probe = getattr(parser, "healthy", None)
        if probe is not None:
            body["worker_alive"] = bool(probe())
            if not body["worker_alive"]:
                status = "unhealthy"
        qinfo = getattr(parser, "quarantine_info", None)
        if qinfo is not None:
            # repeat-offender poison quarantine (serve.scheduler): prompts
            # refused at submit after repeated NaN/dead-FSM/prefill faults
            body["quarantine"] = qinfo()
        # the engine microscope (ISSUE 9): recompilation-sentinel state —
        # a compile after the warmup fence is the shape-churn p99 cliff,
        # surfaced here as an alertable ``warning`` line — plus the last
        # step ledger entry and the live HBM gauges, so one /health scrape
        # answers "where did the last chunk's time go and does memory
        # still match the plan"
        from ..utils import get_compile_watcher
        from ..utils.steplog import get_steplog

        body["compile_sentinel"] = get_compile_watcher().state()
        last_step = get_steplog().last()
        if last_step is not None:
            body["last_step"] = last_step
        hbm = {k: v for k, v in get_metrics().gauges().items()
               if k.startswith("hbm.")}
        if hbm:
            body["hbm"] = hbm
        body["status"] = status
        body["ok"] = status != "unhealthy"
        body["slo"] = slo.state()
        # the quality observatory block (ISSUE 15): windowed golden/margin/
        # degraded means + the quality-SLO verdict — forwarded through the
        # router and the voice /health to the web HUD's quality badge
        body["quality"] = qmon.health()
        # the shed signal (ISSUE 13): the observatory's saturation signals
        # (batch occupancy, KV utilization, admission fraction) folded to
        # one score the router's prober reads — NEW sessions avoid
        # replicas at/over ROUTER_SHED_PRESSURE before this replica's
        # admission controller starts refusing. Read LIVE from the parser
        # (pressure_fractions), not from the last-tick gauges: an idle
        # engine's gauges freeze at their final busy value, and a frozen
        # 1.0 would shed traffic off an empty replica forever. SLO trumps
        # occupancy: a violated SLO is full by definition.
        live = getattr(parser, "pressure_fractions", None)
        fracs = {}
        if live is not None:
            try:
                fracs = {k: round(float(v), 4) for k, v in live().items()}
            except Exception:
                fracs = {}
        fracs["admission"] = round(
            admission.inflight / max(1, admission.max_inflight), 4)
        score = max(fracs.values())
        if body["slo"] == "violated":
            score = 1.0
        elif body["slo"] == "at_risk":
            score = max(score, 0.95)
        body["pressure"] = {"score": round(score, 4), "slo": body["slo"],
                            **fracs}
        return web.json_response(body, status=200 if body["ok"] else 503)

    async def parse(req: web.Request) -> web.Response:
        # the SLO sample covers the WHOLE request (queue + decode), and a
        # 5xx — shed, deadline, engine crash — burns error budget; 4xx are
        # semantic answers about the request, not service health
        t_req0 = time.perf_counter()
        resp = await _parse_inner(req, t_req0)
        slo.record((time.perf_counter() - t_req0) * 1e3, ok=resp.status < 500)
        return resp

    async def _parse_inner(req: web.Request, t_req0: float) -> web.Response:
        trace_id = req.headers.get("x-trace-id", new_trace_id())
        headers = {"x-trace-id": trace_id}
        try:
            body = await req.json()
        except json.JSONDecodeError:
            return web.json_response(
                {"error": "invalid_request", "detail": "body must be JSON"},
                status=400, headers=headers,
            )
        try:
            preq = ParseRequest.model_validate(body)
        except Exception as e:
            return web.json_response(
                {"error": "invalid_request", "detail": str(e)[:500]},
                status=400, headers=headers,
            )
        if preq.speculative and not spec_ok:
            # a session-keyed backend that COMMITS every turn cannot parse
            # a transcript the endpoint may still revise. Refuse fast — the
            # voice service falls back to parsing at final time. (The
            # PlannerParser opts in via two-phase commit/rollback turns.)
            return web.json_response(
                {"error": "speculation_unsupported",
                 "detail": "session-keyed backend commits turns; parse at final"},
                status=409, headers=headers,
            )
        if preq.prefix_feed and not getattr(parser, "supports_prefix_feed",
                                            False):
            # prefix feeds (ISSUE 19) only make sense against an engine
            # batcher with a prefill-only admission path; other backends
            # refuse fast and the voice service latches feeds off for the
            # connection (mirroring the speculation 409 above)
            return web.json_response(
                {"error": "prefix_feed_unsupported",
                 "detail": "backend has no prefill-only admission path"},
                status=409, headers=headers,
            )

        def shed(reason: str, retry_after_s: float = 1.0) -> web.Response:
            return shed_response("brain", reason, headers=headers,
                                 retry_after_s=retry_after_s)

        deadline = Deadline.from_headers(req.headers)
        if deadline is not None and deadline.expired:
            # the caller already gave up: answering with work would burn
            # decode on a response nobody reads
            return shed("deadline_expired", retry_after_s=0)
        if not admission.try_acquire():
            return shed("overload")
        loop = asyncio.get_running_loop()
        from ..utils.resilience import (
            RequestContext,
            pop_request_context,
            push_request_context,
        )
        from ..utils.tracing import pop_stage_notes

        notes: dict = {}
        # the per-request containment handle: carries the deadline into the
        # scheduler and collects the decode canceller, so a client that
        # disconnects (CancelledError below) aborts its in-flight decode at
        # the next chunk boundary instead of burning the slot for a dead
        # socket. The tenant tag (ISSUE 18) rides the same handle: body
        # field first (the voice service sets it), x-tenant header as the
        # router/raw-HTTP fallback.
        ctx = RequestContext(
            deadline, tenant=preq.tenant or req.headers.get("x-tenant"))

        if preq.prefix_feed:
            # prefill-only admission (ISSUE 19): cache warming, not a parse
            # — no decode, no transcript commit, no quality record. A shed
            # ({"ok": False, ...}) is a 200: the feed contract is
            # best-effort and the voice service never retries one.
            def run_feed() -> dict:
                if deadline is not None and deadline.expired:
                    raise DeadlineExpired("budget consumed while queued")
                push_request_context(ctx)
                try:
                    return parser.feed_prefix(preq.text, preq.context,
                                              preq.session_id)
                finally:
                    pop_request_context()

            try:
                out = await loop.run_in_executor(parse_pool, run_feed)
            except asyncio.CancelledError:
                ctx.cancel()
                raise
            except DeadlineExpired:
                return shed("deadline_expired", retry_after_s=0)
            except Exception as e:
                return web.json_response(
                    {"error": "llm_error", "detail": str(e)[:500]},
                    status=500, headers=headers)
            finally:
                admission.release()
            return web.json_response({"prefix_feed": True, **out},
                                     headers=headers)

        def run_admitted(preq: ParseRequest) -> ParseResponse:
            # queue_ms: arrival -> worker-thread start (thread pool + engine
            # lock wait) — the queue/prefill/decode split traceview derives
            notes["queue_ms"] = round((time.perf_counter() - t_req0) * 1e3, 3)
            # re-check on the worker thread: queueing for the pool (or the
            # engine lock) may have consumed the rest of the budget — shed
            # BEFORE decode, not after
            if deadline is not None and deadline.expired:
                raise DeadlineExpired("budget consumed while queued")
            pop_stage_notes()  # drop stale notes from a prior request
            push_request_context(ctx)
            try:
                out = do_parse(preq)
            finally:
                pop_request_context()
            # engine backends deposit prefill_ms/decode_ms on THIS thread
            notes.update(pop_stage_notes())
            return out

        try:
            with tracer.span("parse", trace_id=trace_id, chars=len(preq.text)) as sp:
                resp = await loop.run_in_executor(parse_pool, run_admitted, preq)
                sp.attrs.update(notes)
        except asyncio.CancelledError:
            # client disconnect mid-parse: fire the registered cancellers
            # (mid-decode cancellation in the scheduler) before unwinding
            ctx.cancel()
            get_metrics().inc("brain.parses_cancelled")
            raise
        except DeadlineExpired:
            return shed("deadline_expired", retry_after_s=0)
        except ParserError as e:
            if e.kind == "overloaded":
                # typed engine-plane shed (KV pool exhausted / queue-expired
                # deadline): same 503 + Retry-After contract as admission
                # sheds, so the voice retry/degrade kit handles it
                return shed("engine_overload")
            status = 422 if e.kind == "schema_validation_failed" else 500
            return web.json_response(
                {"error": e.kind, "detail": e.detail[:500]}, status=status,
                headers={"x-trace-id": trace_id},
            )
        except Exception as e:  # engine crash etc.
            return web.json_response(
                {"error": "llm_error", "detail": str(e)[:500]}, status=500,
                headers={"x-trace-id": trace_id},
            )
        finally:
            admission.release()
        # the quality observatory's per-parse record: engine backends
        # deposited the confidence vector as stage notes; rule/planner
        # parses record structurally (degraded-rate window, parse counts)
        qmon.record_intent(
            margin=notes.get("intent_margin"),
            entropy=notes.get("intent_entropy"),
            forced_frac=notes.get("intent_forced_frac"),
            downgraded=downgraded["on"],
            text=preq.text)
        ok_headers = {"x-trace-id": trace_id}
        # the decode split as response headers: the voice service folds them
        # into the utterance's latency_budget stages so the web HUD can show
        # computed-prefill / decode / cache-absorbed-tokens, not just a flat
        # parse_ms (engine backends deposit these as stage notes; rule-based
        # and planner parses simply have none). prompt_tokens rides along —
        # with cached_tokens it is the voice-side outstanding-prefill-at-
        # endpoint measurement; intent_margin feeds the voice HUD badge.
        for note, header in (("prefill_ms", "x-prefill-ms"),
                             ("decode_ms", "x-decode-ms"),
                             ("queue_ms", "x-queue-ms"),
                             ("cached_tokens", "x-cached-tokens"),
                             ("prompt_tokens", "x-prompt-tokens"),
                             ("intent_margin", "x-intent-margin")):
            if note in notes:
                ok_headers[header] = str(notes[note])
        # (speculative implies spec_ok here — the 409 gate already fired)
        if preq.speculative and wants_session and preq.session_id:
            # this turn is PENDING on the session (two-phase): the caller
            # must send the matching non-speculative parse to COMMIT it
            # (zero decode — the cached response comes back), or the next
            # turn rolls it back. The voice service routes its endpoint
            # confirmation through exactly that commit when it sees this.
            ok_headers["x-speculation-pending"] = "1"
        return web.json_response(resp.model_dump(), headers=ok_headers)


    # warm-state handoff endpoints (ISSUE 13): the router GETs a re-homed
    # session's serialized warm state from its old home and POSTs it to
    # the new one (serve.handoff wire format). Parsers without the surface
    # (rule-based, planner) answer 404 and the router counts a cold
    # re-home — the PR 10 behavior, unchanged.
    async def admin_handoff_get(req: web.Request) -> web.Response:
        exporter = getattr(parser, "export_session", None)
        if exporter is None:
            return web.json_response({"error": "handoff_unsupported"},
                                     status=404)
        sid = req.match_info["session_id"]
        loop = asyncio.get_running_loop()
        blob = await loop.run_in_executor(None, exporter, sid)
        if not blob:
            return web.json_response(
                {"error": "no_warm_state", "session_id": sid}, status=404)
        return web.Response(body=blob,
                            content_type="application/octet-stream")

    # a shipped session is transcript ids + raw KV block bytes — tens of
    # MB at serving dims, far past aiohttp's 1 MB default body cap. The
    # cap stays app-wide (a 256 MB client_max_size would let /parse
    # buffer multi-GB of hostile bodies before admission control runs);
    # only THIS route reads the raw stream with its own bound.
    _HANDOFF_MAX_BYTES = 256 * 1024 * 1024

    async def admin_handoff_post(req: web.Request) -> web.Response:
        adopter = getattr(parser, "adopt_session", None)
        if adopter is None:
            return web.json_response({"error": "handoff_unsupported"},
                                     status=404)
        chunks: list[bytes] = []
        total = 0
        while True:
            chunk = await req.content.read(1 << 20)
            if not chunk:
                break
            total += len(chunk)
            if total > _HANDOFF_MAX_BYTES:
                return web.json_response(
                    {"error": "handoff_too_large",
                     "limit_bytes": _HANDOFF_MAX_BYTES}, status=413)
            chunks.append(chunk)
        blob = b"".join(chunks)
        from ..serve import handoff as _frames

        if blob.startswith(_frames.FRAME_MAGIC):
            # HANDOFF_FRAMED wire (ISSUE 20): the SAME warm blob shipped as
            # sequence-numbered parts. Sniffed, never negotiated — a raw
            # TVAH1 blob takes the unchanged path, and a torn/reordered
            # frame body is a COUNTED clean cold fallback, not an install
            # of torn bytes.
            try:
                blob = _frames.deframe(blob)
            except ValueError as e:
                get_metrics().inc("handoff.adopt_fallbacks")
                return web.json_response(
                    {"ok": True, "adopted_tokens": 0,
                     "reason": f"bad frames: {e}"})
        loop = asyncio.get_running_loop()
        adopted = await loop.run_in_executor(None, adopter, blob)
        return web.json_response({"ok": True,
                                  "adopted_tokens": int(adopted)})

    # disagg KV stream endpoints (ISSUE 20). /admin/disagg/prefill runs a
    # prefill-only EXPORT admission and answers a chunked body of
    # sequence-numbered frames — kv_seg segments as the chain computes,
    # then a kv_end summary on the FINAL frame. A shed before any segment
    # answers plain JSON (no stream to tear). /admin/disagg/adopt installs
    # one forwarded blob per POST into the stream's adopter.
    async def admin_disagg_prefill(req: web.Request) -> web.Response:
        exporter = getattr(parser, "disagg_prefill", None)
        if exporter is None:
            return web.json_response({"error": "disagg_unsupported"},
                                     status=404)
        from ..serve import handoff as _frames

        try:
            body = await req.json()
        except json.JSONDecodeError:
            return web.json_response(
                {"error": "invalid_request", "detail": "body must be JSON"},
                status=400)
        text = str(body.get("text") or "")
        context = body.get("context") or {}
        sid = body.get("session_id") or None
        stream_id = str(body.get("stream") or new_trace_id())
        stream_blocks = max(1, int(body.get("stream_blocks") or 4))
        loop = asyncio.get_running_loop()
        q: asyncio.Queue = asyncio.Queue()

        def emit(blob: bytes) -> None:
            # called from the serving-loop thread mid-prefill: bridge each
            # gathered segment onto the event loop without blocking compute
            loop.call_soon_threadsafe(q.put_nowait, blob)

        fut = loop.run_in_executor(parse_pool, lambda: exporter(
            text, context, sid, stream_blocks=stream_blocks, emit=emit,
            stream_id=stream_id))
        fut.add_done_callback(lambda _f: q.put_nowait(None))
        first = await q.get()
        if first is None:
            # export finished before any segment shipped: shed / too_long /
            # tiny prompt — answer JSON, the router falls back or proceeds
            try:
                out = fut.result()  # analyze: ok[async-blocking] -- the None sentinel only enters the queue from fut's done callback, so the future is already resolved
            except Exception as e:
                out = {"ok": False, "reason": f"{type(e).__name__}: {e}"}
            return web.json_response({"disagg_prefill": True, **(out or {})})
        from ..utils.chaos import chaos_fire

        resp = web.StreamResponse(
            status=200, headers={"content-type": "application/x-tva-frames",
                                 "x-disagg-stream": stream_id})
        resp.enable_chunked_encoding()
        await resp.prepare(req)
        seq = 0
        item: bytes | None = first
        while item is not None:
            # satellite drill (prefill_replica_kill): the prefill replica
            # dies MID-KV-STREAM — between frame writes, after earlier
            # segments already landed — the decode home must serve the
            # parse clean-or-cold off whatever partial frontier arrived
            if chaos_fire("prefill_replica_kill"):
                if req.transport is not None:
                    req.transport.close()
                raise asyncio.CancelledError("chaos: prefill replica killed")
            await resp.write(_frames.frame_pack(seq, item))
            seq += 1
            item = await q.get()
        try:
            out = fut.result()  # analyze: ok[async-blocking] -- the None sentinel only enters the queue from fut's done callback, so the future is already resolved
        except Exception as e:
            out = {"ok": False, "reason": f"{type(e).__name__}: {e}"}
        summary = {k: v for k, v in (out or {}).items()
                   if k in ("ok", "reason", "prompt_tokens", "cached_tokens",
                            "chain_tokens", "segments")}
        await resp.write(_frames.frame_pack(
            seq, _frames.pack_kv_end(stream_id, summary), final=True))
        await resp.write_eof()
        return resp

    async def admin_disagg_adopt(req: web.Request) -> web.Response:
        adopter = getattr(parser, "adopt_stream", None)
        if adopter is None:
            return web.json_response({"error": "disagg_unsupported"},
                                     status=404)
        stream_id = req.headers.get("x-disagg-stream")
        if not stream_id:
            return web.json_response(
                {"error": "invalid_request",
                 "detail": "x-disagg-stream header required"}, status=400)
        chunks: list[bytes] = []
        total = 0
        while True:
            chunk = await req.content.read(1 << 20)
            if not chunk:
                break
            total += len(chunk)
            if total > _HANDOFF_MAX_BYTES:
                return web.json_response(
                    {"error": "handoff_too_large",
                     "limit_bytes": _HANDOFF_MAX_BYTES}, status=413)
            chunks.append(chunk)
        loop = asyncio.get_running_loop()
        out = await loop.run_in_executor(None, adopter, stream_id,
                                         b"".join(chunks))
        return web.json_response(out)

    app.router.add_get("/health", health)
    app.router.add_get("/admin/handoff/{session_id}", admin_handoff_get)
    app.router.add_post("/admin/handoff", admin_handoff_post)
    app.router.add_post("/admin/disagg/prefill", admin_disagg_prefill)
    app.router.add_post("/admin/disagg/adopt", admin_disagg_adopt)
    from ..utils.tracing import (
        make_flightrecorder_handler,
        make_metrics_handler,
        make_trace_handler,
    )

    app.router.add_get("/metrics", make_metrics_handler("brain", tracer, slo=slo))
    app.router.add_get("/debug/trace/{trace_id}", make_trace_handler("brain", tracer))
    app.router.add_get("/debug/flightrecorder", make_flightrecorder_handler("brain"))
    from ..utils.steplog import make_steplog_handler

    app.router.add_get("/debug/steplog", make_steplog_handler("brain"))
    app.router.add_get("/debug/quality", make_quality_handler(qmon))

    async def debug_costs(request: web.Request) -> web.Response:
        # cost & efficiency observatory (ISSUE 17): the engine meter's
        # analytic totals + live MFU/MBU, and the per-session attribution
        # rollup. Shape is the /debug/costs schema OBSERVABILITY.md pins.
        meter = getattr(getattr(parser, "batcher", None), "costs", None)
        body: dict = {"service": "brain", "enabled": meter is not None}
        if meter is not None:
            body.update(meter.summary())
        sessions = getattr(parser, "session_costs", None)
        if sessions is not None:
            try:
                top_n = int(request.query.get("top", "8"))
            except ValueError:
                top_n = 8
            body["sessions"] = len(sessions)
            body["top_sessions"] = sessions.top(max(1, min(top_n, 64)))
        # tenant rollup (ISSUE 18): per-lane occupancy/fairness state plus
        # the session ledgers re-rolled by tenant class — absent entirely
        # when the tenancy plane is off
        tenancy = getattr(getattr(parser, "batcher", None), "tenancy", None)
        if tenancy is not None:
            body["tenants"] = tenancy.snapshot()
        return web.json_response(body)

    app.router.add_get("/debug/costs", debug_costs)
    from ..utils.timeseries import attach_timeseries

    attach_timeseries(app, "brain", tracer)
    app.router.add_post("/parse", parse)
    app.router.add_post("/admin/drain", admin_drain)
    return app


def _wrap_batched(engine) -> "BatchedEngineParser":
    """ONE place reading the batched-serving env contract (BRAIN_PREFIX /
    BRAIN_CHUNK) for every engine flavor put behind the batcher. An engine
    carrying a radix tree (PagedDecodeEngine under RADIX_ENABLE=1) gets the
    session-aware transcript rendering — multi-turn prompts become strict
    token extensions, which is what the tree matches on. Dense engines stay
    stateless: without block-level reuse, an extended transcript would only
    LENGTHEN their per-request suffix prefill."""
    if os.environ.get("BRAIN_PREFIX", "1") != "0":
        install_prompt_prefix(engine)
    return BatchedEngineParser(engine,
                               chunk_steps=int(os.environ.get("BRAIN_CHUNK", "16")),
                               session_aware=engine.radix is not None)


def _wrap_engine(engine) -> IntentParser:
    """Prefix-cache the shared prompt head, then pick the serving shape:
    BRAIN_BATCH>1 puts the continuous batcher behind /parse (concurrent
    requests share decode chunks); otherwise the serialized single-slot
    parser. BRAIN_PREFIX=0 disables the prefix cache (debugging)."""
    if engine.batch_slots > 1:
        return _wrap_batched(engine)
    if os.environ.get("BRAIN_PREFIX", "1") != "0":
        install_prompt_prefix(engine)
    return EngineParser(engine)


def make_parser_from_env() -> IntentParser:
    """BRAIN_BACKEND=rule (default) | engine[:preset] | planner[:preset].
    BRAIN_MODEL=<HF checkpoint dir> overrides both: the engine serves the
    checkpoint's weights with its own tokenizer (the real replacement for
    the reference's LLM_BASE_URL/LLM_MODEL env, apps/brain/src/llm.ts:7-9).
    BRAIN_QUANT=int8 enables weight-only quantization for the loaded model.
    BRAIN_BATCH=N (default 1) serves N continuous-batching slots.
    RADIX_ENABLE=1 (paged engines only, read at engine construction) turns
    on the radix KV session cache (serve.radix): the batched parser goes
    session-aware — multi-turn prompts become strict token extensions that
    the tree admits with O(new utterance) prefill. RADIX_MAX_NODES caps the
    tree, RADIX_SESSIONS the host transcript LRU (docs/PERF.md "Session KV
    reuse"). Unset keeps the stateless path byte-identical."""
    import logging

    log = logging.getLogger("tpu_voice_agent.brain")
    slots = int(os.environ.get("BRAIN_BATCH", "1"))
    # grammar fast-forward (BRAIN_FF=0 disables): serves at ANY batch width
    # on the dense AND paged engines — chain steps run the frontier-read
    # block kernels (round-3's single-slot restriction is lifted)
    ff = int(os.environ.get("BRAIN_FF", "8"))
    paged = os.environ.get("BRAIN_PAGED") == "1"
    quant = os.environ.get("BRAIN_QUANT") or None
    moe = "grouped" if os.environ.get("BRAIN_MOE") == "grouped" else None

    def warn_unused(backend_name: str, **knobs) -> None:
        for name, val in knobs.items():
            if val:
                log.warning("%s is not supported by the %s backend; ignoring",
                            name, backend_name)

    model_dir = os.environ.get("BRAIN_MODEL")
    if model_dir:
        from ..serve import DecodeEngine, PagedDecodeEngine

        if paged:
            # classmethod polymorphism: from_hf builds cls(...), so the
            # paged engine loads checkpoints through the same loader
            pool = int(os.environ.get("BRAIN_POOL_BLOCKS", "0")) or None
            eng = PagedDecodeEngine.from_hf(
                model_dir, quant=quant, batch_slots=max(slots, 1),
                moe_impl=moe, pool_blocks=pool)
            return _wrap_batched(eng)
        return _wrap_engine(DecodeEngine.from_hf(model_dir, quant=quant,
                                                 batch_slots=slots, fast_forward=ff,
                                                 moe_impl=moe))
    backend = os.environ.get("BRAIN_BACKEND", "rule")
    if backend == "rule":
        warn_unused("rule", BRAIN_PAGED=paged, BRAIN_QUANT=quant, BRAIN_MOE=moe)
        return RuleBasedParser()
    if backend.startswith("distilled"):
        # the in-tree trained intent checkpoint through the real constrained
        # engine (zero-egress neural serving, VERDICT round-4 next #5):
        # BRAIN_BACKEND=distilled[:<dir>], default checkpoints/<INTENT_CKPT>
        from ..models.llama import LlamaConfig
        from ..train import distill

        warn_unused("distilled", BRAIN_PAGED=paged, BRAIN_QUANT=quant,
                    BRAIN_MOE=moe)
        path = (backend.split(":", 1)[1] if ":" in backend
                else os.path.join("checkpoints", distill.INTENT_CKPT))
        loaded = distill.load_ckpt_path(path, LlamaConfig)
        if loaded is None:
            raise ValueError(f"no distilled intent checkpoint at {path} "
                             "(run python -m tpu_voice_agent.train.make_tiny_ckpts)")
        return distill.intent_engine_from(*loaded)
    if backend.startswith("engine"):
        from ..serve import DecodeEngine, PagedDecodeEngine

        preset = backend.split(":", 1)[1] if ":" in backend else "tinyllama-1.1b"
        cfg = None
        if moe:
            # Pallas grouped-matmul MoE dispatch (FLOPs ∝ K not E) for
            # single-device MoE serving; no-op for dense models
            from dataclasses import replace as _replace

            from ..models.llama import PRESETS as _PRESETS

            cfg = _replace(_PRESETS[preset], moe_impl="grouped")
        if paged:
            # paged KV pool behind the batcher: HBM tracks live tokens, the
            # shared prompt prefix is stored once, BRAIN_POOL_BLOCKS sizes
            # the pool (default: dense worst case)
            pool = int(os.environ.get("BRAIN_POOL_BLOCKS", "0")) or None
            return _wrap_batched(PagedDecodeEngine(
                preset=preset, cfg=cfg, batch_slots=max(slots, 1),
                pool_blocks=pool, quant=quant, fast_forward=ff))
        return _wrap_engine(DecodeEngine(preset=preset, cfg=cfg, batch_slots=slots,
                                         fast_forward=ff, quant=quant))
    if backend.startswith("pp"):
        # TP×PP pipelined engine (the 70B planner serving layout): layers
        # pipeline over pp, each stage tensor-parallel over tp.
        # BRAIN_PP / BRAIN_TP size the axes (default pp=2, tp = rest).
        import jax

        from ..parallel.pipeline import pp_tp_mesh
        from ..serve import PPDecodeEngine

        warn_unused("pp", BRAIN_PAGED=paged, BRAIN_MOE=moe)
        preset = backend.split(":", 1)[1] if ":" in backend else "tinyllama-1.1b"
        ndev = len(jax.devices())
        pp = int(os.environ.get("BRAIN_PP", "0")) or min(2, ndev)
        tp = int(os.environ.get("BRAIN_TP", "0")) or max(1, ndev // pp)
        # ff defaults OFF here, unlike every other engine: the round-5
        # on-chip capture measured fast-forward HURTING the staged layout
        # (219.6 -> 135.5 tok/s, 6.4 -> 4.8 intents/s; BENCH_tpu_20260731_
        # 031554.json) — the wide (B, 1+W) step multiplies the per-stage
        # fill-drain bubble where the dense/paged layouts ride it free.
        # CPU measured the opposite (+14%), so the knob stays available.
        ppff = int(os.environ.get("BRAIN_FF", "0"))  # analyze: ok[env-knob] -- deliberate per-backend default: ff measured HURTING the staged pp layout (see comment above); every other backend keeps the declared default 8
        return _wrap_batched(PPDecodeEngine(preset=preset, mesh=pp_tp_mesh(pp, tp),
                                            batch_slots=slots, quant=quant,
                                            fast_forward=ppff))
    if backend.startswith("planner-distilled"):
        # the in-tree trained intent checkpoint behind the SESSION-KEYED
        # planner: multi-turn transcripts with the distilled short prompt
        # (round-4 VERDICT next #8 — multi-turn quality through the planner
        # with a trained model). BRAIN_BACKEND=planner-distilled[:<dir>]
        import jax

        from ..models.llama import LlamaConfig
        from ..parallel.ring import sp_mesh
        from ..serve import LongSessionPlanner
        from ..train import distill

        warn_unused("planner-distilled", BRAIN_PAGED=paged, BRAIN_QUANT=quant,
                    BRAIN_MOE=moe)
        path = (backend.split(":", 1)[1] if ":" in backend
                else os.path.join("checkpoints", distill.INTENT_CKPT))
        loaded = distill.load_ckpt_path(path, LlamaConfig)
        if loaded is None:
            raise ValueError(f"no distilled intent checkpoint at {path} "
                             "(run python -m tpu_voice_agent.train.make_tiny_ckpts)")
        cfg, params = loaded
        sp = int(os.environ.get("BRAIN_SP", "0")) or len(jax.devices())
        # ff stays at the planner's own default (OFF): forced-chain
        # emission rewrites the token history into canonical runs and the
        # trained model derails at later free choices (measured: every
        # golden dialog truncates mid-string under ff=8, all pass under
        # ff=0 — exactly the divergence the planner docstring warns about)
        planner = LongSessionPlanner(cfg=cfg, mesh=sp_mesh(sp),
                                     ctx_buckets=(512, 1024, 2048))
        planner.load_params(params)
        return PlannerParser(planner, render=distill.distilled_prompt)
    if backend.startswith("planner"):
        # long-session transcripts as model context; BRAIN_SP sizes the
        # sequence-parallel axis (default: every visible device)
        import jax

        from ..parallel.ring import sp_mesh
        from ..serve import LongSessionPlanner

        warn_unused("planner", BRAIN_PAGED=paged, BRAIN_QUANT=quant, BRAIN_MOE=moe)
        preset = backend.split(":", 1)[1] if ":" in backend else "tinyllama-1.1b"
        sp = int(os.environ.get("BRAIN_SP", "0")) or len(jax.devices())
        return PlannerParser(LongSessionPlanner(preset=preset, mesh=sp_mesh(sp)))
    raise ValueError(f"unknown BRAIN_BACKEND {backend!r}")


def main() -> None:
    load_env_cascade()
    from ..utils.compilecache import place_compile_cache

    place_compile_cache()
    # multi-host engines (70B-planner-class meshes spanning hosts): join the
    # DCN job before any JAX call; single-host runs no-op (multihost.py)
    from ..parallel.multihost import init_multihost

    init_multihost()
    port = int(os.environ.get("BRAIN_PORT", "8090"))
    parser = make_parser_from_env()
    from . import warm_up

    warm_up(parser)
    app = build_app(parser, Tracer("brain"))
    web.run_app(app, port=port, handler_cancellation=True)


if __name__ == "__main__":
    main()
