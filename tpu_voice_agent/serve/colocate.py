"""Multi-model colocation: Whisper STT + Llama intent decode on one mesh.

SURVEY.md §7 step 6 and hard part (3): the voice pipeline needs BOTH models
resident at once — streaming STT chunks arrive every ~250 ms while intent
decodes run continuously — and the reference simply pays two cloud vendors
for this (Deepgram + OpenAI; apps/voice/src/deepgram.ts, apps/brain/src/
llm.ts). Here both engines live in the same process on the same device
mesh, sharing HBM, and a host-side scheduler interleaves their dispatches:

- every model executable is shape-bucketed (SpeechEngine frame buckets,
  DecodeEngine prefill buckets, fixed-width decode chunks), so colocation
  adds zero recompilation — the XLA program cache holds one program per
  (model, bucket) pair for the process lifetime
- STT jobs get priority: an utterance chunk is one bounded encoder+decode
  dispatch, and intent decoding advances in chunk_steps-token chunks, so
  the worst-case STT queueing delay is a single decode chunk — this is the
  scheduler-tail-latency knob for the p50 < 800 ms target
- device work stays async (JAX dispatch); the interleave loop only orders
  dispatches and harvests finished results

The engines are constructed by the caller (so tests inject tiny presets and
services pick real ones) and must target the same devices; on a multi-chip
mesh both param trees live in the same HBM pool, which is the point.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import Future
from contextlib import contextmanager
from dataclasses import dataclass, field

import numpy as np

from ..utils import machine
from ..utils.steplog import (
    TICK_SPAN,
    annotation,
    get_steplog,
    note_lock_wait,
    note_watchdog_late,
    span,
)
from .engine import GenerationResult
from .scheduler import ContinuousBatcher
from .stt import SpeechEngine, TranscribeResult


# how late a wake of the watchdog must come before the stamp that needs no
# interpreter fires (``_watch``): a thread that only sleeps comes this late
# where every Python thread stands still, and hardly ever otherwise
SAMPLE_LATE_S = 0.5


@dataclass
class ColocationStats:
    stt_jobs: int = 0
    parse_jobs: int = 0
    stt_busy_ms: float = 0.0
    decode_busy_ms: float = 0.0
    decode_chunks: int = 0
    errors: int = 0  # decode-lane failures survived by the loop
    restarts: int = 0  # dead workers revived by the watchdog
    max_stt_queue: int = 0
    max_parse_inflight: int = 0
    # dispatch-order trace: the last "stt" / "chunk" entries, for fairness
    # asserts (as many as the step ring holds: the process lives longer)
    trace: deque = field(default_factory=lambda: deque(maxlen=get_steplog().max_steps))


class ColocatedServing:
    """Interleaves one SpeechEngine and one ContinuousBatcher.

    Synchronous core (``step``) plus an optional worker thread
    (``start``/``stop``). ``submit_stt`` / ``submit_parse`` are thread-safe
    and return ``concurrent.futures.Future``.
    """

    def __init__(self, stt: SpeechEngine | None, batcher: ContinuousBatcher):
        """``stt=None`` runs the decode lane alone — the brain service uses
        this to put the continuous batcher behind /parse without loading a
        speech model into its process."""
        self.stt = stt
        self.batcher = batcher
        self.stats = ColocationStats()
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._stt_q: list[tuple[np.ndarray, Future]] = []
        # serialized engine-plane calls (warm-state handoff export/adopt):
        # run by step() on the worker thread, the only thread allowed to
        # touch the engine's allocator/pool/radix bookkeeping
        self._call_q: list[tuple[object, Future]] = []
        self._parse_futs: dict[int, Future] = {}
        self._abandoned: set[int] = set()  # tombstones applied by step()
        self._thread: threading.Thread | None = None
        self._watchdog: threading.Thread | None = None
        self._stop = False
        # stalled-step detection: set under the lock when the worker enters
        # batcher.step(), cleared when it returns; the watchdog compares
        # against ENGINE_STALL_S to detect a wedged dispatch
        self._step_t0: float | None = None
        # graceful-drain latch (ISSUE 10): the routing tier stops placing
        # NEW sessions here; this runtime keeps serving whatever still
        # arrives — drain is zero-drop by contract, so stragglers racing
        # the router's eject decision complete normally — and ``drained()``
        # flips once both lanes are empty
        self._draining = False

    # ------------------------------------------------------------ submit

    def submit_stt(self, audio: np.ndarray) -> "Future[TranscribeResult]":
        if self.stt is None:
            raise RuntimeError("this runtime was built without an STT engine")
        fut: Future = Future()
        with self._work:
            self._stt_q.append((audio, fut))
            self.stats.max_stt_queue = max(self.stats.max_stt_queue, len(self._stt_q))
            self._work.notify()
        return fut

    def submit_parse(self, prompt: str, deadline=None,
                     tenant=None) -> "Future[GenerationResult]":
        """``deadline`` (utils.resilience.Deadline, optional) rides into the
        batcher: expired-in-queue requests shed at dequeue and in-flight
        ones cancel at chunk boundaries (the x-deadline-ms propagation now
        reaches INSIDE the inference plane, not just the HTTP seams).
        ``tenant`` (ISSUE 18) tags the request's QoS lane the same way."""
        fut: Future = Future()
        # the tenant kwarg is only forwarded when set: duck-typed batchers
        # that predate the QoS plane keep working untagged
        kw = {"tenant": tenant} if tenant is not None else {}
        # on the trace, on the CALLER's thread: the wait for the lock and the
        # submit; ``rid`` is the one ``sched.admit.request`` / ``brain.deliver`` carry
        with annotation("brain.submit") as on_trace, self._work:
            rid = self.batcher.submit(prompt, deadline=deadline, **kw)
            on_trace.set_metadata(rid=rid)
            fut.request_id = rid  # lets abandon_parse find the request again
            if rid in self.batcher.results:
                # refused at submit (quarantined prompt / throttled tenant):
                # resolve now — no decode step will ever run to harvest it
                self._set_future(fut, value=self.batcher.results.pop(rid))
                return fut
            self._parse_futs[rid] = fut
            self.stats.max_parse_inflight = max(
                self.stats.max_parse_inflight, len(self._parse_futs)
            )
            self._work.notify()
        return fut

    def submit_call(self, fn) -> "Future":
        """Run ``fn()`` on the serving-loop thread between steps and
        resolve the returned future with its result. The engine's host
        bookkeeping (allocator refcounts, radix tree, pool rebinds) is
        single-threaded by contract — the warm-state handoff's
        export/adopt (serve.handoff) go through here instead of racing
        ``batcher.step()`` from an HTTP executor thread."""
        fut: Future = Future()
        with self._work:
            self._call_q.append((fn, fut))
            self._work.notify()
        return fut

    def abandon_parse(self, fut: Future) -> None:
        """Give up on a submitted parse (caller timed out or disconnected):
        drop its future and tombstone the request id, so overload does not
        accumulate work nobody will read. The tombstone is applied by
        step() on the WORKER thread — the only thread that touches batcher
        state — via ``batcher.cancel``: a queued request is dropped, and a
        request already DECODING is evicted at the next chunk boundary,
        releasing its slot and KV blocks instead of burning steps for a
        dead socket (mid-decode cancellation, ISSUE 7)."""
        rid = getattr(fut, "request_id", None)
        if rid is None:
            return
        with self._lock:
            self._parse_futs.pop(rid, None)
            self._abandoned.add(rid)
            self._work.notify()  # an idle worker must wake to apply it
        fut.cancel()

    # cancel-on-disconnect is the same mechanics as a timeout abandon; the
    # name is the API contract the brain's request-cancellation hook uses
    cancel_parse = abandon_parse

    # ------------------------------------------------------------ core

    def _has_decode_work(self) -> bool:
        return bool(self.batcher.pending) or any(
            sl.request_id >= 0 for sl in self.batcher.slots
        )

    @contextmanager
    def _own_lock(self):
        """``_lock`` as the SERVING LOOP's thread takes it (``_loop``, ``step``,
        ``_harvest``): what it waited goes into its next step's record
        (``lock_wait_ms``), so a slow lock and a slow step can be told apart."""
        t0 = time.perf_counter_ns()
        self._lock.acquire()
        note_lock_wait(time.perf_counter_ns() - t0)
        try:
            yield
        finally:
            self._lock.release()

    def step(self) -> bool:
        """One scheduling decision: drain STT queue, else one decode chunk.
        Returns True if any device work was dispatched. On the trace one
        ``sched.tick`` (the batcher's ``sched.step`` nests inside), so the
        stretch between two steps lies under a span."""
        with span(TICK_SPAN):
            return self._tick()

    def _tick(self) -> bool:
        from ..utils import get_metrics

        with self._own_lock():
            stt_jobs = list(self._stt_q)
            self._stt_q.clear()
            calls = list(self._call_q)
            self._call_q.clear()
            tombs: set[int] = set()
            if self._abandoned:
                tombs, self._abandoned = self._abandoned, set()
            # pre-drain depths: what a scrape should see as backlog
            get_metrics().set_gauge("colocate.stt_queue", len(stt_jobs))
            get_metrics().set_gauge("colocate.parse_inflight", len(self._parse_futs))
        # apply cancellations OUTSIDE the lock but ON the worker thread —
        # the only thread that touches batcher state, so this cannot race
        # the worker's own pending.pop(0) or chunk dispatch. cancel() drops
        # queued requests and evicts mid-decode ones at the chunk boundary.
        for rid in tombs:
            self.batcher.cancel(rid)
            # nobody is waiting for a tombstoned result: purge immediately
            # (harvest's orphan sweep only runs when decode work exists)
            self.batcher.results.pop(rid, None)
        did = False

        for audio, fut in stt_jobs:  # priority lane
            t0 = time.perf_counter()
            try:
                result = self.stt.transcribe(audio)
            except Exception as e:  # per-job isolation
                result = None
                self._set_future(fut, exc=e)
            if result is not None:
                self._set_future(fut, value=result)
            with self._own_lock():
                self.stats.stt_busy_ms += (time.perf_counter() - t0) * 1e3
                self.stats.stt_jobs += 1
                self.stats.trace.append("stt")
            did = True

        for fn, fut in calls:  # engine-plane call lane (per-job isolation)
            # AFTER the STT priority lane: a multi-MB handoff export/adopt
            # must not delay latency-critical transcriptions in its tick
            try:
                result = fn()
            except Exception as e:
                self._set_future(fut, exc=e)
            else:
                self._set_future(fut, value=result)
            did = True

        if self._has_decode_work():
            t0 = time.perf_counter()
            with self._own_lock():
                self._step_t0 = t0  # stall watchdog arms on this
            try:
                self.batcher.step()
            except Exception as e:
                # decode-lane failure detection: the batch state is suspect,
                # so fail every inflight parse (callers never hang) and keep
                # the serving loop alive for the STT lane and new requests
                self.stats.errors += 1
                self._fail_inflight(e)
                return True
            finally:
                with self._own_lock():
                    # an abandoned (stall-restarted) worker waking here must
                    # not clear the REPLACEMENT worker's armed timestamp —
                    # that would silently blind the watchdog to a second
                    # stall. Only the live worker disarms.
                    if (self._thread is None
                            or threading.current_thread() is self._thread):
                        self._step_t0 = None
            with self._own_lock():
                self.stats.decode_busy_ms += (time.perf_counter() - t0) * 1e3
                self.stats.decode_chunks += 1
                self.stats.trace.append("chunk")
            did = True
            with span("sched.harvest"):
                self._harvest()
        return did

    @staticmethod
    def _set_future(fut: Future, value=None, exc: Exception | None = None) -> None:
        """Resolve a future, tolerating caller-side cancellation."""
        try:
            if exc is not None:
                fut.set_exception(exc)
            else:
                fut.set_result(value)
        except Exception:
            pass  # already cancelled/resolved by the caller

    def _fail_inflight(self, exc: Exception) -> None:
        # everything under the one lock: a concurrent submit_parse must land
        # either wholly before the reset (and get failed) or wholly after
        with self._lock:
            futs = list(self._parse_futs.values())
            self._parse_futs.clear()
            self.batcher.reset()
        for fut in futs:
            self._set_future(fut, exc=exc)

    def _harvest(self) -> None:
        with self._own_lock():
            done = [rid for rid in self._parse_futs if rid in self.batcher.results]
            for rid in done:
                fut = self._parse_futs.pop(rid)
                res = self.batcher.results.pop(rid)
                self.stats.parse_jobs += 1
                # the waiter reads its wake latency off this (``brain.parse_deliver_ms``)
                fut.resolved_ns = time.perf_counter_ns()
                self._set_future(fut, value=res)
            # purge results whose futures were abandoned (submit and future
            # registration share one lock, so no still-wanted rid lacks one)
            for rid in [r for r in self.batcher.results if r not in self._parse_futs]:
                self.batcher.results.pop(rid)

    def begin_drain(self) -> None:
        """Arm the graceful-drain latch (rolling-restart protocol, ISSUE
        10). Deliberately does NOT refuse new submissions: a request that
        races the router's stop-admitting decision must be served, not
        dropped — the zero-drop drain contract. The brain's /health
        surfaces ``draining``/``drained`` so the router knows when the
        replica is safe to eject."""
        with self._lock:
            self._draining = True
        from ..utils import get_metrics

        get_metrics().inc("colocate.drains_started")

    @property
    def draining(self) -> bool:
        return self._draining

    def drained(self) -> bool:
        """True once the drain latch is set AND both lanes are empty (no
        queued STT work, no parse future unresolved, no slot decoding)."""
        if not self._draining:
            return False
        with self._lock:
            return (not self._stt_q and not self._call_q
                    and not self._parse_futs
                    and not self._has_decode_work())

    def drain(self, timeout_s: float = 120.0) -> None:
        """Block until all queued work (both lanes) has completed.

        Only steps inline when no worker thread is running — two threads
        executing ``batcher.step()`` concurrently would corrupt slot/cache
        state, so with a live worker this just waits for it to finish.
        """
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            with self._lock:
                idle = (not self._stt_q and not self._call_q
                        and not self._parse_futs)
                worker_alive = self._thread is not None and self._thread.is_alive()
            if idle:
                return
            if worker_alive:
                time.sleep(0.005)
            else:
                self.step()
        raise TimeoutError("colocated drain timed out")

    # ------------------------------------------------------------ worker

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop = False
        self._thread = threading.Thread(target=self._loop, name="colocate", daemon=True)
        self._thread.start()

    def start_watchdog(self, interval_s: float = 0.5,
                       stall_s: float | None = None) -> None:
        """Arm a liveness + stall watchdog over the worker thread.

        ``_loop`` survives ordinary exceptions itself, but a thread can
        still die outright (BaseException escape, interpreter-level kill,
        a bug in the survival path) — and a thread can also WEDGE inside a
        decode step (host-side convoy, a hung dispatch) without dying,
        which is a worse outage: /health keeps reporting a live worker
        while every future waits forever. The watchdog covers both:

        - dead worker: fail every inflight future fast, reset the suspect
          batcher, start a fresh loop (``colocate.worker_restarts``)
        - stalled step (no progress for ``stall_s``, default
          ``ENGINE_STALL_S``=30): fail inflights fast, WARM-RESTART the
          engine (``engine.warm_restart()`` — fresh mutable decode state,
          same loaded weights and compiled programs), reset the batcher
          (which bumps its epoch so the stuck step discards its commit if
          it ever wakes), start a fresh loop, and freeze a flight-recorder
          dump (``engine.restarts``). The abandoned thread exits at its
          next loop check — a genuinely hung device call may never wake,
          which is exactly why the replacement loop must not wait for it.

        Long before that, and with no restart (ISSUE 36): its own LATENESS —
        how far each ``sleep(interval_s)`` overslept, histogram
        ``host.watchdog_late`` and ``watchdog_late_ms`` on the next step
        record — and, once a step, a SNAPSHOT of a step older than three
        median steps and a second (``StepLog.stall_snapshot``: every
        thread's frames, the open spans, an open collection, its own
        lateness). This thread needs the interpreter, so where every Python
        thread stands still it comes when the step is over. For that case it
        holds a DEAD MAN'S SWITCH (ISSUE 52): before every sleep it arms the
        process's one stamp that needs no interpreter
        (``utils/machine._Sampler``), which fires only if this thread then
        wakes ``SAMPLE_LATE_S`` late — on time behind a held interpreter, when
        the process runs again where the machine froze it: the long step's
        record tells the two apart by it (``stall.dump_at_ms``). It is let go
        before a recovery, whose own work may outlast it.
        """
        if self._watchdog is not None:
            return
        if stall_s is None:
            import os

            stall_s = float(os.environ.get("ENGINE_STALL_S", "30"))
        # restart counter exists from arming (scrape-visible at zero, like
        # the breaker gauges): 'no series' and 'no restarts' must differ
        from ..utils import get_metrics

        get_metrics().inc("engine.restarts", 0.0)
        if get_steplog().enabled and (sampler := machine.sampler()) is not None:
            sampler.arm(self, interval_s + SAMPLE_LATE_S)
        self._watchdog = threading.Thread(
            target=self._watch, args=(interval_s, stall_s),
            name="colocate-watchdog", daemon=True)
        self._watchdog.start()

    def _restart_worker(self, exc: RuntimeError,
                        reset_batcher: bool = True) -> None:
        """Shared dead/stalled recovery: fail both lanes fast, reset the
        batcher (unless the caller already did, interleaved with a warm
        restart), spin up a fresh serving loop."""
        with self._lock:
            stt_jobs, self._stt_q[:] = list(self._stt_q), []
            calls, self._call_q[:] = list(self._call_q), []
        for _, fut in stt_jobs:
            self._set_future(fut, exc=exc)
        for _, fut in calls:
            self._set_future(fut, exc=exc)
        if reset_batcher:
            self._fail_inflight(exc)  # also resets the suspect batcher (+epoch)
        with self._work:
            if self._stop:
                return
            self._step_t0 = None
            self._thread = threading.Thread(
                target=self._loop, name="colocate", daemon=True)
            self._thread.start()

    def _watch(self, interval_s: float, stall_s: float = 30.0) -> None:
        import logging

        from ..utils import get_metrics
        from ..utils.tracing import log_event

        log = logging.getLogger("tpu_voice_agent.colocate")
        steplog = get_steplog()
        late_ms, snapped = 0.0, None  # the last sleep's lateness; the step photographed
        # the dead man's switch: held from ``start_watchdog`` on (a step that
        # opens before this thread first runs finds it armed)
        sampler = machine.sampler() if steplog.enabled else None
        while True:
            with self._work:
                if self._stop:
                    if sampler is not None:
                        sampler.cancel(self)  # a stopped watchdog holds no switch
                    return
                dead = self._thread is not None and not self._thread.is_alive()
                t0, worker = self._step_t0, self._thread
                age_s = time.perf_counter() - t0 if t0 is not None else 0.0
                stalled = not dead and t0 is not None and age_s >= stall_s
            if (steplog.enabled and not dead and not stalled and t0 != snapped
                    and age_s >= 1.0 and age_s >= steplog.stall_after_s()):
                # a long step, far short of a stall: say what holds the thread
                # (once a step), restart nothing
                snapped = t0
                snap = steplog.stall_snapshot(worker.name if worker else "", age_s, late_ms)
                log_event("colocate", "step.stall_snapshot", age_ms=snap["age_ms"],
                          late_ms=snap["late_ms"], gc_open_ms=snap["gc_open_ms"],
                          open_spans=" > ".join(snap["open_spans"]),
                          batcher_frames=" < ".join(
                              f for t in snap["threads"] if t["name"] == snap["batcher"]
                              for f in t["frames"]))
            if sampler is not None and (dead or stalled):
                # a recovery (a freeze of the flight recorder, a warm restart,
                # lock waits) may outlast the switch while Python runs: what
                # fired then would be the next long step's to misread
                sampler.cancel(self)
            if dead:
                log.error("colocate worker died; failing inflight work and "
                          "restarting the serving loop")
                get_metrics().inc("colocate.worker_restarts")
                self.stats.restarts += 1
                self._restart_worker(RuntimeError(
                    "serving worker died; work failed fast on restart"))
            elif stalled:
                log.error("decode step stalled >%.1fs; failing inflight work "
                          "and warm-restarting the engine", stall_s)
                get_metrics().inc("engine.restarts")
                self.stats.restarts += 1
                from ..utils.tracing import get_flight_recorder

                get_flight_recorder().trigger(
                    "engine.stall", detail=f"step stalled >{stall_s}s")
                # ordering: epoch fence up (batcher.reset) BEFORE the warm
                # restart, both before the fresh loop spawns — the wedged
                # thread is abandoned, and if it ever wakes its step
                # discards rather than commits (epoch mismatch) and
                # _loop's identity check exits it.
                wr = getattr(self.batcher.engine, "warm_restart", None)
                exc = RuntimeError(
                    "decode step stalled; engine warm-restarted, "
                    "work failed fast")
                with self._lock:
                    futs = list(self._parse_futs.values())
                    self._parse_futs.clear()
                    self.batcher.reset()  # epoch fence up BEFORE restart
                    if wr is not None:
                        wr()
                for fut in futs:
                    self._set_future(fut, exc=exc)
                self._restart_worker(exc, reset_batcher=False)
            if sampler is not None:
                # at every wake (one system call: the timer is the kernel's): it
                # fires where THIS thread wakes SAMPLE_LATE_S late or later
                sampler.arm(self, interval_s + SAMPLE_LATE_S)
            t_sleep = time.perf_counter_ns()
            time.sleep(interval_s)
            late_ns = max(0, time.perf_counter_ns() - t_sleep - int(interval_s * 1e9))
            late_ms = late_ns / 1e6
            get_metrics().observe_ms("host.watchdog_late", late_ms)
            if steplog.enabled:
                note_watchdog_late(late_ns)

    def stop(self) -> None:
        with self._work:
            self._stop = True
            self._work.notify()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
        if self._watchdog is not None:
            self._watchdog.join(timeout=30)
            self._watchdog = None

    def healthy(self) -> bool:
        """Worker-liveness probe; a service embedding this runtime should
        surface it from its own /health handler."""
        return self._thread is not None and self._thread.is_alive()

    def _loop(self) -> None:
        import logging

        log = logging.getLogger("tpu_voice_agent.colocate")
        while True:
            with self._own_lock():
                # a stall-watchdog restart replaced this loop while it was
                # wedged inside a step: the impostor must exit, never touch
                # the (warm-restarted) batcher again
                if self._thread is not None and \
                        threading.current_thread() is not self._thread:
                    return
            try:
                did = self.step()
            except Exception:
                # the worker must outlive any single bad step (§5: failure
                # detection — per-job faults are already isolated upstream)
                self.stats.errors += 1
                log.exception("colocate step failed; worker continues")
                did = False
            with self._own_lock():  # ``_work``'s lock: the wait below releases it
                if self._stop:
                    return
                if self._thread is not None and \
                        threading.current_thread() is not self._thread:
                    return
                if not did and not self._stt_q and not self._call_q \
                        and not self._has_decode_work():
                    # on the profiler's trace: the device idles here for
                    # want of a request, not for want of a faster host
                    with span("sched.wait_for_work"):
                        self._work.wait(timeout=0.05)
