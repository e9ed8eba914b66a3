"""Per-step engine telemetry: the step ledger, and the spans it is built from.

The PR 2/PR 6 observability plane stops at the service boundary — once a
request enters ``ContinuousBatcher.step()`` the engine is a black box. The
step ledger opens it: every scheduler chunk records one bounded ring entry
with the step's wall-time decomposition —

    admit     queue/admission bookkeeping (the prefill stage taken out)
    prefill   the engine's layout-kernel calls of this step's admissions
              (``sched.admit.prefill``: what ``prefill_ms`` has always
              timed — the two host→device copies of the staged suffix, on
              the paged layout the block allocation and prefix-tail
              scatter, and the DISPATCH of the jitted forward)
    draft     host drafter share of the chunk (``sched.decode.draft``
              around the spec drafter; carved out of decode)
    decode    the decode_chunk dispatch wall — for spec engines this is the
              whole host-driven draft/verify loop (per-step readbacks
              included), minus the carved drafter share
    readback  the scheduler's one combined device_get (host sync — on the
              plain async-dispatch path this is where device compute time
              surfaces to the host)
    release   post-readback commit: result assembly, release_slot /
              radix-insert, gauge exports, HBM ledger tick

— plus batch occupancy, accepted-token and forward counts, each admission's
parts and queue wait (``admissions``), and any compile events the
recompilation sentinel (utils/compilewatch.py) caught during the step
("compile stall": the step that paid a trace shows it).

ONE span primitive, two sinks. ``StepTimer.span(name, **attrs)`` (and the
module-level ``span`` the engine layers call, which finds the thread's open
step) enters a ``jax.profiler.TraceAnnotation`` — under a microsecond while
no profiler session is active, an event on the profiler's own clock beside
the device's operations while one is — and folds its ``perf_counter_ns``
duration into the step's record. The step itself is a
``StepTraceAnnotation("sched.step", step_num=seq)``. Span names:

    sched.step                       one ContinuousBatcher.step()
      sched.admit                    stage ``admit`` (``stage()``: contiguous)
        sched.admit.request          one admission; attrs rid, queue_ms,
                                     prompt_tokens, cached_tokens
          .tokenize .alloc .first_token_call .slot_state .bookkeeping
                                     its parts (``ADMISSION_PARTS``), in
                                     code order
            sched.admit.prefill      stage ``prefill``, inside ``.alloc``:
                                     the engine's layout-kernel call
              .prefill_call          the part inside both: the jitted call
                                     alone (``.alloc`` is the engine's
                                     ``prefill_slot`` less this call)
              .state_restore         a recurrent model's admissions only: the
                                     prefix's state snapshot copied into the slot
        sched.admit.group            what a group of admissions shares (attr
                                     rows): each member's request span above
                                     is its HOST half alone; the parts in here
                                     (.alloc, .slot_state: one host→device
                                     copy, .prefill_call: the group's ONE
                                     launch, .bookkeeping) are shared out
                                     evenly over the members' ledger entries,
                                     which gain ``rows``
      sched.decode_dispatch          stage ``decode``
        sched.decode.draft           stage ``draft``
      sched.readback                 stage ``readback``
      sched.release                  stage ``release``
    sched.wait_for_work, sched.harvest   serve/colocate.py, between steps

The four ``stage()`` spans are contiguous (one clock reading closes one and
opens the next), and a staged span nested in another is subtracted from it,
so the six stages TILE the step wall by construction: ``sum(stages) ≈
wall``.

Surfaces: ``engine.step.*`` histograms/gauges in the metrics registry,
``GET /debug/steplog`` on the brain, a ``steplog`` section folded into
flight-recorder freezes, the ``tools/stepview.py`` timeline, and the
profiler's trace (``benchmark/readers/host_spans.py``).

``STEPLOG_ENABLE=0`` turns recording off (ring stays empty, no metrics);
the decode path is host-timing only either way, so tokens are identical
with the ledger on or off (tests/test_steplog.py holds this
differentially). ``STEPLOG_STEPS`` sizes the ring (default 256).
"""

from __future__ import annotations

import os
import threading
import time

# the tiling stage order (stepview renders bars in this order)
STAGES = ("admit", "prefill", "draft", "decode", "readback", "release")
# the spans that ARE stages
SPAN_STAGE = {"sched.admit": "admit", "sched.admit.prefill": "prefill",
              "sched.decode.draft": "draft",
              "sched.decode_dispatch": "decode", "sched.readback": "readback",
              "sched.release": "release"}
PREFILL_STAGE_SPAN = "sched.admit.prefill"
REQUEST_SPAN = "sched.admit.request"
ALLOC_SPAN = REQUEST_SPAN + ".alloc"
PREFILL_CALL_SPAN = REQUEST_SPAN + ".prefill_call"
FIRST_TOKEN_SPAN = REQUEST_SPAN + ".first_token_call"
# a model with a recurrent state (models.sambay) alone: the copy of the
# prefix's state snapshot into the slot, inside ``.alloc`` and taken out of
# it like ``.prefill_call`` (``state_restore_ms`` in the admission's entry;
# no other model's admission has the key, so it is no ``ADMISSION_PARTS``)
STATE_RESTORE_SPAN = REQUEST_SPAN + ".state_restore"
# what a GROUP of admissions shares (ISSUE 35): each member's
# ``sched.admit.request`` span is its host half, this span holds the group's
# ``.alloc`` / ``.slot_state`` / ``.prefill_call`` (its one launch) /
# ``.bookkeeping`` parts, once a call, and each member's entry gets an even
# share of them
GROUP_SPAN = "sched.admit.group"
# one admission in code order; each is ``<part>_ms`` in its ledger entry
ADMISSION_PARTS = ("tokenize", "alloc", "prefill_call", "first_token_call",
                   "slot_state", "bookkeeping")
# the thread's open step, so that engine code finds it without plumbing
_ACTIVE = threading.local()


class StepLog:
    """Bounded ring of per-step records (FlightRecorder discipline: always
    on, cheap to feed, immutable dumps on read)."""

    def __init__(self, max_steps: int | None = None,
                 enabled: bool | None = None):
        self.max_steps = max_steps if max_steps is not None \
            else int(os.environ.get("STEPLOG_STEPS", "256"))
        self.enabled = enabled if enabled is not None \
            else os.environ.get("STEPLOG_ENABLE", "1") != "0"
        self._lock = threading.Lock()
        self._steps: list[dict] = []
        self._seq = 0

    # ------------------------------------------------------------ feeding

    def timer(self) -> "StepTimer":
        return StepTimer(self)

    def record(self, rec: dict) -> None:
        """Append one step record and export its metrics. No-op when
        disabled — the scheduler's timing calls still happen (perf_counter
        noise), but nothing is stored or exported."""
        if not self.enabled:
            return
        from . import get_metrics

        with self._lock:
            rec["seq"] = self._seq
            self._seq += 1
            self._steps.append(rec)
            if len(self._steps) > self.max_steps:
                del self._steps[: len(self._steps) - self.max_steps]
        m = get_metrics()
        m.observe_ms("engine.step.wall", rec["wall_ms"])
        for stage, ms in rec["stages"].items():
            m.observe_ms(f"engine.step.{stage}", ms)
        m.set_gauge("engine.step.occupancy", float(rec.get("occupancy", 0)))
        m.set_gauge("engine.step.tokens", float(rec.get("tokens", 0)))
        if rec.get("events"):
            m.inc("engine.step.compile_stalls", float(len(rec["events"])))

    def next_seq(self) -> int:
        """The ``seq`` the next record gets (a step's ``step_num``)."""
        with self._lock:
            return self._seq

    # ------------------------------------------------------------ reading

    def last(self) -> dict | None:
        with self._lock:
            return dict(self._steps[-1]) if self._steps else None

    def steps(self, last: int | None = None) -> list[dict]:
        with self._lock:
            out = [dict(s) for s in self._steps]
        return out[-last:] if last else out

    def dump(self) -> dict:
        """The /debug/steplog body; also folded into flight-recorder
        freezes so an overload autopsy carries the device-plane timeline."""
        with self._lock:
            return {"enabled": self.enabled, "max_steps": self.max_steps,
                    "recorded": self._seq, "steps": [dict(s) for s in self._steps]}

    def clear(self) -> None:
        with self._lock:
            self._steps.clear()
            self._seq = 0


class _Span:
    """One open span: a TraceAnnotation on the profiler's clock, and a
    ``perf_counter_ns`` duration folded into the step's record at exit."""

    __slots__ = ("timer", "name", "stage", "part", "entry", "members", "ann", "t0",
                 "carved_ns")

    def __init__(self, timer: "StepTimer", name: str, attrs: dict):
        self.timer, self.name = timer, name
        self.stage = SPAN_STAGE.get(name)
        # a request span carries the admission's ledger entry, a span named
        # under it is one of the admission's parts
        self.entry = dict(attrs) if name == REQUEST_SPAN else None
        self.members = None  # a ``_Group``'s: the entries its parts are shared over
        self.part = (name[len(REQUEST_SPAN) + 1:] + "_ms"
                     if name.startswith(REQUEST_SPAN + ".") else None)
        self.ann = _annotation(name, **attrs)
        self.carved_ns = 0

    def set(self, **attrs) -> None:
        """Attributes learned inside the span (a prompt's token count)."""
        self.ann.set_metadata(**attrs)
        if self.entry is not None:
            self.entry.update(attrs)

    def drop(self) -> None:
        """This request span is no admission (a chunked admission's start
        or middle): it stays on the trace and leaves the ledger."""
        self.entry = None

    def __enter__(self) -> "_Span":
        self.ann.__enter__()
        self.t0 = time.perf_counter_ns()
        self.timer._open.append(self)
        return self

    def __exit__(self, exc_type=None, exc=None, tb=None, now: int | None = None):
        dur = (time.perf_counter_ns() if now is None else now) - self.t0
        self.ann.__exit__(exc_type, exc, tb)
        timer = self.timer
        timer._open.remove(self)
        if self.stage is not None:
            # a staged span inside another (a prefill call inside admit, the
            # drafter inside decode) is that stage's time and not its
            # parent's: the stages tile the wall
            for up in reversed(timer._open):
                if up.stage is not None:
                    up.carved_ns += dur
                    break
            timer.stages[self.stage] = (timer.stages.get(self.stage, 0.0)
                                        + (dur - self.carved_ns) / 1e6)
        if self.entry is not None:
            if exc_type is None:
                self.entry["request_ms"] = round(dur / 1e6, 4)
                timer.admissions.append(self.entry)
        elif self.part is not None:
            # a part inside another (the jitted call inside ``.alloc``) is
            # its own time and not its parent's: the parts tile the request
            own, nearest = dur - self.carved_ns, True
            for up in reversed(timer._open):
                into = [up.entry] if up.entry is not None else up.members
                if into is not None:  # a request, or the group that shares it out
                    for e in into:
                        e[self.part] = round(e.get(self.part, 0.0) + own / 1e6 / len(into), 4)
                    break
                if up.part is not None and nearest:
                    up.carved_ns += dur
                    nearest = False
        return False


class _Group(_Span):
    """The launches several admissions share (``GROUP_SPAN``): one span on
    the trace; in the ledger its time and its parts' are shared out evenly
    over the members' entries, which gain ``rows``. A launch that raises
    admitted nobody: the members' entries leave the ledger."""

    __slots__ = ()

    def __init__(self, timer: "StepTimer", members: list):
        super().__init__(timer, GROUP_SPAN, {"rows": len(members)})
        self.members = [e for e in members if e is not None]

    def __exit__(self, exc_type=None, exc=None, tb=None, now: int | None = None):
        dur = (time.perf_counter_ns() if now is None else now) - self.t0
        super().__exit__(exc_type, exc, tb, now=now)  # neither a stage nor a part
        n = len(self.members)
        if exc_type is not None:
            self.timer.admissions[:] = [a for a in self.timer.admissions
                                        if all(a is not e for e in self.members)]
            return False
        for e in self.members:
            e["request_ms"] = round(e["request_ms"] + dur / 1e6 / n, 4)
            e["rows"] = n
        return False


class StepTimer:
    """Measures one scheduler step as spans.

    ``stage(name)`` closes the open stage span and opens ``name`` on ONE
    clock reading — stage spans are contiguous, which is what makes the
    ≥95%-accounted property hold by construction. ``span(name)`` is a
    ``with`` block inside them; one whose name maps to a stage
    (``SPAN_STAGE``) is reported as that stage and taken out of the stage
    around it. ``finish`` drains the compile
    sentinel's pending events and records; ``close`` (idempotent) ends
    whatever is still open, for a step that raised or was abandoned."""

    def __init__(self, log: StepLog):
        self._log = log
        self.stages: dict[str, float] = {}
        self.admissions: list[dict] = []
        self._open: list[_Span] = []
        self._stage: _Span | None = None
        self._prev = getattr(_ACTIVE, "timer", None)
        _ACTIVE.timer = self
        self._step = _annotation("sched.step", step_num=log.next_seq(), step=True)
        self._step.__enter__()
        self.t0_ns = time.time_ns()
        self.t0 = time.perf_counter_ns()
        self._t_end: int | None = None  # where the last stage closed

    def span(self, name: str, **attrs) -> _Span:
        return _Span(self, name, attrs)

    def group(self, members: list) -> _Group:
        """The span of what ``members`` — entries of request spans already
        closed — share: see ``_Group``."""
        return _Group(self, members)

    def stage(self, name: str) -> None:
        now = time.perf_counter_ns()
        first = self._t_end is None
        self._close_stage(now)
        self._stage = self.span(name).__enter__()
        # the first stage runs from the step's start, the others from the
        # reading that closed the one before
        self._stage.t0 = self.t0 if first else now

    def _close_stage(self, now: int) -> None:
        if self._stage is not None:
            self._stage.__exit__(now=now)
            self._stage = None
        self._t_end = now

    def close(self) -> None:
        if self._step is None:
            return
        for sp in reversed(list(self._open)):
            sp.__exit__()
        self._stage = None
        self._step.__exit__(None, None, None)
        self._step = None
        if getattr(_ACTIVE, "timer", None) is self:
            _ACTIVE.timer = self._prev

    def finish(self, **meta) -> dict:
        from .compilewatch import get_compile_watcher

        # the wall closes with the LAST stage: everything after it is this
        # recorder's own overhead (pending-drain, dict assembly), which
        # must not show up as unaccounted step time — with it excluded the
        # stages tile the wall by construction
        now, t1_ns = time.perf_counter_ns(), time.time_ns()
        if self._stage is not None:
            self._close_stage(now)
        end = self._t_end if self.stages else now
        self.close()
        rec = {
            "t_s": round(t1_ns / 1e9, 3),
            "t0_ns": self.t0_ns,
            "t1_ns": t1_ns,
            "wall_ms": round((end - self.t0) / 1e6, 3),
            "stages": {k: round(v, 3) for k, v in self.stages.items()},
            "events": get_compile_watcher().take_pending(),
        }
        if self.admissions:
            rec["admissions"] = self.admissions
        rec.update({k: v for k, v in meta.items() if v is not None})
        self._log.record(rec)
        return rec


def span(name: str, **attrs):
    """The span primitive for code below the scheduler (engine, drafter):
    part of the thread's open step when there is one, a bare
    TraceAnnotation when there is none (a direct ``engine.generate``)."""
    timer = getattr(_ACTIVE, "timer", None)
    return timer.span(name, **attrs) if timer is not None else _annotation(name, **attrs)


def _annotation(name: str, step: bool = False, **attrs):
    # jax is imported on first use: ``utils`` is imported by processes that
    # never touch it (the rule-parser brain, the tools)
    from jax.profiler import StepTraceAnnotation, TraceAnnotation

    return (StepTraceAnnotation if step else TraceAnnotation)(name, **attrs)


_GLOBAL_STEPLOG = StepLog()


def get_steplog() -> StepLog:
    return _GLOBAL_STEPLOG


def make_steplog_handler(service: str):
    """aiohttp ``GET /debug/steplog``: the step ring as JSON.
    ``?last=K`` trims to the most recent K steps."""
    from aiohttp import web

    async def steplog_ep(req) -> web.Response:
        log = get_steplog()
        body = log.dump()
        try:
            last = int(req.query.get("last", "0"))
        except ValueError:
            last = 0
        if last > 0:
            body["steps"] = body["steps"][-last:]
        body["service"] = service
        return web.json_response(body)

    return steplog_ep
