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
    decode    the decode_chunk dispatch wall
    readback  the scheduler's one combined device_get (host sync — on the
              plain async-dispatch path this is where device compute time
              surfaces to the host)
    release   post-readback commit: result assembly, release_slot /
              radix-insert, gauge exports, HBM ledger tick

— plus batch occupancy, emitted-token and forward counts, each admission's
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

    sched.tick                       one ColocatedServing.step(): the queue
                                     drains, the step below, the harvest — so
                                     the stretch between two steps has a span
    sched.step                       one ContinuousBatcher.step()
      sched.admit                    stage ``admit`` (``stage()``: contiguous)
        sched.admit.head             from the stage's start to the step's FIRST
                                     launch (the first ``.prefill_call`` to
                                     enter, else ``sched.decode_dispatch``)
        sched.admit.request          one admission; attrs rid, queue_ms,
                                     prompt_tokens, head_ids_reused (of
                                     them, the ids the engine's memo of the
                                     prompt head gave: ``encode_prompt``),
                                     cached_tokens
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
      sched.readback                 stage ``readback``
      sched.release                  stage ``release``
    sched.wait_for_work, sched.harvest   serve/colocate.py, between steps
    host.gc                          one collection of the garbage collector, on
                                     whichever thread it ran (``gc.callbacks``)
    brain.submit, brain.deliver      a request's way in and out, on ITS threads,
                                     attr rid (serve/colocate.py, services/brain.py)

The four ``stage()`` spans are contiguous (one clock reading closes one and
opens the next), and a staged span nested in another is subtracted from it,
so the five stages TILE the step wall by construction: ``sum(stages) ≈
wall``.

WHY the thread was slow there (ISSUE 36). Every stage boundary is read on
three clocks — ``perf_counter_ns``, ``thread_time_ns`` (this thread's CPU)
and ``process_time_ns`` (the process's) — and the record carries, tiled as
``stages`` is, ``cpu_ms`` (CPU the batcher's thread burned in the stage) and
``others_cpu_ms`` (process CPU less the thread's: every OTHER thread's).
``stages[s] - cpu_ms[s]`` is the time the thread did not run: off-CPU with
others' CPU about equal to it → another thread held the interpreter (or the
core); off-CPU with others' CPU near the runtime's floor → the thread slept
on the device or a lock; neither → its own work. Beside them: ``head_ms`` /
``head_cpu_ms`` / ``head_others_cpu_ms`` (``sched.admit.head``), ``gap_ms`` /
``gap_cpu_ms`` / ``gap_others_cpu_ms`` (the previous step's last boundary →
this step's start), ``lock_wait_ms`` (the serving loop's waits for its own
lock since the previous step: ``note_lock_wait``), and what the process's
ONE event ring held when the step closed: collections (``gc_ms``,
``gc_max_ms``, ``gc_n``; ``gc`` lists those of generation ≥ 1 or ≥ 1 ms),
the watchdog's lateness (``watchdog_late_ms``) and its ``stall`` snapshot
(``StepLog.stall_snapshot``). The scalar keys are on every record, 0.0 where
nothing happened; ``gc`` and ``stall`` only where they hold something.

The MACHINE's side of a step (ISSUE 52). Where every Python thread stands
still the watchdog stands still too, so a STAMP that needs no interpreter is
taken for it: ``machine._Sampler``, a kernel timer and a C signal handler, a
dead man's switch the watchdog arms anew at every wake. A step whose wall
passes three median steps (``stall_after_s``) reads its file where it closes,
and its ``stall`` gains ``dump_n`` (flat too: ``stall_dump_n``) and, of a
stamp, ``after_ms``, ``dump_at_ms`` and the frames of the one thread the signal
reached (``threads``) — well before the step's end: the process ran and its
interpreter was held; about the stall's END: the whole process was not run.
It walks no other thread's frames: that killed the process (``_Sampler``
says how, and how a drill still names the thread that holds the interpreter).
And the OS's counters, read at a step's two ends beside the clocks
(``utils/machine.py``, ``MACHINE_KEYS``): ``run_delay_ms`` (the thread runnable
and not run), ``majflt`` (the process's major faults), ``throttled_ms`` (its
cgroup's CPU quota). A source the machine lacks leaves its key OUT of every
record — never 0.0, which would say "no delay".

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

import gc
import os
import sys
import threading
import time
from collections import deque

from . import machine

# the tiling stage order (stepview renders bars in this order)
STAGES = ("admit", "prefill", "decode", "readback", "release")
# the spans that ARE stages
SPAN_STAGE = {"sched.admit": "admit", "sched.admit.prefill": "prefill",
              "sched.decode_dispatch": "decode", "sched.readback": "readback",
              "sched.release": "release"}
PREFILL_STAGE_SPAN = "sched.admit.prefill"
REQUEST_SPAN = "sched.admit.request"
ALLOC_SPAN = REQUEST_SPAN + ".alloc"
PREFILL_CALL_SPAN = REQUEST_SPAN + ".prefill_call"
FIRST_TOKEN_SPAN = REQUEST_SPAN + ".first_token_call"
# a model whose family's record gives a SLOT planes of its own (a recurrent
# state of any kind: ``models.family``'s ``slot_planes``) alone: the copy of the
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
# the head of a step (ISSUE 36): opened with ``sched.admit``, closed by the
# step's first launching span — a span whose name ends in ``LAUNCH_TAIL``,
# else the ``sched.decode_dispatch`` stage
HEAD_SPAN = "sched.admit.head"
LAUNCH_TAIL = ".prefill_call"
TICK_SPAN = "sched.tick"  # serve/colocate.py, around one scheduling decision
GC_SPAN = "host.gc"
# one admission in code order; each is ``<part>_ms`` in its ledger entry
ADMISSION_PARTS = ("tokenize", "alloc", "prefill_call", "first_token_call",
                   "slot_state", "bookkeeping")
# the thread's open step, so that engine code finds it without plumbing; and
# what the serving loop's thread waited for its own lock since its last step
_ACTIVE = threading.local()

# The process's ONE event ring (ISSUE 36): what happens on other threads, or
# between steps, and belongs in a step's record — a collection
# ("gc", t0_ns, dur_ns, generation, collected, thread ident), the watchdog's
# lateness ("late", late_ns), its stall snapshot ("stall", dict).
# Fed under the interpreter's lock alone (``deque.append``); the step that
# closes next takes what is there (``_fold_events``).
_EVENTS: deque = deque(maxlen=4096)
_gc_t0 = 0  # ``perf_counter_ns`` at the open collection's "start"; 0: none open
_gc_ann = None
_TRACE = None  # jax.profiler.TraceAnnotation, bound where the callback is installed


def _clocks() -> tuple[int, int, int]:
    """One boundary on the three clocks: the wall, this thread's CPU, the
    process's."""
    return time.perf_counter_ns(), time.thread_time_ns(), time.process_time_ns()


def _on_gc(phase: str, info: dict) -> None:
    """The process's ``gc.callbacks`` entry: a stamp and an open ``host.gc``
    annotation at "start" (no container is built there), the event at "stop".
    Runs on whichever thread the collection runs on; takes no lock (a metric's
    lock may be held by the very thread that allocated into a collection)."""
    global _gc_t0, _gc_ann
    if phase == "start":
        _gc_t0 = time.perf_counter_ns()
        _gc_ann = _TRACE(GC_SPAN)
    elif _gc_t0:
        t0, ann, _gc_t0, _gc_ann = _gc_t0, _gc_ann, 0, None
        dur = time.perf_counter_ns() - t0
        ann.set_metadata(generation=info["generation"], collected=info["collected"])
        ann.__exit__(None, None, None)
        _EVENTS.append(("gc", t0, dur, info["generation"], info["collected"],
                        threading.get_ident()))


def _install_gc() -> None:
    global _TRACE
    if _on_gc not in gc.callbacks:
        from jax.profiler import TraceAnnotation

        _TRACE = TraceAnnotation
        gc.callbacks.append(_on_gc)


def note_lock_wait(ns: int) -> None:
    """The calling thread waited ``ns`` for a lock of the serving loop's; its
    next step's record carries the sum as ``lock_wait_ms``."""
    _ACTIVE.lock_wait_ns = getattr(_ACTIVE, "lock_wait_ns", 0) + ns


def note_watchdog_late(ns: int) -> None:
    """A watchdog's ``sleep`` overslept by ``ns``: a thread that only sleeps
    and cannot get the interpreter back is the cheapest starvation probe."""
    _EVENTS.append(("late", ns))


def _take_events() -> list[tuple]:
    """Everything the event ring holds, taken off it (each event lands in ONE
    record: the step's that closes next)."""
    taken = []
    while True:
        try:
            taken.append(_EVENTS.popleft())
        except IndexError:
            return taken


def _fold_events(rec: dict, events, t0: int, ident: int) -> None:
    """``events`` into the record of the step that started at ``t0`` on thread
    ``ident`` — the scalar keys always, 0.0 where nothing happened — and
    counted for an operator's scrape."""
    from . import get_metrics

    m = get_metrics()
    kept, total, longest, n, late = [], 0, 0, 0, 0
    for ev in events:
        if ev[0] == "gc":
            _, at, dur, gen, collected, who = ev
            n, total, longest = n + 1, total + dur, max(longest, dur)
            m.observe_ms("host.gc_pause", dur / 1e6)
            if gen >= 1 or dur >= 1_000_000:
                kept.append({"gen": gen, "ms": round(dur / 1e6, 3), "own": who == ident,
                             "at_ms": round((at - t0) / 1e6, 3), "collected": collected})
        elif ev[0] == "late":
            late = max(late, ev[1])
        else:
            rec["stall"] = ev[1]
    if n:
        m.inc("host.gc_collections", float(n))
    rec.update(gc_ms=round(total / 1e6, 3), gc_max_ms=round(longest / 1e6, 3), gc_n=n,
               watchdog_late_ms=round(late / 1e6, 3))
    if kept:
        rec["gc"] = kept


class StepLog:
    """Bounded ring of per-step records (FlightRecorder discipline: always
    on, cheap to feed, immutable dumps on read)."""

    def __init__(self, max_steps: int | None = None,
                 enabled: bool | None = None, sampler: bool = False):
        """``sampler``: this ledger's long steps read the file of the process's
        ONE ``machine._Sampler`` (the global ledger's do, where a watchdog
        holds it armed; a test's own only if told)."""
        self.sampler = sampler
        self.max_steps = max_steps if max_steps is not None \
            else int(os.environ.get("STEPLOG_STEPS", "256"))
        self.enabled = enabled if enabled is not None \
            else os.environ.get("STEPLOG_ENABLE", "1") != "0"
        self._lock = threading.Lock()
        self._steps: list[dict] = []
        self._seq = 0
        self._current: StepTimer | None = None  # the open step, for the watchdog
        self._stall_after_s = 1.0  # ``stall_after_s()``, kept by ``record``
        # where the last recorded step ended: (wall, thread CPU, process CPU, thread)
        self._last_end: tuple[int, int, int, int] | None = None

    # ------------------------------------------------------------ feeding

    def timer(self) -> "StepTimer":
        if self.enabled:
            _install_gc()  # ONE callback a process, and none while the ledger is off
        return StepTimer(self)

    def record(self, rec: dict) -> None:
        """Append one step record and export its metrics. No-op when
        disabled — the scheduler's timing calls still happen (clock
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
            if rec["seq"] < 16 or rec["seq"] % 16 == 0:  # the median of 256 walls moves slowly
                walls = sorted(s["wall_ms"] for s in self._steps)
                self._stall_after_s = max(1.0, 3e-3 * walls[len(walls) // 2])
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
        freezes so an overload autopsy carries the device-plane timeline.
        A stall snapshot whose step has not closed yet (it may never) rides
        along as ``stall_pending``."""
        with self._lock:
            body = {"enabled": self.enabled, "max_steps": self.max_steps,
                    "recorded": self._seq, "steps": [dict(s) for s in self._steps]}
        pending = [ev[1] for ev in list(_EVENTS) if ev[0] == "stall"]
        if pending:
            body["stall_pending"] = pending
        return body

    def clear(self) -> None:
        with self._lock:
            self._steps.clear()
            self._seq = 0
            self._last_end = None
            self._stall_after_s = 1.0

    # ------------------------------------------------------------ stalls

    def stall_after_s(self) -> float:
        """How old an open step must be before the watchdog photographs it and
        its close reads the sampler's file: three times the ring's median step,
        and at least a second. Read where every step closes and at every wake
        of the watchdog, so ``record`` keeps it (it sorted the ring's walls
        wherever it was asked)."""
        return self._stall_after_s

    def stall_snapshot(self, batcher: str, age_s: float, late_ms: float) -> dict:
        """What holds the batcher's thread, photographed from another thread
        (the watchdog's) while a step is ``age_s`` old: every thread's name and
        top six frames, whether a collection is open and since when, the names
        of the step's open spans, and how late the photograph itself came — a
        watchdog that stood still with the rest comes when the step is over,
        and THEN what counts is WHEN the stamp that needs no interpreter was
        written (``machine._Sampler``: the other half of the one ``stall`` of
        the step's record; its ``threads`` replace these). The frames are
        walked HERE, under the interpreter's lock, where they cannot move.
        Pushed onto the event ring, so that the record carries it when (if)
        the step closes; ``dump`` shows it until then."""
        names = {t.ident: t.name for t in threading.enumerate()}
        threads = []
        for ident, frame in sys._current_frames().items():
            frames = []
            while frame is not None and len(frames) < 6:
                code = frame.f_code
                frames.append(f"{os.path.basename(code.co_filename)}:{frame.f_lineno} "
                              f"{code.co_name}")
                frame = frame.f_back
            threads.append({"name": names.get(ident, str(ident)), "frames": frames})
        gc_t0, timer = _gc_t0, self._current
        snap = {"batcher": batcher, "age_ms": round(age_s * 1e3, 1),
                "late_ms": round(late_ms, 3),
                "gc_open_ms": round((time.perf_counter_ns() - gc_t0) / 1e6, 3) if gc_t0 else None,
                "open_spans": timer.open_spans() if timer is not None else [],
                "threads": threads}
        _EVENTS.append(("stall", snap))
        return snap


class _Span:
    """One open span: a TraceAnnotation on the profiler's clock, and a
    ``perf_counter_ns`` duration folded into the step's record at exit. A
    span that is a STAGE is read on the two CPU clocks too (``_clocks``)."""

    __slots__ = ("timer", "name", "stage", "part", "entry", "members", "ann", "t0", "c0", "p0",
                 "carved_ns", "carved_cpu", "carved_proc")

    def __init__(self, timer: "StepTimer", name: str, attrs: dict):
        self.timer, self.name = timer, name
        self.stage = SPAN_STAGE.get(name)
        # a request span carries the admission's ledger entry, a span named
        # under it is one of the admission's parts
        self.entry = dict(attrs) if name == REQUEST_SPAN else None
        self.members = None  # a ``_Group``'s: the entries its parts are shared over
        self.part = (name[len(REQUEST_SPAN) + 1:] + "_ms"
                     if name.startswith(REQUEST_SPAN + ".") else None)
        self.ann = annotation(name, **attrs)
        self.carved_ns = self.carved_cpu = self.carved_proc = 0

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
        if self.stage is not None:
            self.t0, self.c0, self.p0 = _clocks()
        else:
            self.t0 = time.perf_counter_ns()
        self.timer._open.append(self)
        return self

    def __exit__(self, exc_type=None, exc=None, tb=None,
                 now: tuple[int, int, int] | None = None):
        """``now``: the boundary's clocks, where ``stage()`` already read them."""
        if self.stage is not None:
            t, c, p = now or _clocks()
            dur = t - self.t0
        else:
            dur = time.perf_counter_ns() - self.t0
        self.ann.__exit__(exc_type, exc, tb)
        timer = self.timer
        timer._open.remove(self)
        if self.stage is not None:
            # a staged span inside another (a prefill call inside admit) is
            # that stage's time and not its parent's: the stages tile the
            # wall, on all three clocks
            cpu, proc = c - self.c0, p - self.p0
            for up in reversed(timer._open):
                if up.stage is not None:
                    up.carved_ns += dur
                    up.carved_cpu += cpu
                    up.carved_proc += proc
                    break
            own_cpu = cpu - self.carved_cpu
            s = self.stage
            timer.stages[s] = timer.stages.get(s, 0.0) + (dur - self.carved_ns) / 1e6
            timer.cpu[s] = timer.cpu.get(s, 0) + own_cpu
            timer.others[s] = timer.others.get(s, 0) + proc - self.carved_proc - own_cpu
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

    def __exit__(self, exc_type=None, exc=None, tb=None):
        dur = time.perf_counter_ns() - self.t0
        super().__exit__(exc_type, exc, tb)  # neither a stage nor a part
        n = len(self.members)
        if exc_type is not None:
            self.timer.admissions[:] = [a for a in self.timer.admissions
                                        if all(a is not e for e in self.members)]
            return False
        for e in self.members:
            e["request_ms"] = round(e["request_ms"] + dur / 1e6 / n, 4)
            e["rows"] = n
        return False


def _ms(ns: int) -> float:
    """Nanoseconds of a clock difference as a record's milliseconds; the two
    CPU clocks tick apart, so a difference of differences may dip under 0."""
    return max(0.0, round(ns / 1e6, 3))


class StepTimer:
    """Measures one scheduler step as spans.

    ``stage(name)`` closes the open stage span and opens ``name`` on ONE
    reading of the clocks — stage spans are contiguous, which is what makes the
    ≥95%-accounted property hold by construction. ``span(name)`` is a
    ``with`` block inside them; one whose name maps to a stage
    (``SPAN_STAGE``) is reported as that stage and taken out of the stage
    around it. ``finish`` drains the compile
    sentinel's pending events and the event ring and records; ``close``
    (idempotent) ends whatever is still open, for a step that raised or was
    abandoned."""

    def __init__(self, log: StepLog):
        self._log = log
        self.stages: dict[str, float] = {}
        self.cpu: dict[str, int] = {}  # this thread's CPU by stage, ns
        self.others: dict[str, int] = {}  # the process's CPU less this thread's, ns
        self.admissions: list[dict] = []
        self._open: list[_Span] = []
        self._stage: _Span | None = None
        self._head: tuple | None = None  # (annotation, wall, cpu, process) while open
        self.head: tuple[int, int, int] | None = None  # its three durations, once closed
        self._prev = getattr(_ACTIVE, "timer", None)
        _ACTIVE.timer = self
        self.lock_wait_ns, _ACTIVE.lock_wait_ns = getattr(_ACTIVE, "lock_wait_ns", 0), 0
        self.ident = threading.get_ident()
        log._current = self
        self._step = annotation("sched.step", step_num=log.next_seq(), step=True)
        self._step.__enter__()
        # the machine's side, just outside the wall: the OS's counters at this end
        self._m0 = machine.counters().read() if log.enabled else None
        # the sampler some watchdog of this process holds armed, if this
        # ledger's long steps read its file; and what the file held
        self._sampler = machine.armed_sampler() if log.enabled and log.sampler else None
        self.dump: dict | None = None
        # one pair of the wall clock and ``perf_counter_ns``: a file's mtime
        # (``dump_at_ms``) is mapped onto the step's clock through it
        self.t0_ns = time.time_ns()
        self.t0, self.c0, self.p0 = _clocks()
        self._t_end: tuple[int, int, int] | None = None  # where the last stage closed

    def span(self, name: str, **attrs) -> _Span:
        if self._head is not None and name.endswith(LAUNCH_TAIL):
            self._close_head(_clocks())  # the step's first launch
        return _Span(self, name, attrs)

    def group(self, members: list) -> _Group:
        """The span of what ``members`` — entries of request spans already
        closed — share: see ``_Group``."""
        return _Group(self, members)

    def stage(self, name: str) -> None:
        now = _clocks()
        first = self._t_end is None
        self._close_stage(now)
        if self._head is not None and name == "sched.decode_dispatch":
            self._close_head(now)  # a step that admitted nobody: the chunk is its first launch
        self._stage = self.span(name).__enter__()
        # the first stage runs from the step's start, the others from the
        # reading that closed the one before
        self._stage.t0, self._stage.c0, self._stage.p0 = \
            (self.t0, self.c0, self.p0) if first else now
        if name == "sched.admit":
            self._head = (annotation(HEAD_SPAN), *now)

    def _close_head(self, now: tuple[int, int, int]) -> None:
        ann, t, c, p = self._head
        ann.__exit__(None, None, None)
        self._head = None
        self.head = (now[0] - t, now[1] - c, now[2] - p)

    def _close_stage(self, now: tuple[int, int, int]) -> None:
        if self._stage is not None:
            self._stage.__exit__(now=now)
            self._stage = None
        self._t_end = now

    def open_spans(self) -> list[str]:
        """The names of the spans open now, outermost first; read from the
        watchdog's thread (a copy of the list, under the interpreter's lock)."""
        return [sp.name for sp in list(self._open)] + ([HEAD_SPAN] if self._head else [])

    def close(self) -> None:
        if self._step is None:
            return
        for sp in reversed(list(self._open)):
            sp.__exit__()
        self._stage = None
        if self._head is not None:  # a step that launched nothing has no head
            self._head[0].__exit__(None, None, None)
            self._head = None
        self._step.__exit__(None, None, None)
        self._step = None
        if self._sampler is not None and \
                time.perf_counter_ns() - self.t0 >= 1e9 * self._log.stall_after_s():
            # read at the close of a LONG step, and only then: the file is
            # empty unless the watchdog woke late (``machine._Sampler``)
            self.dump = self._sampler.take(self.t0_ns)
        if self._log._current is self:
            self._log._current = None
        if getattr(_ACTIVE, "timer", None) is self:
            _ACTIVE.timer = self._prev

    def finish(self, **meta) -> dict:
        from .compilewatch import get_compile_watcher

        # the wall closes with the LAST stage: everything after it is this
        # recorder's own overhead (pending-drain, dict assembly), which
        # must not show up as unaccounted step time — with it excluded the
        # stages tile the wall by construction
        now, t1_ns = _clocks(), time.time_ns()
        m1 = machine.counters().read() if self._m0 is not None else ()
        if self._stage is not None:
            self._close_stage(now)
        end = self._t_end if self.stages else now
        self.close()
        log = self._log
        rec = {
            "t_s": round(t1_ns / 1e9, 3),
            "t0_ns": self.t0_ns,
            "t1_ns": t1_ns,
            "wall_ms": round((end[0] - self.t0) / 1e6, 3),
            "stages": {k: round(v, 3) for k, v in self.stages.items()},
            "cpu_ms": {k: _ms(v) for k, v in self.cpu.items()},
            "others_cpu_ms": {k: _ms(v) for k, v in self.others.items()},
            "events": get_compile_watcher().take_pending(),
        }
        if self.head is not None:
            dur, cpu, proc = self.head
            rec.update(head_ms=_ms(dur), head_cpu_ms=_ms(cpu), head_others_cpu_ms=_ms(proc - cpu))
        # the gap before this step: from where the last recorded step's last
        # stage closed; the CPU clocks only where that was this thread
        last = log._last_end
        gap = (self.t0 - last[0], self.c0 - last[1], self.p0 - last[2]) \
            if last is not None and last[3] == self.ident else (0, 0, 0)
        rec.update(gap_ms=_ms(gap[0]), gap_cpu_ms=_ms(gap[1]),
                   gap_others_cpu_ms=_ms(gap[2] - gap[1]),
                   lock_wait_ms=_ms(self.lock_wait_ns))
        # a ledger that is off takes nothing off the ring (and records nothing)
        _fold_events(rec, _take_events() if log.enabled else (), self.t0, self.ident)
        if log.enabled:
            log._last_end = (*end, self.ident)
        rec.update({k: round(b - a, 3) for k, a, b in zip(machine.MACHINE_KEYS, self._m0 or (), m1)
                    if a is not None and b is not None})
        if self.dump is not None:  # beside the watchdog's photograph, where there is one
            rec["stall_dump_n"] = self.dump["dump_n"]
            rec.setdefault("stall", {"batcher": threading.current_thread().name}).update(self.dump)
        if self.admissions:
            rec["admissions"] = self.admissions
        rec.update({k: v for k, v in meta.items() if v is not None})
        log.record(rec)
        return rec


def span(name: str, **attrs):
    """The span primitive for code below the scheduler (the engine):
    part of the thread's open step when there is one, a bare
    TraceAnnotation when there is none (a direct ``engine.generate``)."""
    timer = getattr(_ACTIVE, "timer", None)
    return timer.span(name, **attrs) if timer is not None else annotation(name, **attrs)


def annotation(name: str, step: bool = False, **attrs):
    """A bare annotation on the profiler's trace, part of no step (a request's
    ``brain.*`` spans on its own threads; ``span`` is the one for a step's)."""
    # jax is imported on first use: ``utils`` is imported by processes that
    # never touch it (the rule-parser brain, the tools)
    from jax.profiler import StepTraceAnnotation, TraceAnnotation

    return (StepTraceAnnotation if step else TraceAnnotation)(name, **attrs)


_GLOBAL_STEPLOG = StepLog(sampler=True)


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
