"""The machine's side of a step (ISSUE 52): what the step ledger
(``utils/steplog.py``) reads from BELOW the interpreter, kept apart from it for
room alone — the ledger is its only caller, a step's record its only sink.

``_Sampler``: a STAMP that needs no interpreter, held by the colocate watchdog
as a dead man's switch — a POSIX timer of the kernel's whose expiry the C
library answers on a thread of its own. A step that outlasts the ledger's
threshold finds the stamp if the watchdog could not wake: made ON TIME while
every Python thread stands still behind a held interpreter, and LATE only
where the whole process stood. It walks no thread's frames (that killed the
process: below).
``_Machine``: the OS's counters at one end of a step (``MACHINE_KEYS``), each
source probed once a process and its file kept open. Nothing here is touched
while the ledger is off: ``counters()`` and ``sampler()`` build on first use.
"""

from __future__ import annotations

import ctypes
import faulthandler
import os
import re
import resource
import tempfile
import threading
import time

# The machine's side of a record (ISSUE 52), source by source. A key whose
# source the machine lacks is in NO record: zero would say "no delay".
MACHINE_KEYS = ("run_delay_ms", "majflt", "throttled_ms")


def _open(path: str) -> int:
    """A source's file, opened ONCE and kept (what is read at a step's ends is
    ``os.pread`` of a small buffer: an ``open`` a read costs twenty such
    reads); -1 where the machine has none."""
    try:
        return os.open(path, os.O_RDONLY)
    except OSError:
        return -1


def _peek(fd: int, size: int) -> bytes:
    """A PROBE's read of a file that opened: nothing where it cannot be read
    either (a sandbox's kernel may list a file it does not serve)."""
    try:
        return os.pread(fd, size, 0)
    except OSError:
        return b""


def _cpu_stat_paths() -> list[str]:
    """Where this process's cgroup keeps ``cpu.stat``: its own group by
    ``/proc/self/cgroup`` (v2's one line ``0::/path``, v1's line of the ``cpu``
    controller), then the mount's root (where a container's namespace shows
    its own group)."""
    paths = []
    try:
        with open("/proc/self/cgroup") as f:
            for line in f:
                _, ctl, path = line.rstrip("\n").split(":", 2)
                if not ctl or "cpu" in ctl.split(","):
                    paths.append(f"/sys/fs/cgroup/{ctl}{path}/cpu.stat")
    except (OSError, ValueError):
        pass
    return paths + ["/sys/fs/cgroup/cpu.stat", "/sys/fs/cgroup/cpu/cpu.stat"]


def _kernel_counts() -> bool:
    """Whether ``getrusage`` COUNTS here. A sandbox's kernel may answer it and
    count nothing (the benchmark's machines: PERF.md section 6, PR 52), and a
    0 from it is then no "no fault". The probe cannot misread a young process:
    a sleep IS a voluntary switch, so a kernel that keeps the books has one
    more on them behind it."""
    before = resource.getrusage(resource.RUSAGE_SELF).ru_nvcsw
    time.sleep(0.001)
    return resource.getrusage(resource.RUSAGE_SELF).ru_nvcsw > before


class _OwnSchedstat:
    """``/proc/thread-self/schedstat`` as the thread that OPENED it names it:
    the file is that thread's whoever reads it, so each thread that runs steps
    opens its own (a loop ``_restart_worker`` starts probes anew) and the file
    closes with the thread."""

    __slots__ = ("fd",)

    def __init__(self):
        self.fd = _open("/proc/thread-self/schedstat")
        if self.fd >= 0 and len(_peek(self.fd, 64).split()) < 2:
            os.close(self.fd)
            self.fd = -1

    def __del__(self):
        if self.fd >= 0:
            os.close(self.fd)


class _Machine:
    """The OS's counters at one end of a step, in a record's units and
    ``MACHINE_KEYS``' order; None for a source the machine lacks. Each source
    is probed ONCE a process (a thread's own once a thread) and its file kept
    open. What a deployment's plain Linux has and the table cause → signature
    of ``docs/OBSERVABILITY.md`` reads: the thread's context switches and the
    hypervisor's ``steal`` (a ``pread`` of ``/proc/stat`` took 24-28 us on the
    benchmark's machines, half of what a step's two ends may cost) are NOT
    read."""

    def __init__(self):
        self._own = threading.local()
        self._counts = _kernel_counts()
        self._throttle: tuple[int, bytes, float] | None = None  # file, key, its unit in ms
        for path in _cpu_stat_paths():
            fd = _open(path)
            buf = _peek(fd, 512) if fd >= 0 else b""
            # v2 counts microseconds, v1 nanoseconds; a root group counts neither
            unit = [(key, ms) for key, ms in ((b"throttled_usec ", 1e-3), (b"throttled_time ", 1e-6))
                    if key in buf]
            if unit:
                self._throttle = (fd, *unit[0])
                break
            if fd >= 0:
                os.close(fd)

    def run_delay_ms(self) -> float | None:
        """The CALLING thread runnable and not run, so far: the second field of
        its ``schedstat`` ("<on CPU ns> <runnable and not run ns> <slices>")."""
        own = getattr(self._own, "file", None)
        if own is None:
            own = self._own.file = _OwnSchedstat()
        return int(os.pread(own.fd, 64, 0).split()[1]) / 1e6 if own.fd >= 0 else None

    def majflt(self) -> int | None:
        """The PROCESS's major faults, so far."""
        return resource.getrusage(resource.RUSAGE_SELF).ru_majflt if self._counts else None

    def throttled_ms(self) -> float | None:
        if self._throttle is None:
            return None
        fd, key, ms = self._throttle
        buf = os.pread(fd, 512, 0)
        return int(buf[buf.find(key) + len(key):].split(None, 1)[0]) * ms

    def read(self) -> tuple:
        return (self.run_delay_ms(), self.majflt(), self.throttled_ms())

    def sources(self) -> dict[str, bool]:
        """Which of ``MACHINE_KEYS`` this machine has, for the calling thread."""
        return {k: v is not None for k, v in zip(MACHINE_KEYS, self.read())}


_DUMP_THREAD = re.compile(r"(?:Current t|T)hread 0x([0-9a-f]+) ")
_DUMP_FRAME = re.compile(r'  File "(.*)", line (\d+) in (.*)')
_MTIME_TICK_NS = 20_000_000  # a file's mtime is the kernel's tick coarse


class _Sigevent(ctypes.Structure):
    """``struct sigevent`` of Linux (64 bytes on every ABI glibc and musl
    have): the value handed on, how to notify, and for ``SIGEV_THREAD`` the
    function a thread of the C library's runs."""

    _fields_ = [("value", ctypes.c_void_p), ("signo", ctypes.c_int), ("notify", ctypes.c_int),
                ("function", ctypes.c_void_p), ("attribute", ctypes.c_void_p),
                ("_pad", ctypes.c_byte * 32)]


class _Itimerspec(ctypes.Structure):
    _fields_ = [("every_s", ctypes.c_long), ("every_ns", ctypes.c_long),
                ("after_s", ctypes.c_long), ("after_ns", ctypes.c_long)]


_CLOCK_MONOTONIC, _SIGEV_THREAD = 1, 2


class _Sampler:
    """The process's STAMP that needs no interpreter, a DEAD MAN'S SWITCH: the
    colocate watchdog, a Python thread, arms it anew at every wake
    (``ColocatedServing._watch``), so it fires only where that thread could not
    wake for ``after_s`` — where every Python thread stands still. A POSIX
    timer (``timer_create``, ``SIGEV_THREAD``): at its expiry the C library
    starts a thread of its own, which holds no interpreter lock, takes no
    signal to any thread of ours and touches no thread's state; what it runs
    is ``mkdtemp`` — the one function of the C library that takes ONE pointer
    and leaves a time behind: an empty directory under this sampler's private
    temporary directory, whose mtime is the stamp. ON TIME behind a held
    interpreter; at the moment the process runs again where the whole process
    stood. Arming is two system calls and a lock (5 us here, 17 us on the
    benchmark's machines; the watchdog's, twice a second).

    WHY no frames: ISSUE 52 had ``faulthandler.dump_traceback_later`` armed a
    step. Its C thread walks EVERY thread's frames without the interpreter's
    lock; fired into a process whose threads run Python it killed 9 of 18
    benchmark runs on the chip's machine (PERF.md section 6, PR 52) — and a
    process the machine froze thaws with all its threads running and that
    timer expired. ``frames = True`` arms that walk IN PLACE of the stamp; it
    names the thread that holds the interpreter inside a native call
    (``stall.threads``). For a drill (``tools/host_wait_check.py --drill
    hold``) and a debugging session whose stamps came on time — set by hand,
    never by the serving code; there is ONE such timer a process, and this
    takes it from whoever armed it before."""

    def __init__(self):
        libc = ctypes.CDLL(None, use_errno=True)
        self._dir = tempfile.TemporaryDirectory(prefix="steplog-stamp-")
        self._blank = os.path.join(self._dir.name, "XXXXXX").encode()
        self._made = ctypes.create_string_buffer(self._blank)  # ``mkdtemp`` writes the name here
        event = _Sigevent(value=ctypes.addressof(self._made), notify=_SIGEV_THREAD,
                          function=ctypes.cast(libc.mkdtemp, ctypes.c_void_p).value)
        self._timer = ctypes.c_void_p()
        if libc.timer_create(_CLOCK_MONOTONIC, ctypes.byref(event), ctypes.byref(self._timer)):
            raise OSError(ctypes.get_errno(), "timer_create(SIGEV_THREAD)")
        self._settime = libc.timer_settime
        self._settime.argtypes = [ctypes.c_void_p, ctypes.c_int, ctypes.c_void_p, ctypes.c_void_p]
        self._gettime = libc.timer_gettime
        self._gettime.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
        self.frames = False
        self.file = None  # the walk's dumps, opened with the first walk armed
        self.owner = None
        self.after_s = 0.0
        self._walks = False  # which of the two is armed
        self._running = False  # the timer was set and has not been seen to run out
        self._owed, self._waited = False, 0  # it ran out and its stamp has not landed; wakes since
        self._fired_ns: int | None = None  # when the last stamp that landed was made
        self._lock = threading.Lock()  # two batchers' long steps may close together

    def _left(self) -> bool:
        """Whether the timer still runs (the kernel's word): one that was set
        running and does not has EXPIRED."""
        left = _Itimerspec()
        self._gettime(self._timer, ctypes.byref(left))
        return bool(left.after_s or left.after_ns)

    def _stamp_after(self, after_s: float) -> None:
        """(0 disarms.)"""
        spec = _Itimerspec(0, 0, int(after_s), int(after_s % 1 * 1e9))
        self._settime(self._timer, 0, ctypes.byref(spec), None)
        self._running = after_s > 0

    def _collect(self) -> None:
        """A stamp that has landed, taken off the directory: when it was made
        is kept for the step that closes next, and the name is blank again for
        the next one. One that is OWED — the timer ran out, nothing is there
        yet — is on its way: at a thaw the C library's thread starts among all
        of ours that wake."""
        if self._running and not self._left():
            self._running, self._owed, self._waited = False, True, 0
        made = self._made.value
        if made != self._blank:
            try:
                at_ns = os.stat(made).st_mtime_ns
                os.rmdir(made)
            except OSError:  # named and not made yet
                return
            self._made.value, self._fired_ns, self._owed = self._blank, at_ns, False

    def arm(self, owner, after_s: float) -> None:
        with self._lock:
            if self._walks != self.frames and self.owner is not None:
                self._disarm()  # the other one was armed: never both
            if self.frames:
                if self.file is None:
                    self.file = tempfile.TemporaryFile(buffering=0)
                faulthandler.dump_traceback_later(after_s, repeat=False, file=self.file)
            else:
                self._collect()
                if self._owed and self._waited < 2:
                    # spent, its stamp on its way: to set a timer anew DROPS an
                    # expiry the kernel has not handed over yet — the very
                    # stamp this switch is there for. The next wake arms it
                    self._waited += 1
                    return
                self._owed = False
                self._stamp_after(after_s)
            self.owner, self.after_s, self._walks = owner, after_s, self.frames

    def _disarm(self) -> None:
        if self._walks:
            faulthandler.cancel_dump_traceback_later()
        else:
            self._stamp_after(0.0)

    def cancel(self, owner) -> None:
        """Only the one who armed it last disarms it (a watchdog that stops
        must not take the switch from its successor)."""
        with self._lock:
            if self.owner is owner:
                self._disarm()
                self.owner, self._owed = None, False

    def take(self, t0_ns: int) -> dict:
        """What the switch left behind, taken off it, for a step that started
        at ``time.time_ns()`` ``t0_ns``: ``dump_n`` (0: the step was long and
        the switch never fired — the watchdog woke in time, Python threads
        ran), the time without a wake it was armed with (``after_ms``) and when
        it fired (``dump_at_ms``, relative to the step's start as
        ``gc[*].at_ms`` is). A stamp from before the step opened is nobody's
        and dropped. Of a walk (``frames``) also every thread's name and top
        six frames (``threads``, the shape ``StepLog.stall_snapshot`` writes)."""
        dumps: list[str] = []
        with self._lock:
            # a stamp that is owed: this step was seconds long, its close can
            # wait a fifth of one for its cause
            give_up = time.monotonic() + 0.2
            self._collect()
            while self._owed and time.monotonic() < give_up:
                time.sleep(0.001)
                self._collect()
            at_ns, self._fired_ns = self._fired_ns, None
            if self.file is not None and (st := os.fstat(self.file.fileno())).st_size:
                fd = self.file.fileno()
                dumps = os.pread(fd, st.st_size, 0).decode("utf-8", "replace").split("Timeout (")[1:]
                os.ftruncate(fd, 0)
                os.lseek(fd, 0, os.SEEK_SET)  # the timer writes where the last dump ended
                at_ns = st.st_mtime_ns
        if at_ns is None or at_ns < t0_ns - _MTIME_TICK_NS:
            return {"dump_n": 0}
        out = {"dump_n": max(1, len(dumps)), "after_ms": round(self.after_s * 1e3, 1),
               "dump_at_ms": round((at_ns - t0_ns) / 1e6, 3)}
        if dumps:
            # Python 3.12's dump names a thread by its ident alone
            names = {t.ident: t.name for t in threading.enumerate()}
            threads = out["threads"] = []
            for line in dumps[-1].splitlines():
                if m := _DUMP_THREAD.match(line):
                    ident = int(m[1], 16)
                    threads.append({"name": names.get(ident, str(ident)), "frames": []})
                elif threads and len(threads[-1]["frames"]) < 6 and (m := _DUMP_FRAME.match(line)):
                    threads[-1]["frames"].append(f"{os.path.basename(m[1])}:{m[2]} {m[3]}")
        return out


_MACHINE: _Machine | None = None
_SAMPLER: _Sampler | bool | None = None  # False: tried, and the machine has none


def counters() -> _Machine:
    global _MACHINE
    if _MACHINE is None:
        _MACHINE = _Machine()
        from .tracing import log_event

        # once a process, in its log: which keys its records will lack
        log_event("steplog", "machine.sources", **_MACHINE.sources())
    return _MACHINE


def sampler() -> _Sampler | None:
    """The process's one sampler; None on a machine whose C library or kernel
    has no such timer (said once in the process's log)."""
    global _SAMPLER
    if _SAMPLER is None:
        try:
            _SAMPLER = _Sampler()
        except (OSError, AttributeError) as e:
            from .tracing import log_event

            log_event("steplog", "machine.sampler_unavailable", error=repr(e))
            _SAMPLER = False
    return _SAMPLER or None


def armed_sampler() -> _Sampler | None:
    """The sampler, if somebody in this process holds it armed."""
    return _SAMPLER if _SAMPLER and _SAMPLER.owner is not None else None
