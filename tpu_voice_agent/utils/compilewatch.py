"""Recompilation sentinel: cache-miss counting on the jitted entry points.

The classic JAX serving failure is shape-bucket churn: a prompt that lands
in a bucket nobody warmed, or mutable state rebuilt with a new shape after
a restart, silently re-traces and re-compiles an entry point mid-serving —
and the only symptom is an unexplained multi-hundred-ms p99 spike. The
engines here are shape-bucketed precisely so that compile count is bounded
(serve/colocate.py's zero-recompilation contract), but nothing ever
*verified* that at runtime.

This module makes every trace/compile a named, countable event:

- ``watch_compiles(site)`` wraps a jitted callable. Each call compares the
  function's jit-cache size before/after (``_cache_size()`` — stable on the
  jax versions this repo supports); growth means THIS call traced+compiled,
  and the call's wall time is dominated by that compile. The event records
  the call site, the wall ms, and the argument shape signature — the three
  things an operator needs to find the offending bucket.
- events feed the process-global ``CompileWatcher``: ``xla.compiles`` /
  ``xla.compile_ms`` counters, a bounded event ring, and a pending list
  the step ledger (utils/steplog.py) drains so a compile shows up as a
  "compile stall" event on the exact scheduler step it stalled.
- the **warmup fence**: once armed (``arm_fence``), further compiles count
  as ``xla.compiles_post_fence`` and raise a /health warning — serving was
  declared warm, so any new trace is the silent-p99-cliff failure made
  alertable. ``DecodeEngine.warm_restart`` re-arms the fence: a restart
  reuses compiled programs, so a post-restart retrace is exactly as
  suspicious as any other post-warm compile.

Overhead: two C++ cache-size reads and two perf_counter calls per watched
dispatch — noise against a chunk forward. ``XLA_SENTINEL=0`` disables the
wrapping entirely (callables pass through untouched).
"""

from __future__ import annotations

import functools
import os
import threading
import time
from collections import deque


def _shape_sig(args: tuple, kwargs: dict, limit: int = 6) -> str:
    """Compact shape signature of a call: the top-level array args' dtypes
    and shapes (the bucket-bearing ones), container args summarized by
    leaf count. Capped — this is an event label, not a dump."""
    parts: list[str] = []
    items = list(args) + [v for _, v in sorted(kwargs.items())]
    for a in items:
        if len(parts) >= limit:
            parts.append("…")
            break
        shape = getattr(a, "shape", None)
        dtype = getattr(a, "dtype", None)
        if shape is not None and dtype is not None:
            parts.append(f"{dtype}[{','.join(str(s) for s in shape)}]")
        elif isinstance(a, dict):
            parts.append(f"dict({len(a)})")
        elif isinstance(a, (list, tuple)):
            parts.append(f"seq({len(a)})")
        elif isinstance(a, (int, float, bool, str)) or a is None:
            parts.append(repr(a)[:24])
        # anything else (FSM tables, rules, callables) is static config
        # that rarely distinguishes a retrace — skip it
    return " ".join(parts)


class CompileWatcher:
    """Process-global compile-event collector + warmup fence."""

    def __init__(self, max_events: int | None = None):
        self.max_events = max_events if max_events is not None \
            else int(os.environ.get("XLA_SENTINEL_EVENTS", "128"))
        self._lock = threading.Lock()
        self._events: deque[dict] = deque(maxlen=self.max_events)
        self._pending: list[dict] = []  # drained by the step ledger
        self._fence_armed = False
        self._fence_reason: str | None = None
        self._compiles = 0
        self._compile_ms = 0.0
        self._post_fence = 0
        self._last: dict | None = None
        # auto-arm: a compile landing after XLA_FENCE_QUIET_S of compile
        # silence arms the fence implicitly — serving that stopped tracing
        # for that long was warm in every way that matters, and explicit
        # arming (service startup, warm_restart) can't know every topology
        self._quiet_s = float(os.environ.get("XLA_FENCE_QUIET_S", "120"))
        self._last_compile_t: float | None = None

    # ------------------------------------------------------------ fence

    def arm_fence(self, reason: str = "manual") -> None:
        """Declare serving warm: every compile from here on is a named,
        alertable event (``xla.compiles_post_fence`` + /health warning).
        Idempotent; ``warm_restart`` re-arms so post-restart retraces are
        flagged too (the restart reuses compiled programs — a new trace
        after one means the mutable state came back with a new shape)."""
        with self._lock:
            self._fence_armed = True
            self._fence_reason = reason

    def disarm_fence(self) -> None:
        with self._lock:
            self._fence_armed = False
            self._fence_reason = None

    @property
    def fence_armed(self) -> bool:
        return self._fence_armed

    # ------------------------------------------------------------ record

    def record(self, site: str, ms: float, signature: str) -> dict:
        from . import get_metrics, log_event

        # expected-compile allowlist: site prefixes the operator has
        # declared legitimately lazy (XLA_EXPECTED_COMPILES="stt."
        # — e.g. an STT model loaded on first use). Still counted and
        # ringed as compiles, but never flagged post-fence: the alert is
        # for SURPRISE traces only. Read per event (compiles are rare) so
        # tests and live operators can tune it without a restart.
        allow = tuple(s for s in
                      os.environ.get("XLA_EXPECTED_COMPILES", "").split(",")
                      if s)
        expected = any(site.startswith(a) for a in allow)
        with self._lock:
            now_m = time.monotonic()
            if (not self._fence_armed and self._quiet_s > 0
                    and self._last_compile_t is not None
                    and now_m - self._last_compile_t > self._quiet_s):
                self._fence_armed = True
                self._fence_reason = f"auto: {self._quiet_s:g}s compile-quiet"
            self._last_compile_t = now_m
            post_fence = self._fence_armed and not expected
        ev = {
            "site": site,
            "ms": round(ms, 3),
            "shape": signature,
            "t_s": round(time.time(), 3),
            "post_fence": post_fence,
        }
        with self._lock:
            self._events.append(ev)
            if len(self._pending) < self.max_events:
                self._pending.append(ev)
            self._compiles += 1
            self._compile_ms += ms
            if ev["post_fence"]:
                self._post_fence += 1
            self._last = ev
        m = get_metrics()
        m.inc("xla.compiles")
        m.inc("xla.compile_ms", ms)
        if ev["post_fence"]:
            m.inc("xla.compiles_post_fence")
            # the alertable line: a compile AFTER the warmup fence is the
            # shape-churn failure — name the site and bucket, loudly
            log_event("xla", "recompile_after_fence", site=site,
                      ms=round(ms, 1), shape=signature)
        return ev

    # ------------------------------------------------------------ reading

    def take_pending(self) -> list[dict]:
        """Drain events recorded since the last drain (the step ledger
        calls this per scheduler step, so a compile lands as an event on
        the step it stalled)."""
        with self._lock:
            out, self._pending = self._pending, []
        return out

    def events(self, last: int | None = None) -> list[dict]:
        with self._lock:
            evs = list(self._events)
        return evs[-last:] if last else evs

    def state(self) -> dict:
        """The /health surface: counters, fence status, the last event,
        and a human warning line when post-fence compiles occurred."""
        with self._lock:
            body = {
                "compiles": self._compiles,
                "compile_ms": round(self._compile_ms, 1),
                "fence_armed": self._fence_armed,
                "fence_reason": self._fence_reason,
                "post_fence_compiles": self._post_fence,
                "last": dict(self._last) if self._last else None,
            }
        if body["post_fence_compiles"]:
            last = body["last"] or {}
            body["warning"] = (
                f"{body['post_fence_compiles']} recompile(s) after the "
                f"warmup fence (last: {last.get('site')} "
                f"{last.get('ms', 0):.0f} ms)")
        return body

    def reset(self) -> None:
        """Tests only: the watcher is process-global and tests share it."""
        with self._lock:
            self._events.clear()
            self._pending.clear()
            self._fence_armed = False
            self._fence_reason = None
            self._compiles = 0
            self._compile_ms = 0.0
            self._post_fence = 0
            self._last = None
            self._last_compile_t = None


_GLOBAL_WATCHER = CompileWatcher()


def get_compile_watcher() -> CompileWatcher:
    return _GLOBAL_WATCHER


def watch_compiles(site: str):
    """Decorator for a jitted entry point: count its cache misses as
    compile events tagged ``site``. Passes the callable through untouched
    when the sentinel is disabled (``XLA_SENTINEL=0``) or the jit object
    does not expose a cache size (exotic wrappers)."""

    def deco(fn):
        if os.environ.get("XLA_SENTINEL", "1") == "0":
            return fn
        if not hasattr(fn, "_cache_size"):
            return fn

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            before = fn._cache_size()
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            if fn._cache_size() > before:
                # this call traced+compiled: its wall time is the compile
                # stall (dispatch is async — execution is not in it)
                _GLOBAL_WATCHER.record(
                    site, (time.perf_counter() - t0) * 1e3,
                    _shape_sig(args, kwargs))
            return out

        wrapped.__wrapped__ = fn
        return wrapped

    return deco
