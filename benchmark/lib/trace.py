"""From a profiler trace (``.xplane.pb``) to numbers. Pure functions over
plain tuples, so that the arithmetic is tested on a small recorded trace
and on hand-made intervals; only ``load_xplane`` touches JAX and the file,
and it is the run's one pass over it.

Intervals are ``(start_ns, end_ns)``; events are ``(name, start_ns,
dur_ns)``. Device lines nest (a ``while`` holds its body's ops), so busy
time is a UNION of intervals and an op's own time is its duration minus the
children it contains."""

from __future__ import annotations

import glob
import os

ANCHOR = "benchmark_anchor"       # the harness writes these two itself
ANCHOR_END = "benchmark_anchor_end"


SPAN_PREFIX = "sched."            # the program's own spans (``utils/steplog.py``)
HOST_PREFIXES = (ANCHOR, SPAN_PREFIX)  # the host events ``load_xplane`` keeps
SCOPE_STAT = "tf_op"  # where a TPU trace keeps an op's named_scope path


def load_xplane(path: str) -> dict:
    """The run's ``.xplane.pb``, read ONCE for every reader of the run:
    ``{"device": {plane: {line: [(name, start_ns, dur_ns)]}}, "host":
    [(name, start_ns, dur_ns)], "scope": {op name: scope path}}`` — host
    events only where named like an anchor or like the program's spans (the
    rest of the host plane is large and unused); scope paths of the first
    plane that holds operations, the one ``reduce`` reads by operation."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: dict = {"device": {}, "host": [], "scope": {}}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = out["device"].setdefault(plane.name, {})
            for line in plane.lines:
                lines[line.name] = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                                    for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIXES):
                        out["host"].append((ev.name, int(ev.start_ns), int(ev.duration_ns)))
    with_ops = sorted(op_lines(out["device"]))
    if with_ops:
        out["scope"] = op_scopes(path, with_ops[0])
    return out


def _varint(buf, i: int) -> tuple[int, int]:
    n = shift = 0
    while True:
        b = buf[i]
        i += 1
        n |= (b & 0x7F) << shift
        shift += 7
        if not b & 0x80:
            return n, i


def _fields(buf):
    """(field number, value) of one protobuf message: a varint as an int, a
    length-delimited field as a memoryview, a fixed-width one as None."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            n, i = _varint(buf, i)
            yield key >> 3, n
        elif wire == 2:
            n, i = _varint(buf, i)
            yield key >> 3, buf[i:i + n]
            i += n
        else:  # fixed 64 (1) or fixed 32 (5)
            yield key >> 3, None
            i += 8 if wire == 1 else 4


def op_scopes(path: str, plane_name: str) -> dict[str, str]:
    """``{an operation's trace name: its scope path}`` for one plane, from
    the ``.xplane.pb`` itself. On a TPU v5e an XLA op's ``jax.named_scope``
    path (HLO ``op_name`` metadata) is the stat ``tf_op`` of its EVENT
    METADATA, which ``jax.profiler.ProfileData`` does not show (an event's
    ``stats`` are its own three: offset, duration, time scale). The file is
    an ``XSpace`` message; only the named plane's two metadata maps are
    walked (XPlane: 2 name, 4 event_metadata, 5 stat_metadata; XEventMetadata:
    2 name, 5 stats; XStat: 1 metadata_id, 5 str_value, 7 ref_value)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    text = lambda v: bytes(v).decode("utf-8", "replace")
    for field, plane in _fields(space):
        if field != 1:
            continue
        parts = list(_fields(plane))
        if not any(f == 2 and text(v) == plane_name for f, v in parts):
            continue
        stat_names = {}
        for f, entry in parts:
            if f == 5:  # map entry: 1 key, 2 XStatMetadata(1 id, 2 name)
                meta = dict((k, v) for k, v in _fields(dict(_fields(entry))[2]) if k in (1, 2))
                stat_names[meta.get(1, 0)] = text(meta.get(2, b""))
        out = {}
        for f, entry in parts:
            if f != 4:  # map entry: 1 key, 2 XEventMetadata
                continue
            name, scope = None, None
            for k, v in _fields(dict(_fields(entry))[2]):
                if k == 2:
                    name = text(v)
                elif k == 5:
                    st = dict(_fields(v))
                    if stat_names.get(st.get(1)) == SCOPE_STAT:
                        scope = text(st[5]) if 5 in st else stat_names.get(st.get(7), "")
            if name and scope:
                out[name] = scope.rstrip(":")
        return out
    return {}


def first_plane(trace: dict) -> dict | None:
    """What the readers of the program's spans and scopes read
    (``readers/host_spans.py``, ``scopes.py``): ``{"spans": [(name, start_ns,
    end_ns)], "anchors": {name: start_ns}, "ops": [(name, start_ns, dur_ns)],
    "modules": [...], "scope": {op name: scope path}}`` of the first device
    plane that holds operations and the host's kept events; None without one."""
    with_ops = sorted(op_lines(trace["device"]))
    if not with_ops:
        return None
    lines = trace["device"][with_ops[0]]
    host = trace["host"]
    return {"spans": sorted(((n, s, s + d) for n, s, d in host if not n.startswith(ANCHOR)),
                            key=lambda e: e[1]),
            "anchors": {n: s for n, s, _ in host if n.startswith(ANCHOR)},
            "ops": lines["XLA Ops"], "modules": lines.get("XLA Modules", []),
            "scope": trace.get("scope", {})}


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def op_lines(planes: dict) -> dict:
    """Per device plane, the line that holds single operations."""
    return {plane: lines["XLA Ops"] for plane, lines in planes.items() if "XLA Ops" in lines}


def short_name(name: str, limit: int = 96) -> str:
    """An HLO op's trace name is its whole instruction text: keep the
    result name and its type, ``fusion.348 bf16[32,9,14336]``."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:limit]
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{head.lstrip('%')} {shape}"[:limit]


def clip(events, lo: int, hi: int) -> list[tuple[int, int]]:
    out = []
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(intervals) -> int:
    return sum(b - a for a, b in union(intervals))


def gaps(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """The idle stretches of [lo, hi): the complement of the busy union."""
    out, at = [], lo
    for a, b in union(intervals):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def self_times(events) -> dict[str, int]:
    """Own time per op name: duration minus directly nested children."""
    total: dict[str, int] = {}
    stack: list[list] = []  # [name, end, child_ns]

    def close(upto: int) -> None:
        while stack and stack[-1][1] <= upto:
            name, end, child, start = stack.pop()
            total[name] = total.get(name, 0) + (end - start) - child
            if stack:
                stack[-1][2] += end - start

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        close(s)
        stack.append([name, s + d, 0, s])
    close(1 << 62)
    return total


def attribute(gap_list, spans) -> dict[str, int]:
    """Idle nanoseconds by what the host was doing: each gap is split over
    the named host spans ``(name, start_ns, end_ns)`` it overlaps; what no
    span covers is ``unattributed``."""
    out: dict[str, int] = {}
    spans = sorted(spans, key=lambda s: s[1])
    for a, b in gap_list:
        covered = 0
        for name, s, e in spans:
            if e <= a:
                continue
            if s >= b:
                break
            o = min(b, e) - max(a, s)
            if o > 0:
                out[name] = out.get(name, 0) + o
                covered += o
        if b - a - covered > 0:
            out["unattributed"] = out.get("unattributed", 0) + (b - a - covered)
    return out


def stage_spans(steps: list[dict], offset_ns: int, stages: tuple[str, ...]) -> list[tuple]:
    """Host spans on the trace's clock from step-ledger records: a record
    closes at wall time ``t_s`` and its stages tile ``wall_ms`` in ledger
    order; between two steps the loop waits for work (``between_steps``).
    ``offset_ns`` = trace clock minus wall clock, from the anchor."""
    spans, prev_end = [], None
    for rec in sorted(steps, key=lambda r: r["t_s"]):
        end = int(rec["t_s"] * 1e9) + offset_ns
        at = end - int(rec["wall_ms"] * 1e6)
        if prev_end is not None and at > prev_end:
            spans.append(("between_steps", prev_end, at))
        for st in stages:
            ms = rec["stages"].get(st, 0.0)
            if ms > 0:
                spans.append((st, at, at + int(ms * 1e6)))
                at += int(ms * 1e6)
        prev_end = end
    return spans


def reduce(trace: dict, steps: list[dict], anchor_wall_s: float, stages: tuple[str, ...],
           n_chips: int = 1) -> dict | None:
    """Everything the harness reports from one trace, or None when the trace
    holds no device operation inside the anchored window."""
    anchors = {n: s for n, s, _ in trace["host"] if n.startswith(ANCHOR)}
    ops = op_lines(trace["device"])
    if not ops:
        return None
    all_starts = [s for evs in ops.values() for _, s, _ in evs]
    all_ends = [s + d for evs in ops.values() for _, s, d in evs]
    if not all_starts:
        return None
    lo = anchors.get(ANCHOR, min(all_starts))
    hi = anchors.get(ANCHOR_END, max(all_ends))
    planes = sorted(ops)[:n_chips]
    busy = [busy_ns(clip(ops[p], lo, hi)) for p in planes]
    if not any(busy):
        return None
    first = planes[0]
    own = self_times([(short_name(n), s, d) for n, s, d in ops[first] if s + d > lo and s < hi])
    spans = []
    if ANCHOR in anchors:
        spans = stage_spans(steps, anchors[ANCHOR] - int(anchor_wall_s * 1e9), stages)
    idle = attribute(gaps(clip(ops[first], lo, hi), lo, hi), spans)
    modules = trace["device"][first].get("XLA Modules", [])
    progs: dict[str, list[int]] = {}
    for n, s, d in modules:
        if s >= lo and s + d <= hi:
            progs.setdefault(n, []).append(d)
    top = lambda d: [[k, v / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"busy_s": sum(busy) / len(busy) / 1e9, "window_s": (hi - lo) / 1e9,
            "device_ops": top(own), "idle_gaps": top(idle),
            "programs": {k: {"count": len(v), "total_s": sum(v) / 1e9} for k, v in progs.items()},
            "anchored": ANCHOR in anchors and ANCHOR_END in anchors,
            "plane": first_plane(trace)}
