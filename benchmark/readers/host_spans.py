"""Reader ``host_spans``: the device's idle time under the program's OWN
spans (``lib.trace.gaps`` of the device's operations, cut by them). The
batcher writes ``sched.*`` spans (``utils/steplog.py``) onto the profiler's
trace as it works, so they lie on the clock the device's operations lie on: no ledger stamp rounded to a millisecond, no stages laid
end to end after the fact (``lib/trace.stage_spans``). The run's
``.xplane.pb`` is parsed once, for all metrics of the run.

``idle_ms_per_span`` — idle nanoseconds of the anchored stretch lying under
spans named ``span``, per such span that starts inside the stretch.
``attributed_share`` — the share of all idle time in the stretch that SOME
``sched.*`` span covers (their union: a step's stages nest inside it).

Clocks: a ``*.prefill_call`` / ``sched.decode_dispatch`` span launches one
device program, which cannot start before the span does. Where the smallest
(program start − span start) over the stretch is negative, the host's and
the device's timestamps disagree by at least that much: the spans are
shifted by it, and the shift is printed."""

from __future__ import annotations

import os

from ..lib import trace as tr

PREFIX = "sched."
# a launching span (by the end of its name) -> the program it dispatches
LAUNCHES = {".prefill_call": "forward_paged", "sched.decode_dispatch": "paged_chunk_decode_loop"}
SCOPE_STAT = "tf_op"  # where a TPU trace keeps an op's named_scope path
PAIR_SLACK_NS = 5_000_000  # a program may read as starting this long before its launch
_parsed: dict = {}  # the run's trace, parsed once


def load(path: str) -> dict:
    """``{"spans": [(name, start_ns, end_ns)], "anchors": {name: start_ns},
    "ops": [(name, start_ns, dur_ns)], "modules": [...], "scope": {op name:
    scope path}}`` of the first device plane and the host's ``sched.*``
    events; None without a device."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans, anchors, device = [], {}, {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        s = int(ev.start_ns)
                        spans.append((ev.name, s, s + int(ev.duration_ns)))
                    elif ev.name.startswith(tr.ANCHOR):
                        anchors[ev.name] = int(ev.start_ns)
        elif plane.name.startswith("/device:") and any(ln.name == "XLA Ops" for ln in plane.lines):
            device[plane.name] = plane  # a plane of operations, as lib.trace.op_lines picks them
    if not device:
        return None
    first = sorted(device)[0]
    lines = {ln.name: [(ev.name, int(ev.start_ns), int(ev.duration_ns)) for ev in ln.events]
             for ln in device[first].lines if ln.name in ("XLA Ops", "XLA Modules")}
    return {"spans": sorted(spans, key=lambda s: s[1]), "anchors": anchors,
            "ops": lines.get("XLA Ops", []), "modules": lines.get("XLA Modules", []),
            "scope": op_scopes(path, first)}


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


def clock_shift(spans, modules, launches=LAUNCHES) -> tuple[int, int]:
    """(shift_ns <= 0, pairs): the smallest program start − launching span
    start, where below zero. Spans and programs are paired in order: a
    span's program is the first one of its kind not yet taken that starts
    no more than ``PAIR_SLACK_NS`` before the span."""
    least, pairs = None, 0
    for tail, program in launches.items():
        starts = sorted(s for n, s, _ in modules if program in n)
        at = 0
        for name, s0, _ in spans:
            if not name.endswith(tail):
                continue
            while at < len(starts) and starts[at] < s0 - PAIR_SLACK_NS:
                at += 1
            if at == len(starts):
                break
            least = starts[at] - s0 if least is None else min(least, starts[at] - s0)
            at += 1
            pairs += 1
    return min(least or 0, 0), pairs


def overlap_ns(gaps, intervals) -> int:
    """Nanoseconds of ``gaps`` lying inside ``intervals``; both sorted and
    disjoint (``lib.trace.gaps`` / ``union`` give them so). One pass over
    the two lists: ``lib.trace.attribute`` walks every span for every gap,
    and a traced stretch holds 10^5 gaps."""
    total, j = 0, 0
    for a, b in gaps:
        while j < len(intervals) and intervals[j][1] <= a:
            j += 1
        k = j
        while k < len(intervals) and intervals[k][0] < b:
            total += min(b, intervals[k][1]) - max(a, intervals[k][0])
            k += 1
    return total


def stretch(trace: dict) -> tuple[int, int]:
    """The anchored stretch, as ``lib.trace.reduce`` takes it: between the
    harness's two anchors, or all the operations without them."""
    ops = trace["ops"]
    return (trace["anchors"].get(tr.ANCHOR, min(s for _, s, _ in ops)),
            trace["anchors"].get(tr.ANCHOR_END, max(s + d for _, s, d in ops)))


def reduce(trace: dict) -> dict | None:
    """Idle time of the anchored stretch by ``sched.*`` span name, the
    share some span covers, and the clock shift applied."""
    ops = trace["ops"]
    if not ops or not trace["spans"]:
        return None
    lo, hi = stretch(trace)
    shift, pairs = clock_shift([s for s in trace["spans"] if lo <= s[1] < hi],
                               [m for m in trace["modules"] if lo <= m[1] < hi])
    spans = [(n, a + shift, b + shift) for n, a, b in trace["spans"]]
    idle = tr.gaps(tr.clip(ops, lo, hi), lo, hi)
    total = sum(b - a for a, b in idle)
    names = sorted({n for n, _, _ in spans})
    under = {n: overlap_ns(idle, tr.union((a, b) for m, a, b in spans if m == n)) for n in names}
    covered = overlap_ns(idle, tr.union((a, b) for _, a, b in spans))
    return {"idle_ns": total, "covered_ns": covered, "under_ns": under,
            "started": {n: sum(1 for m, a, _ in spans if m == n and lo <= a < hi) for n in names},
            "shift_ns": shift, "pairs": pairs}


def run_trace(ctx: dict) -> dict | None:
    """The run's own trace as ``load`` gives it, parsed once (``scopes``
    reads it too); None without a traced stretch or a device operation."""
    if not ctx.get("trace"):
        return None
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    path = tr.find_xplane(os.path.join(root, ".bench_trace"))
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _parsed:
        _parsed.clear()
        _parsed[key] = load(path)
    return _parsed[key]


def _reduced(ctx: dict) -> dict | None:
    trace = run_trace(ctx)
    if trace is None:
        return None
    if "reduced" not in trace:
        r = trace["reduced"] = reduce(trace)
        if r:
            top = sorted(r["under_ns"].items(), key=lambda kv: -kv[1])[:8]
            print(f"[benchmark] host_spans: idle {r['idle_ns'] / 1e9:.6f}s, under some {PREFIX}* span "
                  f"{r['covered_ns'] / 1e9:.6f}s; clock shift {r['shift_ns']} ns from {r['pairs']} "
                  f"launches; idle s by span {[[n, round(v / 1e9, 6)] for n, v in top]}", flush=True)
    return trace["reduced"]


def read(ctx: dict, what: str, span: str | None = None):
    r = _reduced(ctx)
    if r is None:
        return None
    if what == "attributed_share":
        return 100.0 * r["covered_ns"] / r["idle_ns"] if r["idle_ns"] else None
    if what == "idle_ms_per_span":
        n = r["started"].get(span, 0)
        return r["under_ns"][span] / 1e6 / n if n else None
    raise ValueError(f"host_spans reader: unknown quantity {what!r}")
