"""Reader ``host_spans``: the device's idle time under the program's OWN
spans (``lib.trace.gaps`` of the device's operations, cut by them). The
batcher writes ``sched.*`` spans (``utils/steplog.py``) onto the profiler's
trace as it works, so they lie on the clock the device's operations lie on: no ledger stamp rounded to a millisecond, no stages laid
end to end after the fact (``lib/trace.stage_spans``). They come out of
``lib.trace.load_xplane``'s one pass over the run's ``.xplane.pb``.

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

from ..lib import trace as tr

PREFIX = tr.SPAN_PREFIX
# a launching span (by the end of its name) -> the program it dispatches
LAUNCHES = {".prefill_call": "forward_paged", "sched.decode_dispatch": "paged_chunk_decode_loop"}
PAIR_SLACK_NS = 5_000_000  # a program may read as starting this long before its launch


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
    """The first device plane and the host's spans, as
    ``lib.trace.first_plane`` gives them (``scopes`` and ``roofline`` read it
    too) out of the run's one pass over its ``.xplane.pb``; None without a
    traced stretch or a device operation."""
    return (ctx.get("trace") or {}).get("plane")


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
