"""Reader ``roofline_routed``: a routed-expert decoder's device programs
against the chip's published peaks, with the floor of ``lib/peaks_routed.py``
(the experts counted from the program's ``moe.*`` counters, never ``E`` by
assumption), and the two ratios of those counters and scopes that the
``counters`` and ``scopes`` readers cannot form. ``readers/roofline.py`` and
``lib/peaks.py`` stay the dense ones, untouched.

``program_roofline`` — the least time a routed decode forward can take on
this chip over the device time per forward of ``program`` in the trace,
forwards counted in the SAME traced executions (as ``readers/roofline.py``).
``kernel_roofline`` — the least time one forward's ``grouped_matmul`` calls
can take (touched experts' planes / HBM bandwidth, or routed rows' FLOPs /
bf16 peak) over their device SELF time per forward: the operations whose
scope path holds the kernel's name (``.../layer/ffn/experts/grouped_matmul/...``).
``scope_share`` — device self time under ``scopes`` as a share of the device
time of ``program``'s executions in the stretch.
``padding_share`` — ``moe.padded_rows`` / ``moe.assigned_rows`` - 1: the rows
the dispatch computed beyond the rows that were routed.

A program without the counters or the scopes (the parent of PR 27, a dense
model) gives nothing to read: every quantity returns None and never raises."""

from __future__ import annotations

from ..lib import peaks_routed as pkr
from .host_spans import run_trace
from .roofline import _shape
from .scopes import scope_ns

PROGRAM = "paged_chunk_decode_loop"


def _per_forward(ctx: dict) -> tuple[float, float] | None:
    """(experts touched, rows assigned) per forward, summed over layers,
    from the window's counter deltas."""
    c = ctx.get("counters", {})
    fwds = c.get("scheduler.forwards")
    if not fwds or "moe.experts_touched" not in c or "moe.assigned_rows" not in c:
        return None
    return c["moe.experts_touched"] / fwds, c["moe.assigned_rows"] / fwds


def read(ctx: dict, what: str, program: str = PROGRAM, scopes: list[str] | None = None):
    if what == "padding_share":
        c = ctx.get("counters", {})
        if not c.get("moe.assigned_rows") or "moe.padded_rows" not in c:
            return None
        return 100.0 * (c["moe.padded_rows"] / c["moe.assigned_rows"] - 1.0)
    plane = run_trace(ctx)
    if plane is None:
        return None
    if what == "scope_share":
        r = scope_ns(plane, scopes, program)
        return 100.0 * r["ns"] / r["program_ns"] if r["forwards"] and r["ns"] else None
    routed, shape = _per_forward(ctx), _shape(ctx)
    if routed is None or shape is None or ctx["peaks"] is None or "num_experts" not in ctx["model"]:
        return None
    touched, assigned = routed
    _, rows, context = shape
    model, peaks = ctx["model"], ctx["peaks"]
    wbytes = 1 if ctx["serving"]["quant"] == "int8" else 2
    if what == "program_roofline":
        runs = scope_ns(plane, [], program)
        if not runs["forwards"]:
            return None
        floor, _ = pkr.forward_floor_s(model, peaks, wbytes, round(rows),
                                       1 + ctx["serving"]["fast_forward"], int(context),
                                       touched, assigned)
        return 100.0 * floor / (runs["program_ns"] / 1e9 / runs["forwards"])
    if what == "kernel_roofline":
        r = scope_ns(plane, ["grouped_matmul"], program)
        if not r["forwards"] or not r["ns"]:
            return None
        floor, _ = pkr.grouped_matmul_floor_s(model, peaks, wbytes, touched, assigned)
        return 100.0 * floor / (r["ns"] / 1e9 / r["forwards"])
    raise ValueError(f"roofline_routed reader: unknown quantity {what!r}")
