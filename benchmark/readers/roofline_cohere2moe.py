"""Reader ``roofline_cohere2moe``: the device programs of ONE CHIP'S SHARE
of a Command A+ decoder against the chip's published peaks, with the floor
of ``lib/peaks_cohere2moe.py`` (held experts touched and local rows from the
program's ``moe.*`` counters, shared experts and attention once, the head on
one position a row), and the ratio of counters the ``counters`` reader cannot
form. ``readers/roofline_routed.py`` stays the all-experts-held one.

``program_roofline`` — the least time a decode forward of the share can take
over the device time per forward of ``program`` in the trace, forwards
counted in the SAME traced executions (as ``readers/roofline.py``).
``kernel_roofline`` — the least time one forward's ``grouped_matmul`` calls
can take (touched held experts' planes / HBM bandwidth, or the local rows'
FLOPs / bf16 peak) over their device SELF time per forward: the operations
whose scope path holds the kernel's name.
``padding_share`` — ``moe.padded_rows`` / ``moe.local_rows`` - 1: the rows
the dispatch computed beyond the rows that fell on held experts.

A program without ``moe.local_rows`` (the parent of PR 34; a model that holds
all its experts) gives nothing to read: every quantity returns None and
never raises."""

from __future__ import annotations

from ..lib import peaks_cohere2moe as pkc
from .host_spans import run_trace
from .roofline import _shape
from .scopes import scope_ns

PROGRAM = "paged_chunk_decode_loop"


def _per_forward(ctx: dict) -> tuple[float, float] | None:
    """(held experts touched, local rows) per forward, summed over layers."""
    c = ctx.get("counters", {})
    fwds = c.get("scheduler.forwards")
    if not fwds or "moe.experts_touched" not in c or "moe.local_rows" not in c:
        return None
    return c["moe.experts_touched"] / fwds, c["moe.local_rows"] / fwds


def read(ctx: dict, what: str, program: str = PROGRAM):
    if what == "padding_share":
        c = ctx.get("counters", {})
        if not c.get("moe.local_rows") or "moe.padded_rows" not in c:
            return None
        return 100.0 * (c["moe.padded_rows"] / c["moe.local_rows"] - 1.0)
    plane = run_trace(ctx)
    routed, shape = _per_forward(ctx), _shape(ctx)
    if (plane is None or routed is None or shape is None or ctx["peaks"] is None
            or "num_experts_published" not in ctx["model"]):
        return None
    touched, local = routed
    _, rows, context = shape
    model, peaks = ctx["model"], ctx["peaks"]
    wbytes = 1 if ctx["serving"]["quant"] == "int8" else 2
    if what == "program_roofline":
        runs = scope_ns(plane, [], program)
        if not runs["forwards"]:
            return None
        floor, _ = pkc.forward_floor_s(model, peaks, wbytes, round(rows),
                                       1 + ctx["serving"]["fast_forward"], int(context),
                                       touched, local)
        return 100.0 * floor / (runs["program_ns"] / 1e9 / runs["forwards"])
    if what == "kernel_roofline":
        r = scope_ns(plane, ["grouped_matmul"], program)
        if not r["forwards"] or not r["ns"]:
            return None
        floor, _ = pkc.grouped_matmul_floor_s(model, peaks, wbytes, touched, local)
        return 100.0 * floor / (r["ns"] / 1e9 / r["forwards"])
    raise ValueError(f"roofline_cohere2moe reader: unknown quantity {what!r}")
