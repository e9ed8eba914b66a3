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
``padding_share`` — 1 - ``moe.local_rows`` / ``moe.padded_rows``: of the rows
the dispatch computed, the share that holds no row of a held expert.

A program without ``moe.local_rows`` (the parent of PR 34; a model that holds
all its experts) gives nothing to read: every quantity returns None and
never raises."""

from __future__ import annotations

from ..lib import peaks_cohere2moe as pkc
from .roofline import kernel_share, needed, program_share, step_mfu, weight_bytes
from .roofline_routed import padding_share

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
        return padding_share(ctx, "moe.local_rows")
    routed, n = _per_forward(ctx), needed(ctx)
    if (routed is None or n is None or ctx["peaks"] is None
            or "num_experts_published" not in ctx["model"]):
        return None
    touched, local = routed
    model, peaks = ctx["model"], ctx["peaks"]
    if what == "step_mfu":
        return step_mfu(ctx, n, pkc.forward_flops(model, n["live"], n["positions"], n["context"], local))
    if what == "program_roofline":
        floor, _ = pkc.forward_floor_s(model, peaks, weight_bytes(ctx), n["live"], n["positions"],
                                       n["context"], touched, local, n["common"])
        return program_share(ctx, program, floor)
    if what == "kernel_roofline":
        floor, _ = pkc.grouped_matmul_floor_s(model, peaks, weight_bytes(ctx), touched, local)
        return kernel_share(ctx, program, "grouped_matmul", floor)
    raise ValueError(f"roofline_cohere2moe reader: unknown quantity {what!r}")
