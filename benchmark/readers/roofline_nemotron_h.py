"""Reader ``roofline_nemotron_h``: the device programs of ONE CHIP'S SHARE of
a Nemotron-H hybrid decoder against the chip's published peaks, with the
floor of ``lib/peaks_nemotron_h.py`` (non-expert planes and the head once,
held experts TOUCHED and local rows from the ``moe.*`` counters, states MOVED
from ``ssm.state_rows_moved``, K/V of the attention layers as
``peaks.kv_positions`` counts it, FLOPs of the real positions).

``program_roofline`` / ``step_mfu`` — as ``readers/roofline.py``'s, with this
floor. ``kernel_roofline`` — the ``grouped_matmul`` calls' floor (touched
planes / HBM bandwidth or local rows' FLOPs / bf16 peak) over their device
SELF time a forward. ``scan_roofline`` — the ``ssd_scan`` calls' floor
(states moved x 2 x their bytes / HBM bandwidth, or the recurrence's FLOPs /
peak, whichever is larger) over theirs. ``padding_share`` — 1 -
``moe.local_rows`` / ``moe.padded_rows``.

A program without such a model or without the counters (the parent of PR 47)
gives nothing to read: every quantity returns None and never raises."""

from __future__ import annotations

from ..lib import peaks as pk
from ..lib import peaks_nemotron_h as pkn
from .roofline import kernel_share, needed, program_share, step_mfu, weight_bytes
from .roofline_routed import padding_share

PROGRAM = "paged_chunk_decode_loop"


def _per_forward(ctx: dict) -> tuple[float, float, float] | None:
    """(held experts touched, local rows, states moved) per forward, over layers."""
    c = ctx.get("counters", {})
    fwds = c.get("scheduler.forwards")
    names = ("moe.experts_touched", "moe.local_rows", "ssm.state_rows_moved")
    if not fwds or any(n not in c for n in names):
        return None
    return tuple(c[n] / fwds for n in names)


def read(ctx: dict, what: str, program: str = PROGRAM):
    if what == "padding_share":
        return padding_share(ctx, "moe.local_rows")
    counted, n = _per_forward(ctx), needed(ctx)
    if (counted is None or n is None or ctx["peaks"] is None
            or "hybrid_override_pattern" not in ctx["model"]):
        return None
    touched, local, moved = counted
    model, peaks = ctx["model"], ctx["peaks"]
    if what == "kernel_roofline":
        floor, _ = pkn.grouped_matmul_floor_s(model, peaks, weight_bytes(ctx), touched, local)
        return kernel_share(ctx, program, "grouped_matmul", floor)
    if what == "scan_roofline":
        floor, _ = pkn.scan_floor_s(model, peaks, moved, n["positions"])
        return kernel_share(ctx, program, "ssd_scan", floor)
    # this program sums ``attn.*`` over its attention layers: one read's are a layer's
    reads = pkn.dims(model)["nA"] or 1
    live = pk.live_rows(n["row_blocks"] / reads, n["context"], n["block_size"], n["rows"])
    if what == "step_mfu":
        return step_mfu(ctx, n, pkn.forward_flops(model, live, n["positions"], n["context"], local))
    if what == "program_roofline":
        common = pk.common_positions(n["common_row_blocks"], live, n["block_size"], reads=reads)
        floor, _ = pkn.forward_floor_s(model, peaks, weight_bytes(ctx), live, n["positions"],
                                       n["context"], touched, local, moved, common)
        return program_share(ctx, program, floor)
    raise ValueError(f"roofline_nemotron_h reader: unknown quantity {what!r}")
