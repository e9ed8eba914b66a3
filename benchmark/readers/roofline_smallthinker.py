"""Reader ``roofline_smallthinker``: the device programs of STAGE 0 of a
SmallThinker-21BA3B-Instruct decoder (a router on the layer's input, ReGLU
experts, one full layer among three windowed) against the chip's published
peaks, with the floor of ``lib/peaks_smallthinker.py`` (experts touched and
rows routed from the program's ``moe.*`` counters, the window and the layers'
kinds from the configuration, the cached head's length from the run).

``program_roofline`` / ``step_mfu`` — as ``readers/roofline.py``, with this
block's floor. ``kernel_roofline`` — ``grouped_matmul``'s, as
``readers/roofline_routed.py``. ``window_attn_roofline`` — the least time the
sliding layers' block-kernel calls of one forward can take over the device
SELF time a forward of the operations under ``layer/attn/window`` that are
the kernel's (``jit(paged_block_attention)``). ``padding_share`` —
``roofline_routed``'s.

This program sums ``attn.row_blocks`` over ALL its layers and
``attn.common_row_blocks`` over its full ones (layers of two kinds read other
blocks: ``llama.forward_paged``), so the rows that attend and the positions
they hold in common are formed here from those, not by ``roofline.needed``.

A program without the counters (the parent of PR 50 cannot build the
configuration; any other model) gives nothing to read: every quantity returns
None and never raises."""

from __future__ import annotations

from ..lib import peaks as pk
from ..lib import peaks_smallthinker as pks
from .roofline import kernel_share, needed, program_share, step_mfu, weight_bytes
from .roofline_routed import padding_share

PROGRAM = "paged_chunk_decode_loop"
WINDOW_KERNEL = "layer/attn/window/jit(paged_block_attention)"


def _needed(ctx: dict) -> dict | None:
    """``roofline.needed`` with this model's reading of the attention
    counters, the per-forward expert counts and the cached head's length."""
    c, n = ctx.get("counters", {}), needed(ctx)
    fwds = c.get("scheduler.forwards")
    if (n is None or not fwds or "moe.experts_touched" not in c or "moe.assigned_rows" not in c
            or ctx.get("peaks") is None or "moe_num_primary_experts" not in ctx["model"]):
        return None
    s = pks.dims(ctx["model"])
    live = pk.live_rows(n["row_blocks"] / s["L"], n["context"], n["block_size"], n["rows"])
    return {**n, "live": live,
            "common": pk.common_positions(n["common_row_blocks"], live, n["block_size"],
                                          reads=max(s["n_full"], 1)),
            "head": float(ctx.get("prefix_tokens", 0)),
            "touched": c["moe.experts_touched"] / fwds, "assigned": c["moe.assigned_rows"] / fwds}


def read(ctx: dict, what: str, program: str = PROGRAM, kernel: str = WINDOW_KERNEL):
    if what == "padding_share":
        return padding_share(ctx, "moe.assigned_rows")
    n = _needed(ctx)
    if n is None:
        return None
    model, peaks = ctx["model"], ctx["peaks"]
    if what == "step_mfu":
        return step_mfu(ctx, n, pks.forward_flops(model, n["live"], n["positions"], n["context"],
                                                  n["assigned"]))
    if what == "program_roofline":
        floor, _ = pks.forward_floor_s(model, peaks, weight_bytes(ctx), n["live"], n["positions"],
                                       n["context"], n["touched"], n["assigned"], n["common"],
                                       n["head"])
        return program_share(ctx, program, floor)
    if what == "kernel_roofline":
        floor, _ = pks.grouped_matmul_floor_s(model, peaks, weight_bytes(ctx), n["touched"],
                                              n["assigned"])
        return kernel_share(ctx, program, "grouped_matmul", floor)
    if what == "window_attn_roofline":
        floor, _ = pks.window_attention_floor_s(model, peaks, n["live"], n["positions"],
                                                n["context"], n["head"])
        return kernel_share(ctx, program, kernel, floor)
    raise ValueError(f"roofline_smallthinker reader: unknown quantity {what!r}")
