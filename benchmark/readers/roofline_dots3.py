"""Reader ``roofline_dots3``: the device programs of ONE CHIP'S SHARE of a
``dots3_note`` decoder (learned sparse attention over a latent cache, windowed
latent attention beside it, held experts) against the chip's published peaks,
with the floor of ``lib/peaks_dots3.py`` (held experts touched and local rows
from the program's ``moe.*`` counters, SELECTED and VISIBLE keys from its
``attn.keys_*`` counters, the window from the configuration, everything else
once, the head on one position a row).

``program_roofline`` / ``step_mfu`` — as ``readers/roofline.py``, with this
block's floor. ``grouped_matmul_roofline`` — the held experts' planes touched
or the local rows' FLOPs over the kernel's device self time a forward.
``sparse_attn_roofline`` / ``indexer_roofline`` / ``window_attn_roofline`` —
the least time one forward's calls of that kernel can take over the device
SELF time a forward of the operations under the scope the program opens
around the kernel, which bears the kernel's name (``sparse_latent_attention``,
``indexer_scores``, ``window_latent_attention``).

A program without ``attn.keys_selected`` (the parent of PR 43; every other
model) gives nothing to read: every quantity returns None and never raises."""

from __future__ import annotations

from ..lib import peaks_dots3 as pkd
from .roofline import kernel_share, needed, program_share, step_mfu, weight_bytes

PROGRAM = "paged_chunk_decode_loop"
NEEDS = ("moe.experts_touched", "moe.local_rows", "attn.keys_selected", "attn.keys_visible")


def _per_forward(ctx: dict) -> tuple | None:
    """``NEEDS`` per forward, each summed over layers."""
    c = ctx.get("counters", {})
    fwds = c.get("scheduler.forwards")
    if not fwds or any(k not in c for k in NEEDS) or not c["attn.keys_selected"]:
        return None
    return tuple(c[k] / fwds for k in NEEDS)


def read(ctx: dict, what: str, program: str = PROGRAM):
    counted, n = _per_forward(ctx), needed(ctx)
    if (counted is None or n is None or ctx.get("peaks") is None
            or "index_topk" not in ctx["model"]):
        return None
    touched, local, selected, visible = counted
    model, peaks = ctx["model"], ctx["peaks"]
    rows, positions, context = n["live"], n["positions"], n["context"]
    if what == "step_mfu":
        return step_mfu(ctx, n, pkd.forward_flops(model, rows, positions, context, local, selected, visible))
    if what == "program_roofline":
        floor, _ = pkd.forward_floor_s(model, peaks, weight_bytes(ctx), rows, positions, context,
                                       touched, local, selected, visible)
        return program_share(ctx, program, floor)
    if what == "grouped_matmul_roofline":
        floor, _ = pkd.grouped_matmul_floor_s(model, peaks, weight_bytes(ctx), touched, local)
        return kernel_share(ctx, program, "grouped_matmul", floor)
    if what == "sparse_attn_roofline":
        floor, _ = pkd.selected_attention_floor_s(model, peaks, selected)
        return kernel_share(ctx, program, "sparse_latent_attention", floor)
    if what == "indexer_roofline":
        floor, _ = pkd.indexer_floor_s(model, peaks, context, visible)
        return kernel_share(ctx, program, "indexer_scores", floor)
    if what == "window_attn_roofline":
        floor, _ = pkd.window_attention_floor_s(model, peaks, rows, positions, context)
        return kernel_share(ctx, program, "window_latent_attention", floor)
    raise ValueError(f"roofline_dots3 reader: unknown quantity {what!r}")
