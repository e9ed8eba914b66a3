"""Reader ``roofline_looped``: a looped decoder's decode program against the
chip's published peaks, with the floor of ``lib/peaks_looped.py`` (the layers'
weights read ``total_ut_steps`` times, the K/V of ``total_ut_steps`` x
``num_hidden_layers`` planes with the common positions once, the FLOPs of the
real positions every pass). Handed ``roofline.needed(ctx)`` as every
``roofline_*`` reader; ``readers/roofline.py`` and ``lib/peaks.py`` stay the
dense ones, untouched.

``program_roofline`` — the least time a looped decode forward can take on this
chip over the device time per forward of ``program`` in the trace, forwards
counted in the SAME traced executions (as ``readers/roofline.py``).
``step_mfu`` — the FLOPs the window's forwards NEEDED over the window's seconds
and the bf16 peak, END TO END.

A configuration without ``total_ut_steps`` (another model's), a run without a
step ledger and a CPU rehearsal give nothing to read: None, never a raise."""

from __future__ import annotations

from ..lib import peaks_looped as pkl
from .roofline import needed, program_share, step_mfu, weight_bytes

PROGRAM = "paged_chunk_decode_loop"


def read(ctx: dict, what: str, program: str = PROGRAM):
    n = needed(ctx)
    if n is None or ctx["peaks"] is None or "total_ut_steps" not in ctx["model"]:
        return None
    model, peaks = ctx["model"], ctx["peaks"]
    if what == "step_mfu":
        return step_mfu(ctx, n, pkl.forward_flops(model, n["live"], n["positions"], n["context"]))
    if what == "program_roofline":
        floor, _ = pkl.forward_floor_s(model, peaks, weight_bytes(ctx), n["live"], n["positions"],
                                       n["context"], n["common"])
        return program_share(ctx, program, floor)
    raise ValueError(f"roofline_looped reader: unknown quantity {what!r}")
