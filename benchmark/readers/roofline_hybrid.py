"""Reader ``roofline_hybrid``: a SambaY decoder-hybrid-decoder's device
programs against the chip's published peaks, with the floor of
``lib/peaks_hybrid.py`` (weights once, K/V by layer kind with the window and
the common positions once, the recurrent state twice, the per-position work
on the forward's real positions, the head on one position a row).
``readers/roofline.py`` and ``lib/peaks.py`` stay the dense ones, untouched.

``program_roofline`` — the least time a hybrid decode forward can take on
this chip over the device time per forward of ``program`` in the trace,
forwards counted in the SAME traced executions (as ``readers/roofline.py``).
``scan_roofline`` — the selective scans' byte floor (each live row's float32
state read once and written once in every state-space layer, over HBM
bandwidth) over the device SELF time per forward of the operations whose
scope path holds the kernel's name, ``selective_scan``.

A program without such a model or without the scopes (the parent of PR 32,
a dense or routed model) gives nothing to read: every quantity returns None
and never raises."""

from __future__ import annotations

from ..lib import peaks as pk
from ..lib import peaks_hybrid as pkh
from .roofline import kernel_share, needed, program_share, step_mfu, weight_bytes

PROGRAM = "paged_chunk_decode_loop"


def read(ctx: dict, what: str, program: str = PROGRAM):
    n = needed(ctx)
    if n is None or ctx["peaks"] is None or "ssm_d_state" not in ctx["model"]:
        return None
    model, peaks = ctx["model"], ctx["peaks"]
    if what in ("step_mfu", "program_roofline"):
        # this program sums ``attn.*`` over ALL its attention reads: the blocks live rows hold are its
        # ``attn.window_blocks_held`` a windowed layer, the common row-blocks those of the reads that ride
        s, c = pkh.dims(model), ctx.get("counters", {})
        held = c.get("attn.window_blocks_held", 0.0) / (c.get("scheduler.forwards") or 1.0) / s["n_window"]
        live = pk.live_rows(held, n["context"], n["block_size"], n["rows"])
        if what == "step_mfu":
            return step_mfu(ctx, n, pkh.forward_flops(model, live, n["positions"], n["context"]))
        common = pk.common_positions(n["common_row_blocks"], live, n["block_size"],
                                     reads=s["n_full"] + s["n_cross"])
        floor, _ = pkh.forward_floor_s(model, peaks, weight_bytes(ctx), live, n["positions"],
                                       n["context"], common)
        return program_share(ctx, program, floor)
    if what == "scan_roofline":
        return kernel_share(ctx, program, "selective_scan", pkh.scan_floor_s(model, peaks, n["rows"]))
    raise ValueError(f"roofline_hybrid reader: unknown quantity {what!r}")
