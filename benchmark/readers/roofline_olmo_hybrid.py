"""Reader ``roofline_olmo_hybrid``: the device programs of an OLMo hybrid
decoder against the chip's published peaks, with the floor of
``lib/peaks_olmo_hybrid.py`` (int8 planes and the head once, states MOVED from
``gdn.state_rows_moved`` — unpadded, once in and once out —, K/V of the full
layers as ``peaks.kv_positions`` counts it, FLOPs of the real positions).

``program_roofline`` / ``step_mfu`` — as ``readers/roofline.py``'s, with this
floor. ``scan_roofline`` — the recurrence's floor (states moved x 2 x their
bytes / HBM bandwidth, or its FLOPs / peak, whichever is larger) over the
device SELF time a forward of everything under the scope ``layer/gdn/scan``:
the kernel and what stands around it there (the l2 norms, the decay, the
layout moves into the kernel's operands).

A program without such a model or without the counters (the parent of PR 54)
gives nothing to read: every quantity returns None and never raises."""

from __future__ import annotations

from ..lib import peaks as pk
from ..lib import peaks_olmo_hybrid as pko
from .host_spans import run_trace
from .roofline import needed, program_share, step_mfu, weight_bytes
from .scopes import scope_ns

PROGRAM = "paged_chunk_decode_loop"
SCAN_SCOPE = "layer/gdn/scan"


def read(ctx: dict, what: str, program: str = PROGRAM):
    c, n = ctx.get("counters", {}), needed(ctx)
    fwds = c.get("scheduler.forwards")
    if (not fwds or "gdn.state_rows_moved" not in c or n is None or ctx["peaks"] is None
            or "linear_value_head_dim" not in ctx["model"]):
        return None
    moved = c["gdn.state_rows_moved"] / fwds
    model, peaks = ctx["model"], ctx["peaks"]
    if what == "scan_roofline":
        floor, _ = pko.scan_floor_s(model, peaks, moved, n["positions"])
        plane = run_trace(ctx)
        r = scope_ns(plane, [SCAN_SCOPE], program) if plane else None
        if not r or not r["forwards"] or not r["ns"]:
            return None
        return 100.0 * floor / (r["ns"] / 1e9 / r["forwards"])
    # this program sums ``attn.*`` over its full layers: one read's are a layer's
    reads = pko.dims(model)["nF"] or 1
    live = pk.live_rows(n["row_blocks"] / reads, n["context"], n["block_size"], n["rows"])
    if what == "step_mfu":
        return step_mfu(ctx, n, pko.forward_flops(model, live, n["positions"], n["context"]))
    if what == "program_roofline":
        common = pk.common_positions(n["common_row_blocks"], live, n["block_size"], reads=reads)
        floor, _ = pko.forward_floor_s(model, peaks, weight_bytes(ctx), live, n["positions"],
                                       n["context"], moved, common)
        return program_share(ctx, program, floor)
    raise ValueError(f"roofline_olmo_hybrid reader: unknown quantity {what!r}")
