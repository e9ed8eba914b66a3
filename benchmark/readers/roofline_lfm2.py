"""Reader ``roofline_lfm2``: the device programs of an LFM2 decoder with routed
experts against the chip's published peaks, with the floor of
``lib/peaks_lfm2.py`` (shared int8 planes and the tied head once, experts
TOUCHED from ``moe.experts_touched`` — never 32 by assumption —, rows ASSIGNED
from ``moe.assigned_rows``, tails MOVED from ``conv.tail_rows_moved``, K/V of
the six attention layers as ``peaks.kv_positions`` counts it, FLOPs of the real
positions).

``program_roofline`` / ``step_mfu`` — as ``readers/roofline.py``'s, with this
floor. ``kernel_roofline`` — ``readers/roofline_routed.py``'s, at this model's
expert width. ``shortconv_roofline`` — the 18 convolution mixers' floor (their
int8 W_in and W_out once, the real positions' rows in and out, the live rows'
tails in and out, over HBM bandwidth; or their FLOPs over the peak) over the
device SELF time a forward of everything under the scope ``layer/conv``: the
projection on the packed rows with its norm and gate, the taps and the tail's
gather and write-back on (B, T), the out projection.

A program without such a model or without the counters (the parent of PR 64)
gives nothing to read: every quantity returns None and never raises."""

from __future__ import annotations

from ..lib import peaks as pk
from ..lib import peaks_lfm2 as pkl
from .host_spans import run_trace
from .roofline import kernel_share, needed, program_share, step_mfu, weight_bytes
from .scopes import scope_ns

PROGRAM = "paged_chunk_decode_loop"
CONV_SCOPE = "layer/conv"


def read(ctx: dict, what: str, program: str = PROGRAM):
    c, n = ctx.get("counters", {}), needed(ctx)
    fwds = c.get("scheduler.forwards")
    if (not fwds or n is None or ctx["peaks"] is None or "conv_L_cache" not in ctx["model"]
            or any(k not in c for k in ("conv.tail_rows_moved", "moe.experts_touched", "moe.assigned_rows"))):
        return None
    moved, touched, assigned = (c[k] / fwds for k in ("conv.tail_rows_moved", "moe.experts_touched",
                                                      "moe.assigned_rows"))
    model, peaks = ctx["model"], ctx["peaks"]
    if what == "shortconv_roofline":
        floor, _ = pkl.shortconv_floor_s(model, peaks, weight_bytes(ctx), n["positions"], moved)
        plane = run_trace(ctx)
        r = scope_ns(plane, [CONV_SCOPE], program) if plane else None
        if not r or not r["forwards"] or not r["ns"]:
            return None
        return 100.0 * floor / (r["ns"] / 1e9 / r["forwards"])
    if what == "kernel_roofline":
        floor, _ = pkl.grouped_matmul_floor_s(model, peaks, weight_bytes(ctx), touched, assigned)
        return kernel_share(ctx, program, "grouped_matmul", floor)
    # this program sums ``attn.*`` over its attention layers: one read's are a layer's
    reads = pkl.dims(model)["nF"] or 1
    live = pk.live_rows(n["row_blocks"] / reads, n["context"], n["block_size"], n["rows"])
    if what == "step_mfu":
        return step_mfu(ctx, n, pkl.forward_flops(model, live, n["positions"], n["context"], assigned))
    if what == "program_roofline":
        common = pk.common_positions(n["common_row_blocks"], live, n["block_size"], reads=reads)
        floor, _ = pkl.forward_floor_s(model, peaks, weight_bytes(ctx), live, n["positions"],
                                       n["context"], touched, assigned, moved, common)
        return program_share(ctx, program, floor)
    raise ValueError(f"roofline_lfm2 reader: unknown quantity {what!r}")
