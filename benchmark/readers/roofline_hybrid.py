"""Reader ``roofline_hybrid``: a SambaY decoder-hybrid-decoder's device
programs against the chip's published peaks, with the floor of
``lib/peaks_hybrid.py`` (weights once, K/V by layer kind with the window, the
recurrent state twice, the head on one position a row).
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

from ..lib import peaks_hybrid as pkh
from .host_spans import run_trace
from .roofline import _shape
from .scopes import scope_ns

PROGRAM = "paged_chunk_decode_loop"


def read(ctx: dict, what: str, program: str = PROGRAM):
    shape = _shape(ctx)
    if shape is None or ctx["peaks"] is None or "ssm_d_state" not in ctx["model"]:
        return None
    plane = run_trace(ctx)
    if plane is None:
        return None
    _, rows, context = shape
    model, peaks = ctx["model"], ctx["peaks"]
    if what == "program_roofline":
        runs = scope_ns(plane, [], program)
        if not runs["forwards"]:
            return None
        wbytes = 1 if ctx["serving"]["quant"] == "int8" else 2
        floor, _ = pkh.forward_floor_s(model, peaks, wbytes, rows,
                                       1 + ctx["serving"]["fast_forward"], int(context))
        return 100.0 * floor / (runs["program_ns"] / 1e9 / runs["forwards"])
    if what == "scan_roofline":
        r = scope_ns(plane, ["selective_scan"], program)
        if not r["forwards"] or not r["ns"]:
            return None
        return 100.0 * pkh.scan_floor_s(model, peaks, rows) / (r["ns"] / 1e9 / r["forwards"])
    raise ValueError(f"roofline_hybrid reader: unknown quantity {what!r}")
