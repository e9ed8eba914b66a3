"""Reader ``roofline_routed``: a routed-expert decoder's device programs
against the chip's published peaks, with the floor of ``lib/peaks_routed.py``
(the experts counted from the program's ``moe.*`` counters, never ``E`` by
assumption), and the two ratios of those counters and scopes that the
``counters`` and ``scopes`` readers cannot form. ``readers/roofline.py`` and
``lib/peaks.py`` stay the dense ones, untouched.

``program_roofline`` — the least time a routed decode forward can take on
this chip over the device time per forward of ``program`` in the trace,
forwards counted in the SAME traced executions (as ``readers/roofline.py``).
``kernel_roofline`` — the least time one forward's ``grouped_matmul`` calls
can take (touched experts' planes / HBM bandwidth, or routed rows' FLOPs /
bf16 peak) over their device SELF time per forward: the operations whose
scope path holds the kernel's name (``.../layer/ffn/experts/grouped_matmul/...``).
``scope_share`` — device self time under ``scopes`` as a share of the device
time of ``program``'s executions in the stretch.
``padding_share`` — 1 - ``moe.assigned_rows`` / ``moe.padded_rows``: of the
rows the dispatch computed, the share that holds no routed row.

A program without the counters or the scopes (the parent of PR 27, a dense
model) gives nothing to read: every quantity returns None and never raises."""

from __future__ import annotations

from ..lib import peaks_routed as pkr
from .host_spans import run_trace
from .roofline import kernel_share, needed, program_share, step_mfu, weight_bytes
from .scopes import scope_ns

PROGRAM = "paged_chunk_decode_loop"


def _per_forward(ctx: dict) -> tuple[float, float] | None:
    """(experts touched, rows assigned) per forward, summed over layers,
    from the window's counter deltas."""
    c = ctx.get("counters", {})
    fwds = c.get("scheduler.forwards")
    if not fwds or "moe.experts_touched" not in c or "moe.assigned_rows" not in c:
        return None
    return c["moe.experts_touched"] / fwds, c["moe.assigned_rows"] / fwds


def padding_share(ctx: dict, filled: str):
    """Of the rows the dispatch COMPUTED (``moe.padded_rows``), the share
    that holds no assignment: 1 - ``filled`` / padded, in %. A share of what
    ran, so it cannot pass 100 (until PR 42: padded / filled - 1, which read
    106.9 % where half the computed rows were padding)."""
    c = ctx.get("counters", {})
    if not c.get("moe.padded_rows") or filled not in c:
        return None
    return 100.0 * (1.0 - c[filled] / c["moe.padded_rows"])


def read(ctx: dict, what: str, program: str = PROGRAM, scopes: list[str] | None = None):
    if what == "padding_share":
        return padding_share(ctx, "moe.assigned_rows")
    if what == "scope_share":
        plane = run_trace(ctx)
        r = scope_ns(plane, scopes, program) if plane else None
        return 100.0 * r["ns"] / r["program_ns"] if r and r["forwards"] and r["ns"] else None
    routed, n = _per_forward(ctx), needed(ctx)
    if routed is None or n is None or ctx["peaks"] is None or "num_experts" not in ctx["model"]:
        return None
    touched, assigned = routed
    model, peaks = ctx["model"], ctx["peaks"]
    if what == "step_mfu":
        return step_mfu(ctx, n, pkr.forward_flops(model, n["positions"], n["context"], assigned))
    if what == "program_roofline":
        floor, _ = pkr.forward_floor_s(model, peaks, weight_bytes(ctx), n["live"], n["positions"],
                                       n["context"], touched, assigned, n["common"])
        return program_share(ctx, program, floor)
    if what == "kernel_roofline":
        floor, _ = pkr.grouped_matmul_floor_s(model, peaks, weight_bytes(ctx), touched, assigned)
        return kernel_share(ctx, program, "grouped_matmul", floor)
    raise ValueError(f"roofline_routed reader: unknown quantity {what!r}")
