"""Reader ``roofline``: measured rates against the chip's published peaks
(``lib/peaks.py``). Two different things, named apart:

``weight_read_util`` — an END-TO-END utilisation: forwards per second of the
window times the bytes one forward must read (the weights once, the K/V the
live rows hold in common once, each row's own a row), over HBM bandwidth.
Host clock and counters; idle time counts against it.

``step_mfu`` — the other END-TO-END utilisation: the FLOPs the window's
forwards needed over the window's seconds and the bf16 peak.

``program_roofline`` — a device program's share of its roofline: the least
time a decode forward can take on this chip (the larger of bytes / HBM
bandwidth and FLOPs / bf16 peak, the FLOPs of its REAL positions: ``needed``
below) over the device time per forward of the chunk-decode program in the trace. The forwards are counted
in the SAME traced executions, as ``readers/scopes.py`` counts them (the
occurrences of the operation under ``lm_head``): the ledger's mean
forwards a chunk over the whole window read 36.3 % for 27.8 % on a
``parse_solo`` stretch of 16-forward and 1-forward chunks (PERF.md section 6)."""

from __future__ import annotations

from ..lib import peaks as pk
from .host_spans import run_trace
from .scopes import scope_ns


def needed(ctx: dict) -> dict | None:
    """What a floor is handed, the same for every ``roofline_*`` reader:
    ``rows`` (mean slots occupied a step), ``context`` (mean attended
    positions a row), ``positions`` (REAL positions a forward, all rows
    together: the step ledger's tokens over its forwards —
    ``tokens_per_forward``'s own ratio, never rows x (1 + fast_forward)),
    ``row_blocks`` / ``common_row_blocks`` (the program's ``attn.*`` counters
    a forward; 0 where it counts none) and, from them where ONE read a
    forward is counted, ``live`` (rows that attend a forward:
    ``peaks.live_rows``) and ``common`` (positions they hold in common:
    ``peaks.common_positions``). None without a step ledger."""
    steps = [s for s in ctx.get("steps", []) if s.get("forwards")]
    if not steps:
        return None
    rows = sum(s["occupancy"] for s in steps) / len(steps)
    recs = [r for r in ctx.get("records", []) if "x-prompt-tokens" in r.get("headers", {})]
    prompt = (sum(float(r["headers"]["x-prompt-tokens"]) for r in recs) / len(recs)
              if recs else float(ctx.get("prefix_tokens", 0)))
    c = ctx.get("counters", {})
    fwds = c.get("scheduler.forwards")
    n = {"steps": steps, "rows": rows, "context": prompt + 0.5 * ctx.get("tokens_per_request", 0.0),
         "positions": sum(s["tokens"] for s in steps) / sum(s["forwards"] for s in steps),
         "row_blocks": c.get("attn.row_blocks", 0.0) / fwds if fwds else 0.0,
         "common_row_blocks": c.get("attn.common_row_blocks", 0.0) / fwds if fwds else 0.0,
         "block_size": ctx["serving"].get("block_size", 128)}
    n["live"] = pk.live_rows(n["row_blocks"], n["context"], n["block_size"], rows)
    n["common"] = pk.common_positions(n["common_row_blocks"], n["live"], n["block_size"])
    return n


def weight_bytes(ctx: dict) -> int:
    return 1 if ctx["serving"]["quant"] == "int8" else 2


def program_share(ctx: dict, program: str, floor_s: float):
    """``floor_s`` over the device time a forward of ``program`` in the
    traced stretch, in %; None where the stretch holds no such forward."""
    plane = run_trace(ctx)
    runs = scope_ns(plane, [], program) if plane else None  # counted by scopes' own ``per``
    if not runs or not runs["forwards"]:  # no such program in the stretch, or one without scopes
        return None
    return 100.0 * floor_s / (runs["program_ns"] / 1e9 / runs["forwards"])


def kernel_share(ctx: dict, program: str, kernel: str, floor_s: float):
    """``floor_s`` over the device SELF time a forward of the operations
    whose scope path holds ``kernel``, in %; None where there is none."""
    plane = run_trace(ctx)
    r = scope_ns(plane, [kernel], program) if plane else None
    if not r or not r["forwards"] or not r["ns"]:
        return None
    return 100.0 * floor_s / (r["ns"] / 1e9 / r["forwards"])


def step_mfu(ctx: dict, n: dict, flops_a_forward: float) -> float:
    """The whole step's share of the chip's bf16 peak, END TO END: the FLOPs
    the window's forwards NEEDED (``flops_a_forward``: a floor's own count, on
    real positions) over the window's seconds — admission, the host and idle
    time count against it, as against ``weight_read_util``. A later PR that
    takes a kernel off the path leaves that kernel's roofline silent; this
    still bounds what it may claim."""
    return 100.0 * flops_a_forward * sum(s["forwards"] for s in n["steps"]) / ctx["window_s"] \
        / ctx["peaks"]["flops_per_s"]


def read(ctx: dict, what: str, program: str = "paged_chunk_decode_loop"):
    n = needed(ctx)
    if n is None or ctx["peaks"] is None:  # no ledger, or a CPU rehearsal
        return None
    model, peaks = ctx["model"], ctx["peaks"]
    if what == "step_mfu":
        return step_mfu(ctx, n, pk.forward_flops(model, n["positions"], n["context"]))
    if what == "weight_read_util":
        fwd_per_s = sum(s["forwards"] for s in n["steps"]) / ctx["window_s"]
        return 100.0 * fwd_per_s * pk.forward_bytes(model, weight_bytes(ctx), n["live"], n["context"],
                                                    common=n["common"]) / peaks["bytes_per_s"]
    if what == "program_roofline":
        floor, _ = pk.forward_floor_s(model, peaks, weight_bytes(ctx), n["live"], n["positions"],
                                      n["context"], n["common"])
        return program_share(ctx, program, floor)
    raise ValueError(f"roofline reader: unknown quantity {what!r}")
