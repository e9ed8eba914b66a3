"""Reader ``roofline``: measured rates against the chip's published peaks
(``lib/peaks.py``). Two different things, named apart:

``weight_read_util`` — an END-TO-END utilisation: forwards per second of the
window times the bytes one forward must read, over HBM bandwidth. Host
clock and counters; idle time counts against it.

``program_roofline`` — a device program's share of its roofline: the least
time a decode forward can take on this chip (the larger of bytes / HBM
bandwidth and FLOPs / bf16 peak, from shapes) over the device time per
forward of the chunk-decode program in the trace. The forwards are counted
in the SAME traced executions, as ``readers/scopes.py`` counts them (the
occurrences of the operation under ``lm_head``): the ledger's mean
forwards a chunk over the whole window read 36.3 % for 27.8 % on a
``parse_solo`` stretch of 16-forward and 1-forward chunks (PERF.md section 6)."""

from __future__ import annotations

from ..lib import peaks as pk
from .host_spans import run_trace
from .scopes import scope_ns


def _shape(ctx: dict):
    steps = [s for s in ctx.get("steps", []) if s.get("forwards")]
    if not steps:
        return None
    rows = sum(s["occupancy"] for s in steps) / len(steps)
    recs = [r for r in ctx.get("records", []) if "x-prompt-tokens" in r.get("headers", {})]
    prompt = (sum(float(r["headers"]["x-prompt-tokens"]) for r in recs) / len(recs)
              if recs else float(ctx.get("prefix_tokens", 0)))
    return steps, rows, prompt + 0.5 * ctx.get("tokens_per_request", 0.0)


def read(ctx: dict, what: str, program: str = "paged_chunk_decode_loop"):
    shape = _shape(ctx)
    if shape is None or ctx["peaks"] is None:  # no ledger, or a CPU rehearsal
        return None
    steps, rows, context = shape
    model, peaks = ctx["model"], ctx["peaks"]
    wbytes = 1 if ctx["serving"]["quant"] == "int8" else 2
    if what == "weight_read_util":
        fwd_per_s = sum(s["forwards"] for s in steps) / ctx["window_s"]
        return 100.0 * fwd_per_s * pk.forward_bytes(model, wbytes, round(rows), int(context)) \
            / peaks["bytes_per_s"]
    if what == "program_roofline":
        plane = run_trace(ctx)
        runs = scope_ns(plane, [], program) if plane else None  # counted by scopes' own ``per``
        if not runs or not runs["forwards"]:  # no such program in the stretch, or one without scopes
            return None
        dev_s = runs["program_ns"] / 1e9 / runs["forwards"]
        floor, _ = pk.forward_floor_s(model, peaks, wbytes, round(rows),
                                      1 + ctx["serving"]["fast_forward"], int(context))
        return 100.0 * floor / dev_s
    raise ValueError(f"roofline reader: unknown quantity {what!r}")
