"""Reader ``roofline``: measured rates against the chip's published peaks
(``lib/peaks.py``). Two different things, named apart:

``weight_read_util`` — an END-TO-END utilisation: forwards per second of the
window times the bytes one forward must read, over HBM bandwidth. Host
clock and counters; idle time counts against it.

``program_roofline`` — a device program's share of its roofline: the least
time a decode forward can take on this chip (the larger of bytes / HBM
bandwidth and FLOPs / bf16 peak, from shapes) over the device time per
forward of the chunk-decode program in the trace."""

from __future__ import annotations

from ..lib import peaks as pk


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
        tr = ctx.get("trace")
        hit = [v for k, v in (tr or {}).get("programs", {}).items() if program in k]
        if not hit:
            return None
        # forwards inside the traced executions: the ledger's mean per chunk
        per_chunk = sum(s["forwards"] for s in steps) / len(steps)
        dev_s = sum(v["total_s"] for v in hit) / (sum(v["count"] for v in hit) * per_chunk)
        floor, _ = pk.forward_floor_s(model, peaks, wbytes, round(rows),
                                      1 + ctx["serving"]["fast_forward"], int(context))
        return 100.0 * floor / dev_s
    raise ValueError(f"roofline reader: unknown quantity {what!r}")
