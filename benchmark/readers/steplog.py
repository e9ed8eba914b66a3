"""Reader ``steplog``: the batcher's step ledger (``utils/steplog.py``),
records whose step closed inside the window. Host clocks and counts."""

from __future__ import annotations

from ..lib.stats import median

HOST_STAGES = ("admit", "draft", "release")


def read(ctx: dict, what: str):
    steps = [s for s in ctx.get("steps", []) if s.get("forwards")]
    if not steps:
        return None
    if what == "occupancy":  # mean active slots / slots, per step
        return 100.0 * sum(s["occupancy"] for s in steps) / len(steps) / ctx["serving"]["batch_slots"]
    if what == "tokens_per_forward":
        return sum(s["tokens"] for s in steps) / sum(s["forwards"] for s in steps)
    if what == "step_ms":  # wall per forward, median over steps
        return median([s["wall_ms"] / s["forwards"] for s in steps])
    if what == "host_share":
        return 100.0 * sum(sum(s["stages"].get(k, 0.0) for k in HOST_STAGES) for s in steps) \
            / sum(s["wall_ms"] for s in steps)
    raise ValueError(f"steplog reader: unknown quantity {what!r}")
