"""Reader ``latency_budget``: a stage of voice's per-utterance
``latency_budget`` WebSocket event (host-clock spans the voice service
takes around STT finalisation and the parse round trip)."""

from __future__ import annotations

from ..lib.stats import percentile


def read(ctx: dict, stage: str, q: float = 50.0):
    xs = [ev["stages"][stage] for u in ctx.get("utterances", []) for ev in u["events"]
          if ev["type"] == "latency_budget" and stage in ev.get("stages", {})]
    return percentile(xs, q) if xs else None
