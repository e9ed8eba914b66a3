"""Reader ``client_clock``: times the load generator took on its own clock.
``event``: milliseconds from an utterance's last speech frame (due) to the
named WebSocket event."""

from __future__ import annotations

from ..lib.stats import percentile


def read(ctx: dict, event: str, q: float = 50.0):
    xs = []
    for u in ctx.get("utterances", []):
        for ev in u["events"]:
            if ev["type"] == event:
                xs.append(ev["ms_from_speech_end"])
                break
    return percentile(xs, q) if xs else None
