"""Reader ``counters``: the program's counters over the window
(``scheduler.*``, ``voice.*`` deltas between the window's two edges), as a
ratio ``num / den * scale``. ``den`` may be a counter, ``window_s``,
``utterances`` or ``requests`` (what the generator counted)."""

from __future__ import annotations


def read(ctx: dict, num: str, den: str, scale: float = 1.0):
    delta = ctx.get("counters", {})
    if num not in delta:
        return None
    bottom = {"window_s": ctx["window_s"], "utterances": len(ctx.get("utterances", [])),
              "requests": len(ctx.get("records", []))}.get(den, delta.get(den))
    return delta[num] / bottom * scale if bottom else None
