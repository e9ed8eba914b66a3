"""Reader ``step_fields``: a key of the step ledger's RECORDS
(``utils/steplog.py``; ``HOST_WAIT.md`` lists the keys), over the steps that
closed inside the window — ``readers/steplog.py`` knows four quantities by
name, this one takes the key.

``what`` is the key; ``stat`` is ``median`` (over the steps that hold the
key), ``max`` or ``sum``. ``minus`` names a key taken off ``what`` step by
step (time off the CPU = wall − CPU); ``over`` one whose SUM over the same
steps divides the result (a share of sums, not a mean of shares); ``per``
``"steps"`` divides by their count. ``where`` keeps the steps in which that
key is set and not zero (``admitted``: the steps that admitted somebody);
``scale`` multiplies (100 for a share in %). A record without the key —
every record of a program that writes none — is left out, and nothing left
gives nothing."""

from __future__ import annotations

from ..lib.stats import median

STATS = {"median": median, "max": max, "sum": sum}


def read(ctx: dict, what: str, stat: str = "median", minus: str | None = None,
         over: str | None = None, per: str | None = None, where: str | None = None,
         scale: float = 1.0):
    if stat not in STATS or per not in (None, "steps"):
        raise ValueError(f"step_fields reader: unknown stat {stat!r} or per {per!r}")
    need = [k for k in (what, minus, over) if k is not None]
    steps = [s for s in ctx.get("steps", [])
             if all(k in s for k in need) and (where is None or s.get(where))]
    if not steps:
        return None
    value = STATS[stat]([s[what] - (s[minus] if minus else 0.0) for s in steps])
    if over is not None:
        bottom = sum(s[over] for s in steps)
        if not bottom:
            return None
        value /= bottom
    if per == "steps":
        value /= len(steps)
    return value * scale
