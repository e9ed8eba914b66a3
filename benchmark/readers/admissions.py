"""Reader ``admissions``: what one admission cost the host, from the step
ledger's ``admissions`` entries (``utils/steplog.py``: one per request the
batcher admitted, written by the ``sched.admit.request`` span and its
parts). ``what`` is a key of an entry — ``queue_ms`` (submit → popped from
the queue), ``request_ms`` (the whole admission) or ``<part>_ms`` — and the
reading is its median over the admissions of the steps that closed inside
the window. A program that writes no such entries gives nothing."""

from __future__ import annotations

from ..lib.stats import median


def read(ctx: dict, what: str):
    seen = [a[what] for s in ctx.get("steps", []) for a in s.get("admissions", [])
            if what in a]
    return median(seen) if seen else None
