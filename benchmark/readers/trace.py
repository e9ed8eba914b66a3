"""Reader ``trace``: numbers from the profiler's device plane
(``lib/trace.py``). ``programs`` match XLA module names by substring, and
only executions that lie whole inside the traced stretch count.

``idle_share`` — 1 − union of device-op intervals / traced stretch.
``program_share`` — device time inside the named programs / traced stretch.
``program_ms`` — device milliseconds of the named programs per execution of
the FIRST one named: a layer's work per request, per Whisper pass, per
chunk, timed on the device and not at its asynchronous dispatch. With
``per`` = ``{"num": counter, "den": counter}`` that reading is divided by the
window's ratio of the two counters: ``admit.rows`` / ``admit.calls`` turns
device time a prefill CALL (a group of up to four admissions is one program
since PR 35) into device time an ADMISSION; nothing where either is missing."""

from __future__ import annotations


def read(ctx: dict, what: str, programs: list[str] | None = None, per: dict | None = None):
    tr = ctx.get("trace")
    if not tr:
        return None
    if what == "idle_share":
        return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
    hit = [v["total_s"] for k, v in tr["programs"].items() if any(p in k for p in programs)]
    if what == "program_share":
        return 100.0 * sum(hit) / tr["window_s"] if hit else None
    if what == "program_ms":
        runs = sum(v["count"] for k, v in tr["programs"].items() if programs[0] in k)
        if not runs:
            return None
        c = ctx.get("counters", {})
        if per is not None and not (c.get(per["num"]) and c.get(per["den"])):
            return None
        return 1e3 * sum(hit) / runs / (c[per["num"]] / c[per["den"]] if per else 1.0)
    raise ValueError(f"trace reader: unknown quantity {what!r}")
