"""Reader ``scopes``: device time by the program's OWN names. Every op of
the decoder's programs carries its ``jax.named_scope`` path (``layer/ffn``,
``grammar_mask_sample``, ...; ``docs/OBSERVABILITY.md``) in the trace, so a
layer inside a program can be timed, not only the program.

The reading is the device SELF time (``lib.trace.self_times``: a ``while``
is its own time without its body's) of the ops whose scope path holds one
of ``scopes`` as whole path components, inside the executions of
``program`` that lie whole inside the traced stretch, per forward. The
forwards are counted in the SAME executions, from the trace: the chunk loop
is a ``while`` whose body's operations appear once per iteration, so the
forwards of the stretch are the occurrences of the most frequent single
operation under the scope ``per`` (``lm_head``: the model's one output
projection). The ledger's mean forwards per chunk over the whole window is
NOT used: a stretch of ``parse_solo`` holds chunks of 16 forwards and of 1,
and that divisor moved the reading by 13 % on the same code (PERF.md §6).

A fusion that spans two scopes counts under its ROOT instruction's scope:
XLA gives a fusion the metadata of its root. An op without a scope path (a
Pallas custom call in a trace that names it only by its kernel) is matched
by its instruction name instead. Every op has SOME path (``jit(f)/while/body/
dot_general``), scoped or not; a program that ran in the stretch with no op
under ``per`` carries no scopes — the parent of PR 24, or an executable out
of a compile cache written before them (``utils/compilecache.py`` keeps names
in the cache key so that this does not happen): the reader says so and
reads nothing."""

from __future__ import annotations

from collections import Counter

from ..lib import trace as tr
from .host_spans import run_trace, stretch


def in_scope(path: str, scopes: list[str]) -> bool:
    return any(f"/{s}/" in f"/{path}/" for s in scopes)


def scope_ns(trace: dict, scopes: list[str], program: str, per: str = "lm_head") -> dict:
    """``{"ns": self nanoseconds under scopes, "forwards", "runs",
    "program_ns": device nanoseconds of those runs}`` over the executions of
    ``program`` whole inside the anchored stretch."""
    out = {"ns": 0, "forwards": 0, "runs": 0, "program_ns": 0}
    ops = trace["ops"]
    if not ops:
        return out
    lo, hi = stretch(trace)
    runs = sorted((s, s + d) for n, s, d in trace["modules"] if program in n and s >= lo and s + d <= hi)
    if not runs:
        return out
    inside, at = [], 0
    for ev in sorted(ops, key=lambda e: e[1]):
        while at < len(runs) and runs[at][1] <= ev[1]:
            at += 1
        if at == len(runs):
            break
        if ev[1] >= runs[at][0] and ev[1] + ev[2] <= runs[at][1]:
            inside.append(ev)
    own = tr.self_times(inside)
    paths = trace["scope"]
    times = Counter(ev[0] for ev in inside)
    once = [k for name, k in times.items() if in_scope(paths.get(name, ""), [per])]
    return {"ns": sum(ns for name, ns in own.items()
                      if in_scope(paths.get(name) or tr.short_name(name).split(" ")[0], scopes)),
            "forwards": max(once, default=0), "runs": len(runs),
            "program_ns": sum(b - a for a, b in runs)}


def read(ctx: dict, scopes: list[str], program: str, per: str = "lm_head"):
    trace = run_trace(ctx)
    if trace is None:
        return None
    r = scope_ns(trace, scopes, program, per)
    said = trace.setdefault("said", set())
    if r["runs"] and (program, per) not in said:
        said.add((program, per))
        steps = [s for s in ctx.get("steps", []) if s.get("forwards")]
        mean = sum(s["forwards"] for s in steps) / len(steps) if steps else None
        print(f"[benchmark] scopes: {r['runs']} executions of {program} in the stretch, {r['forwards']} forwards "
              f"counted in them by {per!r} (the ledger's mean per chunk over the window: {mean})"
              + ("" if r["forwards"] else f"; WARNING: none of its operations is under {per!r}, so nothing is "
                 "read by scope: the program carries no scopes (PR 24's parent, or an executable out of a "
                 "compile cache written before them)"), flush=True)
    if not r["forwards"] or not r["ns"]:  # no such program in the stretch, or a program without scopes
        return None
    return r["ns"] / 1e6 / r["forwards"]
