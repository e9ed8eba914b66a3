"""Reader ``roofline_mla_moe``: the device programs of a ``deepseek_v3``
decoder (a latent cache, leading dense layers, routed + shared experts)
against the chip's published peaks, with the floor of
``lib/peaks_mla_moe.py`` (experts touched and rows assigned from the
program's ``moe.*`` counters, the latent cache by ``attn.latent_keys_read``,
attention's dots by ``attn.latent_query_rows``, everything else once, the
head on one position a row).

``program_roofline`` — the least time a decode forward can take over the
device time per forward of ``program`` in the trace, forwards counted in the
SAME traced executions (as ``readers/roofline.py``).
``kernel_roofline`` — the least time one forward's latent-attention calls can
take (the cached positions they read x 1152 B / HBM bandwidth, or query rows
x keys each may see x 2 x (576 + 512) / bf16 peak) over their device SELF
time per forward: the operations whose scope path holds the kernel's name.

A program without ``attn.latent_keys_read`` (the parent of PR 38; every model
whose cache is K and V) gives nothing to read: every quantity returns None
and never raises."""

from __future__ import annotations

from ..lib import peaks_mla_moe as pkm
from .host_spans import run_trace
from .roofline import _shape
from .scopes import scope_ns

PROGRAM = "paged_chunk_decode_loop"
KERNEL = "paged_latent_attention"
NEEDS = ("moe.experts_touched", "moe.assigned_rows", "attn.latent_keys_read",
         "attn.latent_query_rows")


def _per_forward(ctx: dict) -> tuple | None:
    """``NEEDS`` per forward, each summed over layers."""
    c = ctx.get("counters", {})
    fwds = c.get("scheduler.forwards")
    if not fwds or any(k not in c for k in NEEDS) or not c["attn.latent_keys_read"]:
        return None
    return tuple(c[k] / fwds for k in NEEDS)


def read(ctx: dict, what: str, program: str = PROGRAM):
    plane = run_trace(ctx)
    counted, shape = _per_forward(ctx), _shape(ctx)
    if (plane is None or counted is None or shape is None or ctx.get("peaks") is None
            or "kv_lora_rank" not in ctx["model"]):
        return None
    touched, assigned, keys, qrows = counted
    _, rows, context = shape
    model, peaks = ctx["model"], ctx["peaks"]
    wbytes = 1 if ctx["serving"]["quant"] == "int8" else 2
    if what == "program_roofline":
        runs = scope_ns(plane, [], program)
        if not runs["forwards"]:
            return None
        floor, _ = pkm.forward_floor_s(model, peaks, wbytes, round(rows),
                                       1 + ctx["serving"]["fast_forward"], context,
                                       touched, assigned, keys, qrows)
        return 100.0 * floor / (runs["program_ns"] / 1e9 / runs["forwards"])
    if what == "kernel_roofline":
        r = scope_ns(plane, [KERNEL], program)
        if not r["forwards"] or not r["ns"]:
            return None
        floor, _ = pkm.latent_attention_floor_s(model, peaks, keys, qrows, context)
        return 100.0 * floor / (r["ns"] / 1e9 / r["forwards"])
    raise ValueError(f"roofline_mla_moe reader: unknown quantity {what!r}")
