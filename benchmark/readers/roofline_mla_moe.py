"""Reader ``roofline_mla_moe``: the device programs of a ``deepseek_v3``
decoder (a latent cache, leading dense layers, routed + shared experts)
against the chip's published peaks, with the floor of
``lib/peaks_mla_moe.py`` (experts touched and rows assigned from the
program's ``moe.*`` counters, the latent cache by ``attn.latent_keys_read``,
attention's dots and every other per-position matmul on the forward's REAL
positions, everything else once, the head on one position a row).

``program_roofline`` — the least time a decode forward can take over the
device time per forward of ``program`` in the trace, forwards counted in the
SAME traced executions (as ``readers/roofline.py``).
``kernel_roofline`` — the least time one forward's latent-attention calls can
take (the cached positions they read x 1152 B / HBM bandwidth, or the REAL
positions' query rows x keys each may see x 2 x (576 + 512) / bf16 peak) over
their device SELF time per forward: the operations whose scope path holds
the kernel's name.
``grouped_matmul_roofline`` — the least time one forward's ``grouped_matmul``
calls can take (touched experts' planes / HBM bandwidth, or the assigned
rows' FLOPs / bf16 peak) over their device SELF time per forward.

A program without ``attn.latent_keys_read`` (the parent of PR 38; every model
whose cache is K and V) gives nothing to read: every quantity returns None
and never raises."""

from __future__ import annotations

from ..lib import peaks_mla_moe as pkm
from .roofline import kernel_share, needed, program_share, step_mfu, weight_bytes

PROGRAM = "paged_chunk_decode_loop"
KERNEL = "paged_latent_attention"
NEEDS = ("moe.experts_touched", "moe.assigned_rows", "attn.latent_keys_read")


def _per_forward(ctx: dict) -> tuple | None:
    """``NEEDS`` per forward, each summed over layers."""
    c = ctx.get("counters", {})
    fwds = c.get("scheduler.forwards")
    if not fwds or any(k not in c for k in NEEDS) or not c["attn.latent_keys_read"]:
        return None
    return tuple(c[k] / fwds for k in NEEDS)


def read(ctx: dict, what: str, program: str = PROGRAM):
    counted, n = _per_forward(ctx), needed(ctx)
    if (counted is None or n is None or ctx.get("peaks") is None
            or "kv_lora_rank" not in ctx["model"]):
        return None
    touched, assigned, keys = counted
    model, peaks = ctx["model"], ctx["peaks"]
    if what == "step_mfu":
        return step_mfu(ctx, n, pkm.forward_flops(model, n["live"], n["positions"], n["context"], assigned))
    if what == "program_roofline":
        floor, _ = pkm.forward_floor_s(model, peaks, weight_bytes(ctx), n["live"], n["positions"],
                                       n["context"], touched, assigned, keys)
        return program_share(ctx, program, floor)
    if what == "kernel_roofline":
        floor, _ = pkm.latent_attention_floor_s(model, peaks, keys, n["positions"], n["context"])
        return kernel_share(ctx, program, KERNEL, floor)
    if what == "grouped_matmul_roofline":
        floor, _ = pkm.grouped_matmul_floor_s(model, peaks, weight_bytes(ctx), touched, assigned)
        return kernel_share(ctx, program, "grouped_matmul", floor)
    raise ValueError(f"roofline_mla_moe reader: unknown quantity {what!r}")
