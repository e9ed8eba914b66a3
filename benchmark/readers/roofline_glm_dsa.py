"""Reader ``roofline_glm_dsa``: the device programs of ONE CHIP'S SHARE of a
``glm_moe_dsa`` decoder (latent attention under a selection that some layers
make and the others reuse, held experts) against the chip's published peaks,
with the floor of ``lib/peaks_glm_dsa.py`` (held experts touched and local
rows from the program's ``moe.*`` counters, SELECTED and VISIBLE keys from its
``attn.keys_*`` counters — counted over every layer that attends a selection —,
everything else once, the head on one position a row).

``program_roofline`` / ``step_mfu`` — as ``readers/roofline.py``, with this
block's floor. ``grouped_matmul_roofline`` — the held experts' planes touched
or the local rows' FLOPs over the kernel's device self time a forward.
``indexer_roofline`` — the least time one forward's calls of the kernel can
take over the device SELF time a forward of the operations under the scope the
program opens around the kernel, which bears its name (``indexer_scores``: the
indexer layers' calls). ``sparse_attn_roofline`` — the least time the SELECTED
rows of one forward can take (each once a layer, all layers) over the device
self time a forward under ``SELECTED``: the gather that reads them out of the
pool AND the ``sparse_latent_attention`` kernel that attends them. The kernel's
own time alone is no denominator here: at 64 heads a position its floor is the
rows' bytes, the one HBM read the algorithm needs is the GATHER's, and the kernel
alone read 145.6 % of the HBM roof on the chip (PERF.md section 6, PR 61). ``carried_share`` — of the (real
position, layer) pairs that attended a selection, the share whose layer was
HANDED its set: ``attn.selections_carried`` / (made + carried), in %.

A program without ``attn.selections_carried`` (the parent of PR 61; every
model whose layers each select for themselves) gives nothing to read: every
quantity returns None and never raises."""

from __future__ import annotations

from ..lib import peaks_glm_dsa as pkg
from .host_spans import run_trace
from .roofline import kernel_share, needed, program_share, step_mfu, weight_bytes
from .scopes import scope_ns

PROGRAM = "paged_chunk_decode_loop"
SELECTED = ["layer/attn/select", "layer/attn/full"]  # the selected rows: gathered, then attended
NEEDS = ("moe.experts_touched", "moe.local_rows", "attn.keys_selected", "attn.keys_visible")
CARRY = ("attn.selections_made", "attn.selections_carried")


def _per_forward(ctx: dict) -> tuple | None:
    """``NEEDS`` per forward, each summed over layers."""
    c = ctx.get("counters", {})
    fwds = c.get("scheduler.forwards")
    if not fwds or any(k not in c for k in NEEDS + CARRY) or not c["attn.keys_selected"]:
        return None
    return tuple(c[k] / fwds for k in NEEDS)


def read(ctx: dict, what: str, program: str = PROGRAM):
    c = ctx.get("counters", {})
    if what == "carried_share":
        made, carried = (c.get(k) for k in CARRY)
        return None if made is None or carried is None or not made + carried else \
            100.0 * carried / (made + carried)
    counted, n = _per_forward(ctx), needed(ctx)
    if (counted is None or n is None or ctx.get("peaks") is None
            or "indexer_kinds" not in ctx["model"]):
        return None
    touched, local, selected, visible = counted
    model, peaks = ctx["model"], ctx["peaks"]
    rows, positions, context = n["live"], n["positions"], n["context"]
    if what == "step_mfu":
        return step_mfu(ctx, n, pkg.forward_flops(model, rows, positions, local, selected, visible))
    if what == "program_roofline":
        floor, _ = pkg.forward_floor_s(model, peaks, weight_bytes(ctx), rows, positions, context,
                                       touched, local, selected, visible)
        return program_share(ctx, program, floor)
    if what == "grouped_matmul_roofline":
        floor, _ = pkg.grouped_matmul_floor_s(model, peaks, weight_bytes(ctx), touched, local)
        return kernel_share(ctx, program, "grouped_matmul", floor)
    if what == "sparse_attn_roofline":
        floor, _ = pkg.selected_attention_floor_s(model, peaks, selected)
        plane = run_trace(ctx)
        r = scope_ns(plane, SELECTED, program) if plane else None
        return 100.0 * floor / (r["ns"] / 1e9 / r["forwards"]) if r and r["forwards"] and r["ns"] else None
    if what == "indexer_roofline":
        floor, _ = pkg.indexer_floor_s(model, peaks, context, visible)
        return kernel_share(ctx, program, "indexer_scores", floor)
    raise ValueError(f"roofline_glm_dsa reader: unknown quantity {what!r}")
