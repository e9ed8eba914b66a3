"""The yardstick's arithmetic for a ROUTED-expert decoder: the bytes and
operations one decode forward needs, from shapes and from what the routing
really did. Beside ``lib/peaks.py`` and never an edit of it: its
``matmul_params`` / ``forward_bytes`` / ``forward_floor_s`` are dense by
construction (one gate/up/down a layer), and a changed dense floor would
move every dense cell's roofline share.

Attention, the head and the KV bytes are counted as ``peaks.py`` counts
them: on the forward's REAL positions, the K/V live rows hold in common
once. The experts are counted from the program's counters (``moe.*``,
``serve/scheduler.py``; summed over layers and forwards, so per forward they
are those over ``scheduler.forwards``):

- expert BYTES = experts actually touched (``moe.experts_touched``: an
  expert-layer with at least one row) x 3 x d x f x the weight's bytes —
  never ``E`` by assumption, so a share over 100 % cannot come from experts
  nobody read;
- expert FLOPs = rows actually routed (``moe.assigned_rows``) x 3 x 2 x d x
  f — never the rows the dispatch padded to.

Exact Python integers where the inputs are."""

from __future__ import annotations

from . import peaks as pk


def routed_dims(model: dict) -> dict:
    """``peaks.decoder_dims`` plus the expert count (HF key names; ``f`` is
    the width of ONE expert)."""
    return dict(pk.decoder_dims(model), E=model["num_experts"])


def shared_params(model: dict) -> tuple[int, int]:
    """(quantised, unquantised) weights every forward streams whatever is
    routed: attention projections and lm_head; the router (d x E a layer)
    and the q/k norm gains stay in bf16."""
    s = routed_dims(model)
    attn = s["d"] * s["nq"] * s["hd"] + 2 * s["d"] * s["nkv"] * s["hd"] + s["nq"] * s["hd"] * s["d"]
    return s["L"] * attn + s["V"] * s["d"], s["L"] * s["d"] * s["E"]


def expert_bytes(model: dict, weight_bytes: int, touched: float) -> float:
    s = routed_dims(model)
    return touched * 3 * s["d"] * s["f"] * weight_bytes


def expert_flops(model: dict, assigned: float) -> float:
    s = routed_dims(model)
    return assigned * 3 * 2 * s["d"] * s["f"]


def forward_bytes(model: dict, weight_bytes: int, rows: float, ctx: float, touched: float,
                  kv_bytes: int = 2, common: float = 0.0) -> float:
    """HBM bytes ONE decode forward must read: the shared weights once, the
    planes of the experts it touched, the attended K and V (the ``common``
    positions once, each live row's own a row: ``peaks.kv_positions``)."""
    s = routed_dims(model)
    quant, plain = shared_params(model)
    kv = 2 * s["L"] * pk.kv_positions(rows, ctx, common) * s["nkv"] * s["hd"] * kv_bytes
    return quant * weight_bytes + plain * 2 + expert_bytes(model, weight_bytes, touched) + kv


def forward_flops(model: dict, positions: float, ctx: float, assigned: float) -> float:
    """FLOPs of ``positions`` REAL token positions at attended context
    ``ctx``: 2 per MAC over the shared matmuls and the router, 4*nq*hd per
    attended position, and the expert rows that were routed."""
    s = routed_dims(model)
    quant, plain = shared_params(model)
    return positions * (2 * (quant + plain) + ctx * 4 * s["nq"] * s["hd"]) + expert_flops(model, assigned)


def forward_floor_s(model: dict, peaks: dict, weight_bytes: int, rows: float,
                    positions: float, ctx: float, touched: float,
                    assigned: float, common: float = 0.0) -> tuple[float, str]:
    """Least seconds one routed decode forward can take on this chip, and
    which roof sets it. ``positions``: the forward's real positions."""
    t_b = forward_bytes(model, weight_bytes, rows, ctx, touched, common=common) / peaks["bytes_per_s"]
    t_f = forward_flops(model, positions, ctx, assigned) / peaks["flops_per_s"]
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")


def grouped_matmul_floor_s(model: dict, peaks: dict, weight_bytes: int, touched: float,
                           assigned: float) -> tuple[float, str]:
    """Least seconds the three ``grouped_matmul`` calls of every layer of one
    forward can take: the touched experts' planes over HBM bandwidth, or the
    routed rows' FLOPs over the bf16 peak (the kernel multiplies bf16 x bf16).
    Rows ASSIGNED and planes TOUCHED, never the row tiles the dispatch padded
    to: the tile follows the packed width x top-k (PR 37), the floor does not."""
    t_b = expert_bytes(model, weight_bytes, touched) / peaks["bytes_per_s"]
    t_f = expert_flops(model, assigned) / peaks["flops_per_s"]
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")
