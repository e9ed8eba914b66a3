"""The yardstick's arithmetic for ONE CHIP'S SHARE of a Command A+
(``cohere2_moe``) decoder (``benchmark/reference/cohere2moe_decoder.py`` has
the equations): the bytes and operations one decode forward needs HERE, from
the configuration's own keys and from what the routing really did. Beside
``lib/peaks.py`` and ``lib/peaks_routed.py`` and never an edit of either.

- WEIGHTS every forward streams once, int8: a layer's attention planes (q, k,
  v, o at ``head_dim`` a head, which is not hidden / heads) and its
  ``num_shared_experts`` shared experts, and the int8 head (the copy of the
  tied embedding; the embedding itself is a gather of a few rows). The router
  (d x ``num_experts_published`` a layer) stays bf16.
- EXPERT BYTES = held experts actually touched (``moe.experts_touched``, of
  the ``num_experts`` held a layer) x 3 x d x f x the weight's bytes — never
  the held count by assumption, and never an absent expert: nothing of one is
  on this chip.
- EXPERT FLOPs = the rows that fell on held experts (``moe.local_rows``) x 3
  x 2 x d x f — neither the rows the router assigned elsewhere nor the rows
  the dispatch padded to.
- K/V as ``peaks_routed`` counts it: the positions live rows hold in common
  ONCE a forward, each row's own beyond them a row, every layer (a sliding
  layer reads min(context, window): at this cell's 1536 positions the context).
- the HEAD on ONE position a row (the chunk program runs it there alone);
  every other matmul on the forward's REAL positions, never on rows x (1 + W).

Exact Python integers where the inputs are."""

from __future__ import annotations

from . import peaks as pk


def dims(model: dict) -> dict:
    """``peaks.decoder_dims`` (``f`` is the width of ONE expert) plus the
    router's width, the experts held and shared, and the window."""
    return dict(pk.decoder_dims(model), E=model["num_experts_published"], held=model["num_experts"],
                shared=model["num_shared_experts"], window=model["sliding_window"],
                switch=model["layer_switch"])


def layer_params(model: dict) -> tuple[int, int]:
    """(int8, bf16) weights of the LAYERS every forward streams whatever is
    routed: attention and the shared experts; the router."""
    s = dims(model)
    attn = s["d"] * s["nq"] * s["hd"] + 2 * s["d"] * s["nkv"] * s["hd"] + s["nq"] * s["hd"] * s["d"]
    return s["L"] * (attn + s["shared"] * 3 * s["d"] * s["f"]), s["L"] * s["d"] * s["E"]


def kv_positions(model: dict, rows: float, ctx: float, common: float = 0.0) -> float:
    """Positions of K (and of V) ONE forward reads, over the layers and the
    live rows: a layer's ``common`` leading positions once
    (``peaks.kv_positions``), a sliding layer never more than its window."""
    s = dims(model)
    n_full = s["L"] // s["switch"]
    return (n_full * pk.kv_positions(rows, ctx, common)
            + (s["L"] - n_full) * pk.kv_positions(rows, min(ctx, s["window"]), common))


def expert_bytes(model: dict, weight_bytes: int, touched: float) -> float:
    s = dims(model)
    return touched * 3 * s["d"] * s["f"] * weight_bytes


def expert_flops(model: dict, local_rows: float) -> float:
    s = dims(model)
    return local_rows * 3 * 2 * s["d"] * s["f"]


def forward_bytes(model: dict, weight_bytes: int, rows: float, ctx: float, touched: float,
                  kv_bytes: int = 2, common: float = 0.0) -> float:
    s = dims(model)
    quant, plain = layer_params(model)
    kv = 2 * kv_positions(model, rows, ctx, common) * s["nkv"] * s["hd"] * kv_bytes
    return ((quant + s["V"] * s["d"]) * weight_bytes + plain * 2
            + expert_bytes(model, weight_bytes, touched) + kv)


def forward_flops(model: dict, rows: float, positions: float, ctx: float, local_rows: float) -> float:
    """``positions`` REAL token positions through the layers at attended
    context ``ctx``, the head on one position of each of ``rows`` rows."""
    s = dims(model)
    quant, plain = layer_params(model)
    per_position = 2 * (quant + plain) + 4 * s["nq"] * s["hd"] * kv_positions(model, 1, ctx)
    return positions * per_position + rows * 2 * s["V"] * s["d"] + expert_flops(model, local_rows)


def forward_floor_s(model: dict, peaks: dict, weight_bytes: int, rows: float,
                    positions: float, ctx: float, touched: float,
                    local_rows: float, common: float = 0.0) -> tuple[float, str]:
    """Least seconds one decode forward of this share can take on this chip,
    and which roof sets it. ``positions``: the forward's real positions."""
    t_b = forward_bytes(model, weight_bytes, rows, ctx, touched, common=common) / peaks["bytes_per_s"]
    t_f = forward_flops(model, rows, positions, ctx, local_rows) / peaks["flops_per_s"]
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")


def grouped_matmul_floor_s(model: dict, peaks: dict, weight_bytes: int, touched: float,
                           local_rows: float) -> tuple[float, str]:
    """Least seconds the three ``grouped_matmul`` calls of every layer of one
    forward can take: the touched held experts' planes over HBM bandwidth, or
    the local rows' FLOPs over the bf16 peak (the kernel multiplies bf16 x bf16).
    Local rows and planes touched, never the row tiles the dispatch padded to."""
    t_b = expert_bytes(model, weight_bytes, touched) / peaks["bytes_per_s"]
    t_f = expert_flops(model, local_rows) / peaks["flops_per_s"]
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")
