"""The yardstick's arithmetic for STAGE 0 of a SmallThinker-21BA3B-Instruct
decoder (``benchmark/reference/smallthinker_decoder.py`` has the equations):
the bytes and operations one decode forward needs, from the configuration's
own keys (the source spells them ``moe_*``) and from what the routing really
did. Beside ``lib/peaks.py`` and ``lib/peaks_routed.py`` and never an edit of
either.

- WEIGHTS every forward streams once, int8: a layer's attention planes (q, k,
  v, o at ``head_dim`` a head: 28 x 128 is not the hidden size) and the head.
  The router (d x ``moe_num_primary_experts`` a layer) stays bf16.
- EXPERT BYTES = experts actually touched (``moe.experts_touched``) x 3 x d x
  f x the weight's bytes; EXPERT FLOPs = the rows actually routed
  (``moe.assigned_rows``) x 3 x 2 x d x f — never the row tiles the dispatch
  padded to.
- K/V of a FULL layer (``layer_kinds`` F) as ``peaks_routed`` counts it: the
  positions live rows hold in common ONCE a forward, each row's own beyond
  them a row.
- K/V of a SLIDING layer (S): a row reads its last ``sliding_window_size``
  positions. Behind a cached head of ``head`` positions that every row's
  table names by the SAME blocks, the part of every live row's window that
  lies inside the head is held in common and is NEEDED once a forward — the
  positions from the furthest row's window start to the head's end — and a
  row's own positions beyond the head a row (PR 42's rule; the program walks
  each row's whole window a row, and that is the finding).
- the HEAD on ONE position a row (the chunk program runs it there alone);
  every other matmul on the forward's REAL positions, never on rows x (1 + W).

Exact Python integers where the inputs are."""

from __future__ import annotations

from . import peaks as pk


def dims(model: dict) -> dict:
    kinds = str(model["layer_kinds"])
    return {"d": model["hidden_size"], "f": model["moe_ffn_hidden_size"],
            "nq": model["num_attention_heads"], "nkv": model["num_key_value_heads"],
            "hd": model["head_dim"], "L": model["num_hidden_layers"], "V": model["vocab_size"],
            "E": model["moe_num_primary_experts"], "K": model["moe_num_active_primary_experts"],
            "window": model["sliding_window_size"], "n_full": kinds.count("F"),
            "n_sliding": kinds.count("S")}


def layer_params(model: dict) -> tuple[int, int]:
    """(int8, bf16) weights of the LAYERS every forward streams whatever is
    routed: attention; the router."""
    s = dims(model)
    attn = s["d"] * s["nq"] * s["hd"] + 2 * s["d"] * s["nkv"] * s["hd"] + s["nq"] * s["hd"] * s["d"]
    return s["L"] * attn, s["L"] * s["d"] * s["E"]


def expert_params(model: dict) -> int:
    """One layer's experts, all of them."""
    s = dims(model)
    return s["E"] * 3 * s["d"] * s["f"]


def held_bytes(model: dict, blocks: int, block_size: int) -> dict:
    """What the chip holds, by part: int8 layers, the bf16 router, the bf16
    embedding, the int8 head, the bf16 K/V pool."""
    s = dims(model)
    quant, plain = layer_params(model)
    return {"layers": quant + s["L"] * expert_params(model), "router": 2 * plain,
            "embedding": 2 * s["V"] * s["d"], "head": s["V"] * s["d"],
            "kv": blocks * block_size * s["L"] * 2 * s["nkv"] * s["hd"] * 2}


def window_positions(model: dict, rows: float, ctx: float, head: float) -> float:
    """Positions of K (and of V) ONE sliding layer's reads NEED a forward over
    ``rows`` live rows of mean context ``ctx`` behind a shared head of ``head``
    positions: the rows' windows' common part inside the head once, each
    row's own positions beyond the head a row. Without a shared head
    (``head`` 0) every row's min(context, window) a row."""
    s = dims(model)
    own = max(ctx - head, 0.0)
    if own >= s["window"] or head <= 0:
        return rows * min(ctx, s["window"])
    inside = min(s["window"] - own, head)  # of a row's window, what lies inside the head
    return inside + rows * own


def kv_positions(model: dict, rows: float, ctx: float, common: float, head: float) -> float:
    """Positions of K (and of V) ONE forward needs, over all the layers."""
    s = dims(model)
    return (s["n_full"] * pk.kv_positions(rows, ctx, common)
            + s["n_sliding"] * window_positions(model, rows, ctx, head))


def expert_bytes(model: dict, weight_bytes: int, touched: float) -> float:
    s = dims(model)
    return touched * 3 * s["d"] * s["f"] * weight_bytes


def expert_flops(model: dict, assigned: float) -> float:
    s = dims(model)
    return assigned * 3 * 2 * s["d"] * s["f"]


def forward_bytes(model: dict, weight_bytes: int, rows: float, ctx: float, touched: float,
                  common: float, head: float, kv_bytes: int = 2) -> float:
    s = dims(model)
    quant, plain = layer_params(model)
    kv = 2 * kv_positions(model, rows, ctx, common, head) * s["nkv"] * s["hd"] * kv_bytes
    return ((quant + s["V"] * s["d"]) * weight_bytes + plain * 2
            + expert_bytes(model, weight_bytes, touched) + kv)


def attended(model: dict, ctx: float) -> float:
    """Keys ONE position attends, summed over the layers."""
    s = dims(model)
    return s["n_full"] * ctx + s["n_sliding"] * min(ctx, s["window"])


def forward_flops(model: dict, rows: float, positions: float, ctx: float, assigned: float) -> float:
    """``positions`` REAL token positions through the layers at attended
    context ``ctx``, the head on one position of each of ``rows`` rows."""
    s = dims(model)
    quant, plain = layer_params(model)
    per_position = 2 * (quant + plain) + 4 * s["nq"] * s["hd"] * attended(model, ctx)
    return positions * per_position + rows * 2 * s["V"] * s["d"] + expert_flops(model, assigned)


def forward_floor_s(model: dict, peaks: dict, weight_bytes: int, rows: float, positions: float,
                    ctx: float, touched: float, assigned: float, common: float,
                    head: float) -> tuple[float, str]:
    """Least seconds one decode forward of this stage can take on this chip,
    and which roof sets it. ``positions``: the forward's real positions."""
    t_b = forward_bytes(model, weight_bytes, rows, ctx, touched, common, head) / peaks["bytes_per_s"]
    t_f = forward_flops(model, rows, positions, ctx, assigned) / peaks["flops_per_s"]
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")


def grouped_matmul_floor_s(model: dict, peaks: dict, weight_bytes: int, touched: float,
                           assigned: float) -> tuple[float, str]:
    """Least seconds the three ``grouped_matmul`` calls of every layer of one
    forward can take: the touched experts' planes over HBM bandwidth, or the
    routed rows' FLOPs over the bf16 peak."""
    t_b = expert_bytes(model, weight_bytes, touched) / peaks["bytes_per_s"]
    t_f = expert_flops(model, assigned) / peaks["flops_per_s"]
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")


def window_attention_floor_s(model: dict, peaks: dict, rows: float, positions: float, ctx: float,
                             head: float, kv_bytes: int = 2) -> tuple[float, str]:
    """Least seconds the SLIDING layers' block-kernel calls of one forward can
    take: the K and V positions they need (``window_positions``) over HBM
    bandwidth, or the real positions' query rows x their window's keys x 4 x
    head_dim over the bf16 peak."""
    s = dims(model)
    t_b = (2 * s["n_sliding"] * window_positions(model, rows, ctx, head) * s["nkv"] * s["hd"]
           * kv_bytes) / peaks["bytes_per_s"]
    t_f = (s["n_sliding"] * positions * 4 * s["nq"] * s["hd"] * min(ctx, s["window"])
           / peaks["flops_per_s"])
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")
