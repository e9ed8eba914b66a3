"""The yardstick's arithmetic for ONE CHIP'S SHARE of a Nemotron-H hybrid
decoder (``benchmark/reference/nemotron_h_decoder.py`` has the equations):
the bytes and operations one decode forward NEEDS here, by layer KIND, from
the configuration's own keys and from what the program counted. Beside
``lib/peaks.py`` and never an edit of it.

The layers are the first ``num_hidden_layers`` characters of
``hybrid_override_pattern``: ``M`` Mamba-2, ``E`` latent experts, ``*``
attention — ONE block a layer.

- WEIGHTS every forward streams once, int8: an M layer's in_proj (d x
  (2 d_inner + 2 G N + H)) and out_proj; an E layer's fc1, fc2 and shared
  expert (2 x d x shared width); a * layer's q, k, v, o; the int8 head. The
  router (d x its published width a layer), the convolutions and the norms
  stay bf16.
- EXPERT BYTES = held experts actually TOUCHED (``moe.experts_touched``) x 2
  planes x latent x expert width x the weight's bytes — never the held count
  by assumption, never an absent expert.
- EXPERT FLOPs = the rows that fell on held experts (``moe.local_rows``) x 2
  planes x 2 x latent x expert width.
- STATE = (live row, M layer) pairs a forward moved (``ssm.state_rows_moved``)
  x H x P x N x 4 B x 2: read once and written once whatever the block's
  length.
- K/V as ``peaks.kv_positions`` counts it for each of the * layers: the
  positions live rows hold in common ONCE, each row's own a row.
- FLOPs: 2 a MAC over the per-position matmuls on the forward's REAL
  positions, the head on ONE position a row, 4 x heads x head_dim an attended
  position a * layer, and 5 a state element a position in the recurrence
  (decay, outer product, add, and the read's multiply-add).

Exact Python integers where the inputs are."""

from __future__ import annotations

from . import peaks as pk


def dims(model: dict) -> dict:
    L = model["num_hidden_layers"]
    pattern = model["hybrid_override_pattern"][:L]
    H, P = model["mamba_num_heads"], model["mamba_head_dim"]
    G, N = model["n_groups"], model["ssm_state_size"]
    return {"d": model["hidden_size"], "H": H, "P": P, "G": G, "N": N, "K": model["conv_kernel"],
            "di": H * P, "cd": H * P + 2 * G * N, "nq": model["num_attention_heads"],
            "nkv": model["num_key_value_heads"], "hd": model["head_dim"],
            "E": model.get("n_routed_experts_published", model["n_routed_experts"]),
            "held": model["n_routed_experts"], "top_k": model["num_experts_per_tok"],
            "lat": model["moe_latent_size"], "f": model["moe_intermediate_size"],
            "sf": model["moe_shared_expert_intermediate_size"], "V": model["vocab_size"],
            "nM": pattern.count("M"), "nE": pattern.count("E"), "nA": pattern.count("*")}


def kind_params(model: dict) -> dict:
    """Parameters of ONE layer of each kind, apart from its routed experts:
    {"M" | "E" | "*": (int8, bf16)}, and "expert": one routed expert's."""
    s = dims(model)
    d = s["d"]
    return {"M": (d * (s["di"] + s["cd"] + s["H"]) + s["di"] * d,
                  s["K"] * s["cd"] + s["cd"] + 3 * s["H"] + s["di"] + d),
            "E": (2 * d * s["lat"] + 2 * d * s["sf"], d * s["E"] + s["E"] + d),
            "*": (2 * d * s["nq"] * s["hd"] + 2 * d * s["nkv"] * s["hd"], d),
            "expert": 2 * s["lat"] * s["f"]}


def layer_params(model: dict) -> tuple[int, int]:
    """(int8, bf16) weights of the LAYERS every forward streams whatever is routed."""
    s, k = dims(model), kind_params(model)
    return tuple(s["nM"] * k["M"][i] + s["nE"] * k["E"][i] + s["nA"] * k["*"][i] for i in (0, 1))


def expert_bytes(model: dict, weight_bytes: int, touched: float) -> float:
    return touched * kind_params(model)["expert"] * weight_bytes


def expert_flops(model: dict, local_rows: float) -> float:
    return local_rows * 2 * kind_params(model)["expert"]


def state_bytes(model: dict, moved: float) -> float:
    """``moved`` (live row, M layer) pairs: each state read once and written once."""
    s = dims(model)
    return moved * s["H"] * s["P"] * s["N"] * 4 * 2


def scan_flops(model: dict, positions: float) -> float:
    s = dims(model)
    return positions * s["nM"] * s["H"] * s["P"] * s["N"] * 5


def kv_positions(model: dict, rows: float, ctx: float, common: float = 0.0) -> float:
    return dims(model)["nA"] * pk.kv_positions(rows, ctx, common)


def forward_bytes(model: dict, weight_bytes: int, rows: float, ctx: float, touched: float,
                  moved: float, kv_bytes: int = 2, common: float = 0.0) -> float:
    s = dims(model)
    quant, plain = layer_params(model)
    kv = 2 * kv_positions(model, rows, ctx, common) * s["nkv"] * s["hd"] * kv_bytes
    return ((quant + s["V"] * s["d"]) * weight_bytes + plain * 2
            + expert_bytes(model, weight_bytes, touched) + state_bytes(model, moved) + kv)


def forward_flops(model: dict, rows: float, positions: float, ctx: float, local_rows: float) -> float:
    """``positions`` REAL token positions through the layers at attended
    context ``ctx``, the head on one position of each of ``rows`` rows."""
    s = dims(model)
    quant, plain = layer_params(model)
    per_position = 2 * (quant + plain) + 4 * s["nq"] * s["hd"] * kv_positions(model, 1, ctx)
    return (positions * per_position + scan_flops(model, positions) + rows * 2 * s["V"] * s["d"]
            + expert_flops(model, local_rows))


def forward_floor_s(model: dict, peaks: dict, weight_bytes: int, rows: float, positions: float,
                    ctx: float, touched: float, local_rows: float, moved: float,
                    common: float = 0.0) -> tuple[float, str]:
    """Least seconds one decode forward of this share can take on this chip,
    and which roof sets it."""
    t_b = forward_bytes(model, weight_bytes, rows, ctx, touched, moved, common=common) / peaks["bytes_per_s"]
    t_f = forward_flops(model, rows, positions, ctx, local_rows) / peaks["flops_per_s"]
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")


def grouped_matmul_floor_s(model: dict, peaks: dict, weight_bytes: int, touched: float,
                           local_rows: float) -> tuple[float, str]:
    """Least seconds the two ``grouped_matmul`` calls of every E layer of one
    forward can take: the touched held experts' planes over HBM bandwidth, or
    the local rows' FLOPs over the bf16 peak."""
    t_b = expert_bytes(model, weight_bytes, touched) / peaks["bytes_per_s"]
    t_f = expert_flops(model, local_rows) / peaks["flops_per_s"]
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")


def scan_floor_s(model: dict, peaks: dict, moved: float, positions: float) -> tuple[float, str]:
    """Least seconds the ``ssd_scan`` calls of one forward can take: the
    states moved over HBM bandwidth, or the recurrence's FLOPs over the peak."""
    t_b = state_bytes(model, moved) / peaks["bytes_per_s"]
    t_f = scan_flops(model, positions) / peaks["flops_per_s"]
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")
