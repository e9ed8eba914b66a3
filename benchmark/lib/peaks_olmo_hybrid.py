"""The yardstick's arithmetic for an OLMo hybrid decoder
(``benchmark/reference/olmo_hybrid_decoder.py`` has the equations): the bytes
and operations one decode forward NEEDS, by layer KIND, from the
configuration's own keys and from what the program counted. Beside
``lib/peaks.py`` and never an edit of it.

The layers are the first ``num_hidden_layers`` letters of ``layer_kinds``
(``layer_types``, a letter a layer): ``L`` Gated DeltaNet, ``F`` full
attention — each with an MLP of ``intermediate_size``.

- WEIGHTS every forward streams once, int8: an L layer's W_q, W_k (d x H d_k
  each), W_v, W_g (d x H d_v each) and W_o (H d_v x d); an F layer's q, k, v,
  o; every layer's gate, up and down; the int8 head. The a | b projections
  (d x 2 H), the convolutions and the norms stay bf16.
- STATE = (live row, L layer) pairs a forward moved (``gdn.state_rows_moved``)
  x H x d_k x d_v x 4 B x 2: read once and written once whatever the block's
  length, UNPADDED (the program's planes are dense: two heads on 384 lanes).
- K/V as ``peaks.kv_positions`` counts it for each of the F layers: the
  positions live rows hold in common ONCE, each row's own a row.
- FLOPs: 2 a MAC over the per-position matmuls on the forward's REAL
  positions, the head on ONE position a row, 4 x heads x head_dim an attended
  position an F layer, and 7 a state element a real position in the
  recurrence (decay; the read S'^T k: multiply and add; the write k (x) u:
  multiply and add; the output S^T q: multiply and add).

Exact Python integers where the inputs are."""

from __future__ import annotations

from . import peaks as pk


def dims(model: dict) -> dict:
    L = model["num_hidden_layers"]
    kinds = model["layer_kinds"][:L]
    d, nq = model["hidden_size"], model["num_attention_heads"]
    return {"d": d, "f": model["intermediate_size"], "nq": nq, "nkv": model["num_key_value_heads"],
            "hd": d // nq, "H": model["linear_num_value_heads"], "dk": model["linear_key_head_dim"],
            "dv": model["linear_value_head_dim"], "K": model["linear_conv_kernel_dim"],
            "V": model["vocab_size"], "nL": kinds.count("L"), "nF": kinds.count("F")}


def kind_params(model: dict) -> dict:
    """Parameters of ONE layer of each kind: {"L" | "F": (int8, bf16)}, the MLP in both."""
    s = dims(model)
    d, kd, vd = s["d"], s["H"] * s["dk"], s["H"] * s["dv"]
    mlp = 3 * d * s["f"]
    return {"L": (d * (2 * kd + 2 * vd) + vd * d + mlp,
                  d * 2 * s["H"] + s["K"] * (2 * kd + vd) + 2 * s["H"] + s["dv"] + 2 * d),
            "F": (2 * d * s["nq"] * s["hd"] + 2 * d * s["nkv"] * s["hd"] + mlp,
                  s["nq"] * s["hd"] + s["nkv"] * s["hd"] + 2 * d)}


def layer_params(model: dict) -> tuple[int, int]:
    """(int8, bf16) weights of the LAYERS every forward streams."""
    s, k = dims(model), kind_params(model)
    return tuple(s["nL"] * k["L"][i] + s["nF"] * k["F"][i] for i in (0, 1))


def state_bytes(model: dict, moved: float) -> float:
    """``moved`` (live row, L layer) pairs: each state read once and written once."""
    s = dims(model)
    return moved * s["H"] * s["dk"] * s["dv"] * 4 * 2


def scan_flops(model: dict, positions: float) -> float:
    s = dims(model)
    return positions * s["nL"] * s["H"] * s["dk"] * s["dv"] * 7


def kv_positions(model: dict, rows: float, ctx: float, common: float = 0.0) -> float:
    return dims(model)["nF"] * pk.kv_positions(rows, ctx, common)


def forward_bytes(model: dict, weight_bytes: int, rows: float, ctx: float, moved: float,
                  kv_bytes: int = 2, common: float = 0.0) -> float:
    s = dims(model)
    quant, plain = layer_params(model)
    kv = 2 * kv_positions(model, rows, ctx, common) * s["nkv"] * s["hd"] * kv_bytes
    return (quant + s["V"] * s["d"]) * weight_bytes + plain * 2 + state_bytes(model, moved) + kv


def forward_flops(model: dict, rows: float, positions: float, ctx: float) -> float:
    """``positions`` REAL token positions through the layers at attended
    context ``ctx``, the head on one position of each of ``rows`` rows."""
    s = dims(model)
    quant, plain = layer_params(model)
    per_position = 2 * (quant + plain) + 4 * s["nq"] * s["hd"] * kv_positions(model, 1, ctx)
    return positions * per_position + scan_flops(model, positions) + rows * 2 * s["V"] * s["d"]


def forward_floor_s(model: dict, peaks: dict, weight_bytes: int, rows: float, positions: float,
                    ctx: float, moved: float, common: float = 0.0) -> tuple[float, str]:
    """Least seconds one decode forward can take on this chip, and which roof sets it."""
    t_b = forward_bytes(model, weight_bytes, rows, ctx, moved, common=common) / peaks["bytes_per_s"]
    t_f = forward_flops(model, rows, positions, ctx) / peaks["flops_per_s"]
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")


def scan_floor_s(model: dict, peaks: dict, moved: float, positions: float) -> tuple[float, str]:
    """Least seconds the ``gated_delta_scan`` calls of one forward can take: the
    states moved over HBM bandwidth, or the recurrence's FLOPs over the peak."""
    t_b = state_bytes(model, moved) / peaks["bytes_per_s"]
    t_f = scan_flops(model, positions) / peaks["flops_per_s"]
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")
