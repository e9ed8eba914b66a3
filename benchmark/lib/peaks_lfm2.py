"""The yardstick's arithmetic for an LFM2 decoder with routed experts
(``benchmark/reference/lfm2_decoder.py`` has the equations): the bytes and
operations one decode forward NEEDS, by mixer kind and MLP kind, from the
configuration's own keys and from what the program counted. Beside
``lib/peaks.py`` and never an edit of it.

A layer's mixer is its letter of ``layer_kinds`` (``layer_types``, a letter a
layer): ``C`` gated short convolution, ``F`` attention; its MLP is dense
(``intermediate_size``) for the first ``num_dense_layers`` layers and
``num_experts`` experts of ``moe_intermediate_size`` behind them.

- WEIGHTS every forward streams once, int8: a C mixer's W_in (d x 3d) and W_out
  (d x d); an F mixer's q, k, v, o; a dense layer's gate, up and down; the tied
  head's int8 copy (V x d) ONCE — the embedding's rows are a gather of the real
  positions'. The taps, the norms, the routers (d x E) and their biases stay
  bf16 / float32.
- EXPERTS as ``peaks_routed`` counts them: the planes of the experts a forward
  TOUCHED (``moe.experts_touched``), never E by assumption; FLOPs of the rows
  ASSIGNED (``moe.assigned_rows``), never the tiles' padding.
- TAILS = (live row, C layer) pairs a forward moved (``conv.tail_rows_moved``)
  x (L - 1) x d x 2 B x 2: read once and written once.
- K/V as ``peaks.kv_positions`` counts it for each of the F layers.
- FLOPs: 2 a MAC over the per-position matmuls on the forward's REAL positions,
  (2 L + 2) d a position a C layer for the taps and the two gates, the head on
  ONE position a row, 4 x heads x head_dim an attended position an F layer.

Exact Python integers where the inputs are."""

from __future__ import annotations

from . import peaks as pk


def dims(model: dict) -> dict:
    L = model["num_hidden_layers"]
    kinds = model["layer_kinds"][:L]
    d, nq = model["hidden_size"], model["num_attention_heads"]
    nD = min(model["num_dense_layers"], L)
    return {"d": d, "df": model["intermediate_size"], "f": model["moe_intermediate_size"],
            "E": model["num_experts"], "top": model["num_experts_per_tok"], "nq": nq,
            "nkv": model["num_key_value_heads"], "hd": d // nq, "K": model["conv_L_cache"],
            "V": model["vocab_size"], "nC": kinds.count("C"), "nF": kinds.count("F"), "nD": nD, "nR": L - nD}


def kind_params(model: dict) -> dict:
    """Parameters of ONE of each: {"C" | "F" | "dense" | "routed": (int8, bf16)} —
    ``routed`` without its experts —, and ``expert``: one expert's int8."""
    s = dims(model)
    d = s["d"]
    return {"C": (4 * d * d, s["K"] * d + d),
            "F": (2 * d * s["nq"] * s["hd"] + 2 * d * s["nkv"] * s["hd"], 2 * s["hd"] + d),
            "dense": (3 * d * s["df"], d), "routed": (0, d * s["E"] + s["E"] + d),
            "expert": 3 * d * s["f"]}


def shared_params(model: dict) -> tuple[int, int]:
    """(int8, bf16) weights of the LAYERS every forward streams whatever is routed."""
    s, k = dims(model), kind_params(model)
    return tuple(s["nC"] * k["C"][i] + s["nF"] * k["F"][i] + s["nD"] * k["dense"][i]
                 + s["nR"] * k["routed"][i] for i in (0, 1))


def model_params(model: dict) -> int:
    """Every parameter of the model, the tied embedding once."""
    s, k = dims(model), kind_params(model)
    return sum(shared_params(model)) + s["nR"] * s["E"] * k["expert"] + s["V"] * s["d"] + s["d"]


def expert_bytes(model: dict, weight_bytes: int, touched: float) -> float:
    return touched * kind_params(model)["expert"] * weight_bytes


def expert_flops(model: dict, assigned: float) -> float:
    return assigned * 2 * kind_params(model)["expert"]


def tail_bytes(model: dict, moved: float) -> float:
    """``moved`` (live row, C layer) pairs: each tail read once and written once."""
    s = dims(model)
    return moved * (s["K"] - 1) * s["d"] * 2 * 2


def kv_positions(model: dict, rows: float, ctx: float, common: float = 0.0) -> float:
    return dims(model)["nF"] * pk.kv_positions(rows, ctx, common)


def forward_bytes(model: dict, weight_bytes: int, rows: float, positions: float, ctx: float,
                  touched: float, moved: float, kv_bytes: int = 2, common: float = 0.0) -> float:
    s = dims(model)
    quant, plain = shared_params(model)
    kv = 2 * kv_positions(model, rows, ctx, common) * s["nkv"] * s["hd"] * kv_bytes
    return ((quant + s["V"] * s["d"]) * weight_bytes + plain * 2 + positions * s["d"] * 2
            + expert_bytes(model, weight_bytes, touched) + tail_bytes(model, moved) + kv)


def forward_flops(model: dict, rows: float, positions: float, ctx: float, assigned: float) -> float:
    """``positions`` REAL token positions through the layers at attended context
    ``ctx``, ``assigned`` expert rows, the head on one position of each of ``rows``."""
    s = dims(model)
    quant, plain = shared_params(model)
    per_position = (2 * (quant + plain) + s["nC"] * (2 * s["K"] + 2) * s["d"]
                    + 4 * s["nq"] * s["hd"] * kv_positions(model, 1, ctx))
    return positions * per_position + expert_flops(model, assigned) + rows * 2 * s["V"] * s["d"]


def forward_floor_s(model: dict, peaks: dict, weight_bytes: int, rows: float, positions: float,
                    ctx: float, touched: float, assigned: float, moved: float,
                    common: float = 0.0) -> tuple[float, str]:
    """Least seconds one decode forward can take on this chip, and which roof sets it."""
    t_b = forward_bytes(model, weight_bytes, rows, positions, ctx, touched, moved,
                        common=common) / peaks["bytes_per_s"]
    t_f = forward_flops(model, rows, positions, ctx, assigned) / peaks["flops_per_s"]
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")


def grouped_matmul_floor_s(model: dict, peaks: dict, weight_bytes: int, touched: float,
                           assigned: float) -> tuple[float, str]:
    """Least seconds the three ``grouped_matmul`` calls of every routed layer of
    one forward can take (``peaks_routed.grouped_matmul_floor_s`` at this model's
    expert: ``moe_intermediate_size``, not ``intermediate_size``)."""
    t_b = expert_bytes(model, weight_bytes, touched) / peaks["bytes_per_s"]
    t_f = expert_flops(model, assigned) / peaks["flops_per_s"]
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")


def shortconv_floor_s(model: dict, peaks: dict, weight_bytes: int, positions: float,
                      moved: float) -> tuple[float, str]:
    """Least seconds the C mixers of one forward can take: int8 W_in and W_out and
    the bf16 taps and norm once, the real positions' input read and output
    written (d bf16 each, a layer), the live rows' tails in and out, over HBM
    bandwidth; or their FLOPs over the peak."""
    s, k = dims(model), kind_params(model)
    t_b = (s["nC"] * (k["C"][0] * weight_bytes + k["C"][1] * 2 + positions * 2 * s["d"] * 2)
           + tail_bytes(model, moved)) / peaks["bytes_per_s"]
    t_f = positions * s["nC"] * (2 * k["C"][0] + (2 * s["K"] + 2) * s["d"]) / peaks["flops_per_s"]
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")
