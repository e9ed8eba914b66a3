"""The yardstick's arithmetic for a SambaY decoder-hybrid-decoder
(``benchmark/reference/sambay_decoder.py`` has the equations): the bytes and
operations one decode forward needs, by layer KIND, from the configuration's
own keys. Beside ``lib/peaks.py`` and never an edit of it.

With L layers and h = L / 2 there are h/2 + 1 state-space layers, h/2
windowed attention layers, one full attention layer, h/2 - 1 cross-attention
layers (they read the full layer's K/V and own none) and h/2 - 1 gated
memory units; every layer has the (d -> 2f -> d) MLP.

- WEIGHTS a forward streams once: the int8 planes of every large projection
  and the int8 head (the copy of the tied embedding; the embedding itself is
  a gather of a few rows), the small bf16 ones (x_proj, dt_proj, the
  convolution) beside them.
- K/V a forward reads, bf16: a windowed layer at most ``window`` positions a
  row (and never more than the context; no row rides a common pass there),
  the full layer the context, each cross-attention layer the full layer's
  context AGAIN (it is a read of its own) — in those 1 + 7 reads the
  positions live rows hold in common ONCE, each row's own a row — 2 (K and
  V) x n_kv_heads x head_dim a position, the published width
  (the served layout packs pairs of half-heads, the same bytes).
- STATE of a live row: each state-space layer's float32 (d_inner x d_state)
  read once and written once a forward, whatever the block's length.
- FLOPs: 2 a MAC over the per-position matmuls on the forward's REAL
  positions (never rows x (1 + W): this model packs nothing and its floor
  still counts what is needed), the head on ONE position a row, 4 x n_heads
  x head_dim an attended position (two softmaxes over half
  the heads each, values twice as wide: the same count as plain attention
  at these head sizes), and ~9 a state element a position in the scan.

Exact Python integers where the inputs are."""

from __future__ import annotations

from . import peaks as pk


def dims(model: dict) -> dict:
    d, nq = model["hidden_size"], model["num_attention_heads"]
    L = model["num_hidden_layers"]
    return {"d": d, "f": model["intermediate_size"], "nq": nq, "nkv": model["num_key_value_heads"],
            "hd": d // nq, "L": L, "V": model["vocab_size"], "window": model["sliding_window"],
            "di": model["ssm_d_inner"], "ds": model["ssm_d_state"], "dc": model["ssm_d_conv"],
            "dr": model["ssm_dt_rank"],
            "n_ssm": L // 4 + 1, "n_window": L // 4, "n_full": 1, "n_cross": L // 4 - 1,
            "n_gmu": L // 4 - 1}


def layer_params(model: dict) -> tuple[int, int]:
    """(int8, bf16) matmul weights of the LAYERS a forward streams."""
    s = dims(model)
    mlp = 3 * s["d"] * s["f"]
    ssm = 3 * s["d"] * s["di"]  # in_proj (d -> 2 di) and out_proj
    gmu = 2 * s["d"] * s["di"]
    qo = 2 * s["d"] * s["nq"] * s["hd"]  # W_q's part and W_o
    kv = 2 * s["d"] * s["nkv"] * s["hd"]
    int8 = (s["L"] * mlp + s["n_ssm"] * ssm + s["n_gmu"] * gmu
            + (s["n_window"] + s["n_full"]) * (qo + kv) + s["n_cross"] * qo)
    small = s["n_ssm"] * (s["di"] * (s["dr"] + 2 * s["ds"]) + s["dr"] * s["di"] + s["dc"] * s["di"])
    return int8, small


def kv_positions(model: dict, rows: float, ctx: float, common: float = 0.0) -> float:
    """Positions of K (and of V) ONE forward reads, over the layers and the
    live rows: the full layer's and the cross-attention reads take their
    ``common`` leading positions once (``peaks.kv_positions``)."""
    s = dims(model)
    return (s["n_window"] * rows * min(ctx, s["window"])
            + (s["n_full"] + s["n_cross"]) * pk.kv_positions(rows, ctx, common))


def state_bytes(model: dict, rows: float) -> float:
    """The scans' byte floor of one forward: each live row's float32 state,
    read once and written once, in every state-space layer."""
    s = dims(model)
    return rows * s["n_ssm"] * s["di"] * s["ds"] * 4 * 2


def forward_bytes(model: dict, weight_bytes: int, rows: float, ctx: float, kv_bytes: int = 2,
                  common: float = 0.0) -> float:
    s = dims(model)
    int8, small = layer_params(model)
    kv = kv_positions(model, rows, ctx, common) * 2 * s["nkv"] * s["hd"] * kv_bytes
    return (int8 + s["V"] * s["d"]) * weight_bytes + small * 2 + kv + state_bytes(model, rows)


def forward_flops(model: dict, rows: float, positions: float, ctx: float) -> float:
    """``positions``: the forward's REAL positions, all rows together."""
    s = dims(model)
    int8, small = layer_params(model)
    attn = kv_positions(model, 1, ctx) * 4 * s["nq"] * s["hd"]
    scan = s["n_ssm"] * s["di"] * s["ds"] * 9
    return positions * (2 * (int8 + small) + attn + scan) + rows * 2 * s["V"] * s["d"]


def forward_floor_s(model: dict, peaks: dict, weight_bytes: int, rows: float,
                    positions: float, ctx: float, common: float = 0.0) -> tuple[float, str]:
    """Least seconds one hybrid decode forward can take on this chip, and
    which roof sets it."""
    t_b = forward_bytes(model, weight_bytes, rows, ctx, common=common) / peaks["bytes_per_s"]
    t_f = forward_flops(model, rows, positions, ctx) / peaks["flops_per_s"]
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")


def scan_floor_s(model: dict, peaks: dict, rows: float) -> float:
    return state_bytes(model, rows) / peaks["bytes_per_s"]
