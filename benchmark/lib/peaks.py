"""The yardstick's arithmetic: published peaks and the bytes / operations a
forward pass needs, from shapes. Copies of ``utils/costmodel.py``
(``PEAK_TABLE``, ``llm_token_flops``, ``llm_attn_flops_per_ctx``,
``decode_step_bytes``) kept here so a later change to the program cannot
change what its speed is measured against. Exact Python integers."""

from __future__ import annotations

# Published per-chip peaks keyed by the EXACT jax ``device_kind``.
# A device that is not in the table is an error, not a default.
PEAK_TABLE = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,      # bf16
        "int8_ops_per_s": 393e12,
        "bytes_per_s": 819e9,       # HBM
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


def peaks_for(kind: str) -> dict:
    if kind not in PEAK_TABLE:
        raise KeyError(f"no published peaks for device_kind {kind!r}; add it to "
                       "benchmark/lib/peaks.py PEAK_TABLE with its source")
    return PEAK_TABLE[kind]


def decoder_dims(model: dict) -> dict:
    """Shape numbers from a decoder configuration file (HF key names)."""
    d, nq = model["hidden_size"], model["num_attention_heads"]
    return {"d": d, "f": model["intermediate_size"], "nq": nq,
            "nkv": model["num_key_value_heads"], "hd": model.get("head_dim") or d // nq,
            "L": model["num_hidden_layers"], "V": model["vocab_size"]}


def matmul_params(model: dict) -> int:
    """Weights every forward streams: the per-layer matmuls and lm_head
    (the embedding is a one-row gather)."""
    s = decoder_dims(model)
    attn = s["d"] * s["nq"] * s["hd"] + 2 * s["d"] * s["nkv"] * s["hd"] + s["nq"] * s["hd"] * s["d"]
    return s["L"] * (attn + 3 * s["d"] * s["f"]) + s["V"] * s["d"]


def forward_bytes(model: dict, weight_bytes: int, rows: int, ctx: int, kv_bytes: int = 2) -> int:
    """HBM bytes ONE decode forward must read: all matmul weights once for
    the batch, plus each live row's attended K and V (bf16: 2 bytes)."""
    s = decoder_dims(model)
    kv = 2 * s["L"] * ctx * s["nkv"] * s["hd"] * kv_bytes * rows
    return matmul_params(model) * weight_bytes + kv


def forward_flops(model: dict, positions: int, ctx: int) -> int:
    """FLOPs of ``positions`` token positions at attended context ``ctx``:
    2 per MAC over the weight matmuls + 4*nq*hd per attended position."""
    s = decoder_dims(model)
    return positions * (2 * matmul_params(model) + ctx * 4 * s["nq"] * s["hd"])


def forward_floor_s(model: dict, peaks: dict, weight_bytes: int, rows: int,
                    positions_per_row: float, ctx: int) -> tuple[float, str]:
    """Least seconds one decode forward can take on this chip, and which
    roof sets it: max(bytes / HBM bandwidth, FLOPs / bf16 peak)."""
    t_b = forward_bytes(model, weight_bytes, rows, ctx) / peaks["bytes_per_s"]
    t_f = forward_flops(model, int(round(rows * positions_per_row)), ctx) / peaks["flops_per_s"]
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")
