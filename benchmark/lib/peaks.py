"""The yardstick's arithmetic: published peaks and the bytes / operations a
forward pass needs, from shapes. Copies of ``utils/costmodel.py``
(``PEAK_TABLE``, ``llm_token_flops``, ``llm_attn_flops_per_ctx``,
``decode_step_bytes``) kept here so a later change to the program cannot
change what its speed is measured against. Exact Python integers where the
inputs are.

One principle for this file and the four ``peaks_*`` beside it (PR 42): a
floor counts the work that is NEEDED, whatever the program does with it.
``positions`` are the REAL positions of a forward (the tokens it produced:
the numerator of ``tokens_per_forward``), never ``rows x (1 + fast_forward)``
— a program that stops computing padding must not raise its own share of
the roofline; and cached positions every live row holds in common
(``common``) are read ONCE a forward, each row's own beyond them a row."""

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


def kv_positions(rows: float, ctx: float, common: float = 0.0) -> float:
    """Cached positions ONE layer's attention must read a forward: the
    ``common`` leading positions the live rows hold in common once, each
    row's own beyond them a row."""
    common = min(max(common, 0.0), ctx)
    return common + rows * (ctx - common)


def live_rows(row_blocks: float, ctx: float, block_size: int, rows: float) -> float:
    """Rows that attend in a forward, from the program's ``attn.row_blocks`` a
    forward (the blocks live rows hold up to their frontiers, one read): over
    the MOST blocks a row of context ``ctx`` can hold, so never more rows than
    were live, and never more than ``rows``, the slots occupied. A slot whose
    plan ended inside a chunk of 16 forwards is occupied and attends nothing
    for the rest of it: ``parse_flood`` holds 32 slots and ~23 live rows a
    forward. Without the counter: ``rows``."""
    return min(rows, row_blocks / (ctx / block_size + 1.0)) if row_blocks else rows


def common_positions(common_row_blocks: float, live: float, block_size: int, reads: int = 1) -> float:
    """The leading positions live rows hold in common, from the program's
    ``attn.common_row_blocks`` a forward (blocks of the common pass x its
    riders, summed over the ``reads`` of a forward that take one) over the
    ``live`` rows: fewer riders than live rows read as fewer common blocks."""
    return common_row_blocks / reads / live * block_size if live else 0.0


def forward_bytes(model: dict, weight_bytes: int, rows: float, ctx: float, kv_bytes: int = 2,
                  common: float = 0.0) -> float:
    """HBM bytes ONE decode forward must read: all matmul weights once for
    the batch, plus the attended K and V (bf16: 2 bytes) — the ``common``
    positions once, each of the ``rows`` that attend its own."""
    s = decoder_dims(model)
    kv = 2 * s["L"] * kv_positions(rows, ctx, common) * s["nkv"] * s["hd"] * kv_bytes
    return matmul_params(model) * weight_bytes + kv


def forward_flops(model: dict, positions: float, ctx: float) -> float:
    """FLOPs of ``positions`` REAL token positions at attended context
    ``ctx``: 2 per MAC over the weight matmuls + 4*nq*hd per attended position."""
    s = decoder_dims(model)
    return positions * (2 * matmul_params(model) + ctx * 4 * s["nq"] * s["hd"])


def forward_floor_s(model: dict, peaks: dict, weight_bytes: int, rows: float,
                    positions: float, ctx: float, common: float = 0.0) -> tuple[float, str]:
    """Least seconds one decode forward can take on this chip, and which
    roof sets it: max(bytes / HBM bandwidth, FLOPs / bf16 peak).
    ``positions``: the forward's real positions, all rows together."""
    t_b = forward_bytes(model, weight_bytes, rows, ctx, common=common) / peaks["bytes_per_s"]
    t_f = forward_flops(model, positions, ctx) / peaks["flops_per_s"]
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")
