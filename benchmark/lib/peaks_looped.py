"""The yardstick's arithmetic for a LOOPED decoder — ``num_hidden_layers`` layers
of weights run ``total_ut_steps`` times a token, every (pass, layer) with K/V of
its own (``benchmark/reference/ouro_decoder.py`` has the equations): the bytes
and operations one decode forward NEEDS, from the configuration's own keys.
Beside ``lib/peaks.py`` and never an edit of it: there ONE ``num_hidden_layers``
sizes the weights read, the K/V planes and the FLOPs alike; here they part.

- WEIGHTS a forward streams: the layers' matmuls ONCE A PASS (a pass is a full
  walk of the stack: nothing of 2.47 GB stays on the chip between two), the int8
  head once. The four norms' gains a layer (bf16) and the exit gate: a pass's
  0.8 MB, counted with them.
- K/V as ``peaks.kv_positions`` counts it, for each of the ``total_ut_steps`` x
  ``num_hidden_layers`` PLANES: the positions live rows hold in common ONCE, each
  row's own a row.
- FLOPs: 2 a MAC over the per-position matmuls on the forward's REAL positions,
  every pass; 4 x heads x head_dim an attended position a plane; the head on ONE
  position a row (the selected pass's state).
- what the chip HOLDS (``held_bytes``): the layers once whatever the passes, the
  bf16 embedding, the head, and the pool's blocks at a token's bytes over ALL planes.

Exact Python integers where the inputs are."""

from __future__ import annotations

from . import peaks as pk


def dims(model: dict) -> dict:
    """``peaks.decoder_dims`` plus the passes and the planes."""
    s = dict(pk.decoder_dims(model), U=int(model["total_ut_steps"]))
    return dict(s, planes=s["U"] * s["L"])


def layer_params(model: dict) -> tuple[int, int]:
    """(int8, bf16) weights of ONE pass over the layers: q, k, v, o and the
    SwiGLU's three planes; the four norms' gains a layer."""
    s = dims(model)
    attn = 2 * s["d"] * s["nq"] * s["hd"] + 2 * s["d"] * s["nkv"] * s["hd"]
    return s["L"] * (attn + 3 * s["d"] * s["f"]), s["L"] * 4 * s["d"]


def token_bytes(model: dict, kv_bytes: int = 2) -> int:
    """Pool bytes ONE cached token takes: K and V by head in every plane."""
    s = dims(model)
    return 2 * s["planes"] * s["nkv"] * s["hd"] * kv_bytes


def held_bytes(model: dict, pool_blocks: int, block_size: int, weight_bytes: int = 1) -> dict:
    """What the chip holds, by part (scales and norms aside: under 1 %)."""
    s = dims(model)
    return {"layers": layer_params(model)[0] * weight_bytes, "embedding": s["V"] * s["d"] * 2,
            "head": s["V"] * s["d"] * weight_bytes,
            "kv": pool_blocks * block_size * token_bytes(model)}


def kv_positions(model: dict, rows: float, ctx: float, common: float = 0.0) -> float:
    """Cached positions a forward's attention must read, over all planes."""
    return dims(model)["planes"] * pk.kv_positions(rows, ctx, common)


def forward_bytes(model: dict, weight_bytes: int, rows: float, ctx: float, kv_bytes: int = 2,
                  common: float = 0.0) -> float:
    """HBM bytes ONE decode forward must read: the layers once a pass, the head
    once, the attended K and V of every plane."""
    s = dims(model)
    quant, plain = layer_params(model)
    kv = 2 * kv_positions(model, rows, ctx, common) * s["nkv"] * s["hd"] * kv_bytes
    return s["U"] * (quant * weight_bytes + plain * 2) + s["V"] * s["d"] * weight_bytes + kv


def forward_flops(model: dict, rows: float, positions: float, ctx: float) -> float:
    """``positions`` REAL token positions through every pass at attended context
    ``ctx``, the head on one position of each of ``rows`` rows."""
    s = dims(model)
    per_position = s["U"] * 2 * layer_params(model)[0] + 4 * s["nq"] * s["hd"] * kv_positions(model, 1, ctx)
    return positions * per_position + rows * 2 * s["V"] * s["d"]


def forward_floor_s(model: dict, peaks: dict, weight_bytes: int, rows: float, positions: float,
                    ctx: float, common: float = 0.0) -> tuple[float, str]:
    """Least seconds one looped decode forward can take on this chip, and which roof sets it."""
    t_b = forward_bytes(model, weight_bytes, rows, ctx, common=common) / peaks["bytes_per_s"]
    t_f = forward_flops(model, rows, positions, ctx) / peaks["flops_per_s"]
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")
