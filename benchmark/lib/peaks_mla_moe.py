"""The yardstick's arithmetic for a ``deepseek_v3`` decoder (Moonlight:
``benchmark/reference/moonlight_decoder.py`` has the equations): the bytes
and operations one decode forward needs, from the configuration's own keys
and from what the routing and the latent cache really did. Beside
``lib/peaks.py`` and the other ``peaks_*`` files and never an edit of one.

- WEIGHTS every forward streams once, at the weight's bytes: a layer's
  attention planes (W_q d x H (dn + dr), W_kva d x (C + dr), W_kvb C x H (dn +
  dv), W_o H dv x d), the leading dense layers' SwiGLU at
  ``intermediate_size``, a routed layer's shared SwiGLU (``n_shared_experts``
  x ``moe_intermediate_size`` columns) and the head (the embedding is a gather
  of a few rows). The router (d x ``n_routed_experts``) stays bf16.
- EXPERT BYTES = experts actually touched (``moe.experts_touched``, summed
  over the routed layers) x 3 x d x f — never E by assumption.
- EXPERT FLOPs = the rows the router assigned (``moe.assigned_rows``) x 3 x
  2 x d x f — not the rows the dispatch padded to.
- the LATENT CACHE by what attention read: ``attn.latent_keys_read`` cached
  positions (whole blocks, one the live rows hold in common ONCE) x (C + dr)
  x 2 B — never rows x context by assumption, and never decompressed K/V.
- ATTENTION FLOPs = query rows (REAL positions x heads, summed over layers:
  ``query_rows``) x the keys each may see x 2 x ((C + dr) + C): a score
  against the latent and the rotated key, a value from the latent. The
  program's ``attn.latent_query_rows`` counts all 1 + W positions of a live
  row, padding with them, so it says that the program counts and no more.
- the HEAD on ONE position a row (the chunk program runs it there alone);
  every other matmul on the forward's REAL positions, never on rows x (1 + W).

Exact Python integers where the inputs are."""

from __future__ import annotations


def dims(model: dict) -> dict:
    H = model["num_attention_heads"]
    return {"d": model["hidden_size"], "H": H, "dn": model["qk_nope_head_dim"],
            "dr": model["qk_rope_head_dim"], "dv": model["v_head_dim"], "C": model["kv_lora_rank"],
            "f": model["moe_intermediate_size"], "fd": model["intermediate_size"],
            "E": model["n_routed_experts"], "K": model["num_experts_per_tok"],
            "shared": model["n_shared_experts"], "L": model["num_hidden_layers"],
            "dense": model["first_k_dense_replace"], "V": model["vocab_size"]}


def attention_params(model: dict) -> int:
    """The four attention matrices of one layer."""
    s = dims(model)
    return (s["d"] * s["H"] * (s["dn"] + s["dr"]) + s["d"] * (s["C"] + s["dr"])
            + s["C"] * s["H"] * (s["dn"] + s["dv"]) + s["H"] * s["dv"] * s["d"])


def expert_params(model: dict) -> int:
    """ONE routed expert's three planes."""
    s = dims(model)
    return 3 * s["d"] * s["f"]


def layer_bytes(model: dict, weight_bytes: int, routed: bool) -> int:
    """What ONE layer holds: a routed one (all its experts, the shared
    SwiGLU, the bf16 router) or a leading dense one."""
    s = dims(model)
    if not routed:
        return (attention_params(model) + 3 * s["d"] * s["fd"]) * weight_bytes
    return ((attention_params(model) + (s["E"] + s["shared"]) * expert_params(model)) * weight_bytes
            + s["d"] * s["E"] * 2)


def cache_bytes_per_token(model: dict, cache_bytes: int = 2) -> int:
    """The latent cache of ONE token over all layers."""
    s = dims(model)
    return s["L"] * (s["C"] + s["dr"]) * cache_bytes


def streamed_params(model: dict) -> tuple[int, int]:
    """(weights at the weight's bytes, bf16 weights) every forward streams
    whatever is routed: attention, the dense layers' MLP, the shared SwiGLU,
    the head; the routers."""
    s = dims(model)
    routed = s["L"] - s["dense"]
    quant = (s["L"] * attention_params(model) + s["dense"] * 3 * s["d"] * s["fd"]
             + routed * s["shared"] * expert_params(model) + s["V"] * s["d"])
    return quant, routed * s["d"] * s["E"]


def expert_bytes(model: dict, weight_bytes: int, touched: float) -> float:
    return touched * expert_params(model) * weight_bytes


def expert_flops(model: dict, assigned_rows: float) -> float:
    return assigned_rows * 2 * expert_params(model)


def cache_read_bytes(model: dict, keys_read: float, cache_bytes: int = 2) -> float:
    """``keys_read``: cached positions read, summed over layers."""
    s = dims(model)
    return keys_read * (s["C"] + s["dr"]) * cache_bytes


def query_rows(model: dict, positions: float) -> float:
    """Query rows of ``positions`` real positions: x heads, over all layers."""
    s = dims(model)
    return positions * s["H"] * s["L"]


def attention_flops(model: dict, query_rows: float, ctx: float) -> float:
    """``query_rows``: REAL positions x heads, summed over layers."""
    s = dims(model)
    return query_rows * ctx * 2 * ((s["C"] + s["dr"]) + s["C"])


def forward_bytes(model: dict, weight_bytes: int, touched: float, keys_read: float) -> float:
    quant, plain = streamed_params(model)
    return (quant * weight_bytes + plain * 2 + expert_bytes(model, weight_bytes, touched)
            + cache_read_bytes(model, keys_read))


def forward_flops(model: dict, rows: float, positions: float, ctx: float, assigned_rows: float) -> float:
    """``positions`` REAL token positions through the layers and attention,
    the head on one position of each of ``rows`` rows."""
    s = dims(model)
    quant, plain = streamed_params(model)
    through_layers = quant - s["V"] * s["d"] + plain
    return (positions * 2 * through_layers + rows * 2 * s["V"] * s["d"]
            + expert_flops(model, assigned_rows)
            + attention_flops(model, query_rows(model, positions), ctx))


def forward_floor_s(model: dict, peaks: dict, weight_bytes: int, rows: float,
                    positions: float, ctx: float, touched: float, assigned_rows: float,
                    keys_read: float) -> tuple[float, str]:
    """Least seconds one decode forward can take on this chip, and which roof
    sets it. ``positions``: the forward's real positions, all rows together."""
    t_b = forward_bytes(model, weight_bytes, touched, keys_read) / peaks["bytes_per_s"]
    t_f = forward_flops(model, rows, positions, ctx, assigned_rows) / peaks["flops_per_s"]
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")


def grouped_matmul_floor_s(model: dict, peaks: dict, weight_bytes: int, touched: float,
                           assigned_rows: float) -> tuple[float, str]:
    """Least seconds the three ``grouped_matmul`` calls of every routed layer
    of one forward can take: the touched experts' planes over HBM bandwidth,
    or the assigned rows' FLOPs over the bf16 peak — rows ASSIGNED and planes
    TOUCHED, never the row tiles the dispatch padded to."""
    t_b = expert_bytes(model, weight_bytes, touched) / peaks["bytes_per_s"]
    t_f = expert_flops(model, assigned_rows) / peaks["flops_per_s"]
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")


def latent_attention_floor_s(model: dict, peaks: dict, keys_read: float, positions: float,
                             ctx: float) -> tuple[float, str]:
    """Least seconds the latent-attention kernel's calls of one forward can
    take: the cache it read over HBM bandwidth, or the dots of its REAL
    positions' query rows over the bf16 peak."""
    t_b = cache_read_bytes(model, keys_read) / peaks["bytes_per_s"]
    t_f = attention_flops(model, query_rows(model, positions), ctx) / peaks["flops_per_s"]
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")
