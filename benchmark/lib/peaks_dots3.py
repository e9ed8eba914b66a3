"""The yardstick's arithmetic for ONE CHIP'S SHARE of a ``dots3_note``
decoder (dots3-note-prev: ``benchmark/reference/dots3_decoder.py`` has the
equations): the bytes and operations one decode forward needs, from the
configuration's own keys and from what the routing, the indexer and the two
caches really did. Beside ``lib/peaks.py`` and the other ``peaks_*`` files and
never an edit of one.

- WEIGHTS every forward streams once, at the weight's bytes: each layer's
  attention planes BY KIND (a full layer W_qa d x Cq, W_qb Cq x H (dn + dr),
  W_kva d x (C + dr), W_kvb C x H (dn + dv), W_o H dv x d, the gate d x H and
  the indexer's Cq x Hi di + d x di + d x Hi; a sliding layer the same at the
  ``swa_*`` sizes without an indexer), the leading dense layers' SwiGLU, a
  routed layer's shared SwiGLU and the head over the rows this chip holds.
  The router (d x the PUBLISHED experts) stays bf16.
- EXPERT BYTES = held experts actually touched (``moe.experts_touched``) x
  3 d f; EXPERT FLOPs = the rows that fell on a held expert
  (``moe.local_rows``) x 3 x 2 d f.
- a FULL layer's attention reads the SELECTED keys, not the visible ones:
  ``attn.keys_selected`` (summed over the full layers and the real positions:
  min(position + 1, ``index_topk``) each) x (C + dr) x 2 B, and its dots are
  selected keys x H x 2 x ((C + dr) + C).
- the INDEXER scores every visible key once a position: ``attn.keys_visible``
  x Hi x di x 2 FLOPs, and reads each visible index key once a forward
  (the longest context's, ``ctx`` x di x 2 B a full layer).
- a SLIDING layer's attention reads its WINDOW only: a live row's window and
  its own positions, (``sliding_window_size`` - 1 + positions a row) keys x
  (Cs + dr) x 2 B, its dots positions x Hs x min(window, context) keys.
- the HEAD on ONE position a row; every other matmul on the forward's REAL
  positions, never on rows x (1 + W).

Exact Python integers where the inputs are."""

from __future__ import annotations


def kinds(model: dict) -> dict:
    """The attention sizes of the two kinds and how many layers of each."""
    m = model
    served = str(m["layer_kinds"])
    full = {"n": served.count("F"), "H": m["num_attention_heads"], "dn": m["qk_nope_head_dim"],
            "dr": m["qk_rope_head_dim"], "dv": m["v_head_dim"], "Cq": m["q_lora_rank"],
            "C": m["kv_lora_rank"]}
    swa = {"n": served.count("S"), "H": m["swa_num_attention_heads"],
           "dn": m["swa_qk_nope_head_dim"], "dr": m["swa_qk_rope_head_dim"],
           "dv": m["swa_v_head_dim"], "Cq": m["swa_q_lora_rank"], "C": m["swa_kv_lora_rank"]}
    return {"full": full, "sliding": swa}


def indexer_params(model: dict) -> int:
    m = model
    return (m["q_lora_rank"] * m["index_n_heads"] * m["index_head_dim"]
            + m["hidden_size"] * m["index_head_dim"] + m["hidden_size"] * m["index_n_heads"])


def attention_params(model: dict, kind: str) -> int:
    """One layer of ``kind``: its matrices, its gate, a full layer's indexer."""
    d, k = model["hidden_size"], kinds(model)[kind]
    own = (d * k["Cq"] + k["Cq"] * k["H"] * (k["dn"] + k["dr"]) + d * (k["C"] + k["dr"])
           + k["C"] * k["H"] * (k["dn"] + k["dv"]) + k["H"] * k["dv"] * d + d * k["H"])
    return own + (indexer_params(model) if kind == "full" else 0)


def expert_params(model: dict) -> int:
    """ONE routed (or the shared) expert's three planes."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def dense_params(model: dict) -> int:
    return 3 * model["hidden_size"] * model["intermediate_size"]


def layer_bytes(model: dict, weight_bytes: int, kind: str, routed: bool) -> int:
    """What ONE layer of ``kind`` holds on this chip: a routed one (the held
    experts, the shared one, the bf16 router as wide as published) or a
    leading dense one."""
    if not routed:
        return (attention_params(model, kind) + dense_params(model)) * weight_bytes
    held = model["n_routed_experts"] + model["n_shared_experts"]
    return ((attention_params(model, kind) + held * expert_params(model)) * weight_bytes
            + model["hidden_size"] * model["n_routed_experts_published"] * 2)


def cache_bytes_per_token(model: dict, cache_bytes: int = 2) -> dict:
    """A token's cache a layer of each kind, as the file states it (the
    rotated key's 64 values are a plane padded to a lane tile in HBM)."""
    k = kinds(model)
    return {"full": (k["full"]["C"] + k["full"]["dr"] + model["index_head_dim"]) * cache_bytes,
            "sliding": (k["sliding"]["C"] + k["sliding"]["dr"]) * cache_bytes}


def streamed_params(model: dict) -> tuple[int, int]:
    """(weights at the weight's bytes, bf16 weights) every forward streams
    whatever is routed."""
    k = kinds(model)
    routed = model["num_hidden_layers"] - model["first_k_dense_replace"]
    quant = (k["full"]["n"] * attention_params(model, "full")
             + k["sliding"]["n"] * attention_params(model, "sliding")
             + model["first_k_dense_replace"] * dense_params(model)
             + routed * model["n_shared_experts"] * expert_params(model)
             + model["vocab_size"] * model["hidden_size"])
    return quant, routed * model["hidden_size"] * model["n_routed_experts_published"]


def expert_bytes(model: dict, weight_bytes: int, touched: float) -> float:
    return touched * expert_params(model) * weight_bytes


def expert_flops(model: dict, local_rows: float) -> float:
    return local_rows * 2 * expert_params(model)


def selected_bytes(model: dict, keys_selected: float, cache_bytes: int = 2) -> float:
    """``keys_selected``: selected keys, summed over full layers and positions."""
    k = kinds(model)["full"]
    return keys_selected * (k["C"] + k["dr"]) * cache_bytes


def selected_flops(model: dict, keys_selected: float) -> float:
    k = kinds(model)["full"]
    return keys_selected * k["H"] * 2 * ((k["C"] + k["dr"]) + k["C"])


def indexer_bytes(model: dict, ctx: float, cache_bytes: int = 2) -> float:
    """Each visible index key once a full layer: the longest context's."""
    return kinds(model)["full"]["n"] * ctx * model["index_head_dim"] * cache_bytes


def indexer_flops(model: dict, keys_visible: float) -> float:
    """``keys_visible``: visible keys, summed over full layers and positions."""
    return keys_visible * model["index_n_heads"] * model["index_head_dim"] * 2


def window_keys(model: dict, rows: float, positions: float, ctx: float) -> float:
    """Cached positions the sliding layers of one forward must read: a live
    row's window and its own positions, as far as its context reaches."""
    if not rows:
        return 0.0
    span = min(model["sliding_window_size"] - 1 + positions / rows, ctx)
    return kinds(model)["sliding"]["n"] * rows * span


def window_bytes(model: dict, rows: float, positions: float, ctx: float, cache_bytes: int = 2) -> float:
    k = kinds(model)["sliding"]
    return window_keys(model, rows, positions, ctx) * (k["C"] + k["dr"]) * cache_bytes


def window_flops(model: dict, positions: float, ctx: float) -> float:
    k = kinds(model)["sliding"]
    seen = min(model["sliding_window_size"], ctx)
    return k["n"] * positions * k["H"] * seen * 2 * ((k["C"] + k["dr"]) + k["C"])


def forward_bytes(model: dict, weight_bytes: int, rows: float, positions: float, ctx: float,
                  touched: float, keys_selected: float) -> float:
    quant, plain = streamed_params(model)
    return (quant * weight_bytes + plain * 2 + expert_bytes(model, weight_bytes, touched)
            + selected_bytes(model, keys_selected) + indexer_bytes(model, ctx)
            + window_bytes(model, rows, positions, ctx))


def forward_flops(model: dict, rows: float, positions: float, ctx: float, local_rows: float,
                  keys_selected: float, keys_visible: float) -> float:
    """``positions`` REAL token positions through the layers and attention,
    the head on one position of each of ``rows`` rows."""
    quant, plain = streamed_params(model)
    head = model["vocab_size"] * model["hidden_size"]
    return (positions * 2 * (quant - head + plain) + rows * 2 * head
            + expert_flops(model, local_rows) + selected_flops(model, keys_selected)
            + indexer_flops(model, keys_visible) + window_flops(model, positions, ctx))


def _floor(peaks: dict, nbytes: float, flops: float) -> tuple[float, str]:
    t_b, t_f = nbytes / peaks["bytes_per_s"], flops / peaks["flops_per_s"]
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")


def forward_floor_s(model: dict, peaks: dict, weight_bytes: int, rows: float, positions: float,
                    ctx: float, touched: float, local_rows: float, keys_selected: float,
                    keys_visible: float) -> tuple[float, str]:
    """Least seconds one decode forward of the share can take on this chip,
    and which roof sets it."""
    return _floor(peaks, forward_bytes(model, weight_bytes, rows, positions, ctx, touched, keys_selected),
                  forward_flops(model, rows, positions, ctx, local_rows, keys_selected, keys_visible))


def grouped_matmul_floor_s(model: dict, peaks: dict, weight_bytes: int, touched: float,
                           local_rows: float) -> tuple[float, str]:
    return _floor(peaks, expert_bytes(model, weight_bytes, touched), expert_flops(model, local_rows))


def selected_attention_floor_s(model: dict, peaks: dict, keys_selected: float) -> tuple[float, str]:
    """The selected-attention kernel's calls of one forward: the selected
    keys' (c, r) rows once a position, or their dots."""
    return _floor(peaks, selected_bytes(model, keys_selected), selected_flops(model, keys_selected))


def indexer_floor_s(model: dict, peaks: dict, ctx: float, keys_visible: float) -> tuple[float, str]:
    return _floor(peaks, indexer_bytes(model, ctx), indexer_flops(model, keys_visible))


def window_attention_floor_s(model: dict, peaks: dict, rows: float, positions: float,
                             ctx: float) -> tuple[float, str]:
    return _floor(peaks, window_bytes(model, rows, positions, ctx), window_flops(model, positions, ctx))
