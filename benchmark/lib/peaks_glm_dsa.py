"""The yardstick's arithmetic for ONE CHIP'S SHARE of a ``glm_moe_dsa`` decoder
(GLM-5.2: ``benchmark/reference/glm_dsa_decoder.py`` has the equations): the
bytes and operations one decode forward NEEDS, from the configuration's own
keys and from what the routing and the selection really did. Beside
``lib/peaks.py`` and the other ``peaks_*`` files and never an edit of one.

- WEIGHTS every forward streams once, at the weight's bytes: each layer's
  attention planes (W_qa d x Cq, W_qb Cq x H (dn + dr), W_kva d x (C + dr),
  W_kvb C x H (dn + dv), W_o H dv x d), an indexer (Cq x Hi di + d x di + d x
  Hi) in the layers that RUN one (``indexer_kinds``' F), the leading dense
  layers' SwiGLU, a routed layer's shared SwiGLU and the head over the rows this
  chip holds. The router (d x the PUBLISHED experts) stays bf16.
- EXPERT BYTES = held experts actually touched (``moe.experts_touched``) x
  3 d f; EXPERT FLOPs = the rows that fell on a held expert
  (``moe.local_rows``) x 3 x 2 d f.
- EVERY layer's attention reads the SELECTED keys' rows once, its own:
  ``attn.keys_selected`` (summed over ALL layers and the real positions:
  min(position + 1, ``index_topk``) each) x (C + dr) x 2 B, and its dots are
  selected keys x H x 2 x ((C + dr) + C).
- the INDEXER scores every visible key once a position IN THE LAYERS THAT RUN
  ONE: ``attn.keys_visible`` (counted over all layers) x the indexer layers'
  share x Hi x di x 2 FLOPs, and reads each visible index key once a forward
  (the longest context's, ``ctx`` x di x 2 B an indexer layer). A layer that
  reuses a selection needs nothing of this.
- the HEAD on ONE position a row; every other matmul on the forward's REAL
  positions, never on rows x (1 + W).

Exact Python integers where the inputs are."""

from __future__ import annotations

# what the two selected-latent block types count alike (the same keys of the configuration): an
# indexer's and an expert's planes, the dense SwiGLU, the held experts' bytes and FLOPs and their floor
from .peaks_dots3 import (_floor, dense_params, expert_bytes, expert_flops,  # noqa: F401
                          expert_params, grouped_matmul_floor_s, indexer_params)


def layers(model: dict) -> tuple[int, int]:
    """(layers that attend a selection, layers that make one)."""
    served = str(model["indexer_kinds"])[:model["num_hidden_layers"]]
    return len(served), served.count("F")


def attention_params(model: dict) -> int:
    """One layer's attention matrices, without an indexer."""
    m = model
    d, H, C, Cq = m["hidden_size"], m["num_attention_heads"], m["kv_lora_rank"], m["q_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    return d * Cq + Cq * H * (dn + dr) + d * (C + dr) + C * H * (dn + dv) + H * dv * d


def layer_bytes(model: dict, weight_bytes: int, indexed: bool, routed: bool) -> int:
    """What ONE layer holds on this chip: with or without an indexer, a routed
    one (the held experts, the shared one, the bf16 router as wide as
    published) or a leading dense one."""
    attn = attention_params(model) + (indexer_params(model) if indexed else 0)
    if not routed:
        return (attn + dense_params(model)) * weight_bytes
    held = model["n_routed_experts"] + model["n_shared_experts"]
    return ((attn + held * expert_params(model)) * weight_bytes
            + model["hidden_size"] * model["n_routed_experts_published"] * 2)


def cache_bytes_per_token(model: dict, cache_bytes: int = 2) -> int:
    """A token's cache over the served layers: a row [c | r] in each, an index
    key in those that run an indexer."""
    n, n_index = layers(model)
    return (n * (model["kv_lora_rank"] + model["qk_rope_head_dim"])
            + n_index * model["index_head_dim"]) * cache_bytes


def streamed_params(model: dict) -> tuple[int, int]:
    """(weights at the weight's bytes, bf16 weights) every forward streams
    whatever is routed."""
    n, n_index = layers(model)
    routed = model["num_hidden_layers"] - model["first_k_dense_replace"]
    quant = (n * attention_params(model) + n_index * indexer_params(model)
             + model["first_k_dense_replace"] * dense_params(model)
             + routed * model["n_shared_experts"] * expert_params(model)
             + model["vocab_size"] * model["hidden_size"])
    return quant, routed * model["hidden_size"] * model["n_routed_experts_published"]


def selected_bytes(model: dict, keys_selected: float, cache_bytes: int = 2) -> float:
    """``keys_selected``: selected keys, summed over ALL layers and positions."""
    return keys_selected * (model["kv_lora_rank"] + model["qk_rope_head_dim"]) * cache_bytes


def selected_flops(model: dict, keys_selected: float) -> float:
    C, dr = model["kv_lora_rank"], model["qk_rope_head_dim"]
    return keys_selected * model["num_attention_heads"] * 2 * ((C + dr) + C)


def indexer_bytes(model: dict, ctx: float, cache_bytes: int = 2) -> float:
    """Each visible index key once a layer that runs an indexer: the longest context's."""
    return layers(model)[1] * ctx * model["index_head_dim"] * cache_bytes


def indexer_flops(model: dict, keys_visible: float) -> float:
    """``keys_visible``: visible keys, summed over ALL layers and positions;
    the layers that run an indexer score their share of them."""
    n, n_index = layers(model)
    return keys_visible * n_index / n * model["index_n_heads"] * model["index_head_dim"] * 2


def forward_bytes(model: dict, weight_bytes: int, ctx: float, touched: float,
                  keys_selected: float) -> float:
    quant, plain = streamed_params(model)
    return (quant * weight_bytes + plain * 2 + expert_bytes(model, weight_bytes, touched)
            + selected_bytes(model, keys_selected) + indexer_bytes(model, ctx))


def forward_flops(model: dict, rows: float, positions: float, local_rows: float,
                  keys_selected: float, keys_visible: float) -> float:
    """``positions`` REAL token positions through the layers and attention,
    the head on one position of each of ``rows`` rows."""
    quant, plain = streamed_params(model)
    head = model["vocab_size"] * model["hidden_size"]
    return (positions * 2 * (quant - head + plain) + rows * 2 * head
            + expert_flops(model, local_rows) + selected_flops(model, keys_selected)
            + indexer_flops(model, keys_visible))


def forward_floor_s(model: dict, peaks: dict, weight_bytes: int, rows: float, positions: float,
                    ctx: float, touched: float, local_rows: float, keys_selected: float,
                    keys_visible: float) -> tuple[float, str]:
    """Least seconds one decode forward of the share can take on this chip,
    and which roof sets it."""
    return _floor(peaks, forward_bytes(model, weight_bytes, ctx, touched, keys_selected),
                  forward_flops(model, rows, positions, local_rows, keys_selected, keys_visible))


def selected_attention_floor_s(model: dict, peaks: dict, keys_selected: float) -> tuple[float, str]:
    """The selected-attention kernel's calls of one forward, all layers: the
    selected keys' [c | r] rows once a position a layer, or their dots."""
    return _floor(peaks, selected_bytes(model, keys_selected), selected_flops(model, keys_selected))


def indexer_floor_s(model: dict, peaks: dict, ctx: float, keys_visible: float) -> tuple[float, str]:
    return _floor(peaks, indexer_bytes(model, ctx), indexer_flops(model, keys_visible))
