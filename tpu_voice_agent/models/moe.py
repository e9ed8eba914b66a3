"""Top-k MoE routing math shared by the served decoder and the EP layer.

Dense-dispatch routing (no data-dependent shapes — jit/MXU friendly): the
(token, expert, slot) one-hot dispatch/combine tensors turn expert selection
into einsums. Used by:

- ``models.llama`` when ``LlamaConfig.n_experts > 0`` (a served Mixtral-style
  decoder: the MoE FFN replaces the dense SwiGLU inside the layer scan)
- ``parallel.expert`` (the standalone EP shard_map layout over an ``ep``
  mesh axis)

Capacity semantics are standard Switch/GShard: each expert owns C slots;
overflow tokens lose that expert's contribution and the combine weights
renormalize over the survivors.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def moe_capacity(n_tokens: int, n_experts: int, top_k: int,
                 capacity_factor: float = 1.25) -> int:
    return max(1, int(np.ceil(n_tokens * top_k / n_experts * capacity_factor)))


def _select_topk(router_w: jax.Array, x: jax.Array, n_experts: int,
                 top_k: int, score_fn: str = "softmax",
                 bias: jax.Array | None = None) -> tuple[jax.Array, jax.Array, jax.Array]:
    """THE expert-selection rule, in one place: x (T, d), router_w (d, E)
    -> (probs (T, E) f32 scores, eids (T, K) int32 iterative-argmax picks,
    their scores (T, K)). Both dispatch layouts (dense one-hot and
    flat/grouped) derive from this, so expert choice and tie behavior can
    never drift apart. ``score_fn`` is a property of the MODEL: "softmax"
    over all experts (Mixtral, OLMoE) or "sigmoid" of each expert's logit
    alone (``expert_selection_fn: sigmoid``, cohere2_moe) — positive either
    way, which the masked argmax below relies on. ``bias`` (E,), likewise the
    MODEL's (``topk_method: noaux_tc``, deepseek_v3): the K experts with the
    largest score + bias are chosen, and each carries its score WITHOUT the
    bias (the sum may be negative: chosen ones are masked to -inf there)."""
    E, K = n_experts, top_k
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router_w.astype(jnp.float32))
    if score_fn == "sigmoid":
        probs = jax.nn.sigmoid(logits)  # (T, E)
    elif score_fn == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    else:
        raise ValueError(f"router score function {score_fn!r}: softmax or sigmoid")
    ids, vals = [], []
    if bias is not None:
        sel = probs + bias.astype(jnp.float32)[None, :]
        for _ in range(K):
            idx = jnp.argmax(sel, axis=-1)  # (T,)
            hot = jax.nn.one_hot(idx, E, dtype=bool)
            ids.append(idx.astype(jnp.int32))
            vals.append(jnp.sum(jnp.where(hot, probs, 0.0), axis=-1))
            sel = jnp.where(hot, -jnp.inf, sel)
        return probs, jnp.stack(ids, axis=1), jnp.stack(vals, axis=1)
    masked = probs
    for _ in range(K):
        idx = jnp.argmax(masked, axis=-1)  # (T,)
        ids.append(idx.astype(jnp.int32))
        vals.append(jnp.max(masked, axis=-1))  # the pick's own probability: no gather afterwards
        masked = masked * (1.0 - jax.nn.one_hot(idx, E, dtype=probs.dtype))
    return probs, jnp.stack(ids, axis=1), jnp.stack(vals, axis=1)


def route_topk_flat(router_w: jax.Array, x: jax.Array, n_experts: int,
                    top_k: int, renormalize: bool = True,
                    score_fn: str = "softmax", bias: jax.Array | None = None,
                    scale: float = 1.0) -> tuple[jax.Array, jax.Array]:
    """x (T, d), router_w (d, E) -> (eids (T, K) int32, gates (T, K) f32).
    ``renormalize`` is a property of the MODEL: Mixtral divides the K chosen
    softmax weights by their sum, OLMoE (``norm_topk_prob: false``) keeps
    them as they are. The flat (assignment-list) layout for the
    grouped-matmul dispatch path; selection comes from ``_select_topk`` so
    it is identical to the dense path by construction. ``bias`` selects
    (``_select_topk``); ``scale`` multiplies the finished gates
    (``routed_scaling_factor``)."""
    _, eids, gates = _select_topk(router_w, x, n_experts, top_k, score_fn, bias)
    if renormalize:
        denom = jnp.sum(gates, axis=1, keepdims=True)
        gates = gates / jnp.where(denom == 0.0, 1.0, denom)
    return eids, gates if scale == 1.0 else gates * scale


def route_topk(router_w: jax.Array, x: jax.Array, n_experts: int, top_k: int,
               capacity: int, renormalize: bool = True,
               score_fn: str = "softmax", bias: jax.Array | None = None,
               scale: float = 1.0) -> tuple[jax.Array, jax.Array]:
    """x (T, d), router_w (d, E) -> (dispatch (T, E, C) one-hot,
    combine (T, E, C) gate-weighted). Pure function of static E/K/C.
    ``renormalize`` as in ``route_topk_flat`` (over the experts that KEPT
    the token: an overflow changes the sum)."""
    E, K, C = n_experts, top_k, capacity
    probs, eids, _ = _select_topk(router_w, x, E, K, score_fn, bias)
    # (T, E) gate matrix from the selected ids
    gates = jnp.sum(
        jax.nn.one_hot(eids, E, dtype=probs.dtype, axis=-1) * probs[:, None, :],
        axis=1,
    )

    chosen = gates > 0.0  # (T, E) bool
    # slot position of each token within its expert's queue, in token order
    pos = jnp.cumsum(chosen.astype(jnp.int32), axis=0) - 1  # (T, E)
    keep = chosen & (pos < C)
    kept_gate = jnp.where(keep, gates, 0.0)
    if renormalize:  # over the experts that kept the token
        denom = jnp.sum(kept_gate, axis=-1, keepdims=True)
        kept_gate = kept_gate / jnp.where(denom == 0.0, 1.0, denom)

    slot_onehot = jax.nn.one_hot(jnp.where(keep, pos, C), C, dtype=probs.dtype)  # (T,E,C)
    dispatch = slot_onehot * keep[..., None]
    combine = dispatch * kept_gate[..., None]
    return dispatch, combine if scale == 1.0 else combine * scale


def dispatch_topk(eids: jax.Array, gates: jax.Array, n_experts: int,
                  capacity: int) -> tuple[jax.Array, jax.Array]:
    """The dense dispatch of picks made ELSEWHERE (a router that reads another
    tensor than the experts: ``llama._route_ahead``): eids (T, K) int32 and
    their finished gates (T, K) -> (dispatch (T, E, C) one-hot, combine
    (T, E, C) gate-weighted), slots in token order as ``route_topk`` fills
    them. The gates are used as given: serving is drop-free (``capacity``
    covers every assignment), so no renormalisation over survivors arises."""
    E, C = n_experts, capacity
    hot = jax.nn.one_hot(eids, E, dtype=gates.dtype, axis=-1)  # (T, K, E)
    chosen = jnp.sum(hot, axis=1) > 0.0  # (T, E)
    gate = jnp.sum(hot * gates[:, :, None], axis=1)  # (T, E)
    pos = jnp.cumsum(chosen.astype(jnp.int32), axis=0) - 1
    keep = chosen & (pos < C)
    dispatch = jax.nn.one_hot(jnp.where(keep, pos, C), C, dtype=gates.dtype) * keep[..., None]
    return dispatch, dispatch * jnp.where(keep, gate, 0.0)[..., None]
