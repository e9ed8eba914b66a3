"""Plain reference: the Command A+ (``model_type`` ``cohere2_moe``) decoder's
forward pass in straightforward ``jax.numpy``, float32,
``default_matmul_precision("highest")`` — no kernels, no cache, no batching,
no dispatch, one layer at a time so that it fits beside the served one.

Equations (the published ``config.json``'s keys; ``use_parallel_block``,
``layer_types``, ``expert_selection_fn``, ``num_shared_experts``,
``shared_expert_combination_strategy``, ``position_embedding_type``). For
block input ``x`` (T, d), with u = LayerNorm(x) (a gain, NO bias, eps
``layer_norm_eps``) the ONE norm of the block:

    q = Wq u, k = Wk u, v = Wv u        n_heads / n_kv_heads heads of head_dim
    sliding layer:  q, k rotated in INTERLEAVED pairs (x[2i], x[2i+1]) by
                    pos * theta^(-2i/hd)  (``rope_gptj``); query i sees keys
                    i - window < j <= i
    full layer:     nothing rotated (no positions at all); sees every j <= i
    a = Wo softmax(q k^T / sqrt(hd)) v
    s = sigmoid(Wr u)                   (T, E), each expert's logit alone
    T8 = the top-k experts of s;  g_e = s_e / sum_{j in T8} s_j
    Exp(u; W) = Wdown (silu(Wgate u) * (Wup u))
    m = sum_{e in T8} g_e Exp(u; W_e)  +  (1 / n_shared) sum_i Exp(u; S_i)
    x' = x + a + m                      BOTH halves on the same residual
    logits = logit_scale * LayerNorm(x_L) embed^T

THE CHIP'S SHARE (the guide's usual cut): the parameter tree holds the expert
planes of ``num_experts`` experts, ids ``first_expert`` onward, of the
``num_experts_published`` the router scores. The first sum then runs over
T8 ∩ held with g_e unchanged — normalised over all k chosen, wherever they
live — and that partial result goes on to the next layer. Given all the
experts (held = published) this file is the uncut model.

Every held expert is computed on every token and weighted by its gate or by
zero: plain, exact, free of any capacity, sort or dispatch order. The four
shared experts lie side by side in ``shared_*`` (n_shared * f columns); they
are taken apart here and their MEAN is added. ``dense`` (int8 leaves
dequantised, the int4 control) and ``pad_len`` are ``reference/decoder.py``'s;
the norm, the rotation, the window mask, the router, the shared experts, the
layer loop over kinds and the tied head are this file's own.

Departures, each deliberate:
- the head multiplies by the SERVED int8 copy of the embedding, dequantised
  (``lm_head``: int8 is the configuration's weight precision), where the
  published model multiplies by the embedding itself; the lookup reads the
  bf16 embedding on both sides.
- the layer pattern is derived from ``layer_switch`` and
  ``order_of_interleaved_layers`` ("local_attn_first": every
  ``layer_switch``-th layer is full, sliding ones first), the scalars the
  harness hands over; the builder checks it against ``layer_types``.
- the vision tower is no part of the language model's ``config.json`` entry
  the catalog carries, and is left out.

What this module owes the comparison (``lib/refcheck.py``; README.md "What a
reference module owes"): ``SAMPLE``, ``TOLERANCE``, ``CONTROL`` and
``logits`` at the end of the file.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import decoder as dense_ref

F32 = jnp.float32


def layer_norm(x, g, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g


def rope_pairs(x, pos, theta):
    """x (T, H, hd), pos (T,) -> rotated, pairs (2i, 2i + 1)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = pos.astype(F32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * c - odd * s, odd * c + even * s], axis=-1).reshape(x.shape)


def layer_is_sliding(li: int, model: dict) -> bool:
    """``local_attn_first``: layers 0 .. switch-2 of every period slide, the
    last of it is full."""
    if model.get("order_of_interleaved_layers", "local_attn_first") != "local_attn_first":
        raise ValueError("only the published order of layers is written down here")
    return (li + 1) % int(model["layer_switch"]) != 0


def gates_of(u, router, top_k: int):
    """(T, E) float32: g_e over the k chosen, zero elsewhere."""
    s = jax.nn.sigmoid(u @ router.astype(F32))  # the router is never quantised
    top, chosen = jax.lax.top_k(s, top_k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    return jnp.zeros_like(s).at[jnp.arange(u.shape[0])[:, None], chosen].set(top)


def swiglu(u, gate, up, down):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def routed_part(u, w, dense, *, top_k: int, first: int):
    """sum over the HELD experts of g_e Exp(u; W_e): (T, d)."""
    held = w["moe_gate"]["q"].shape[0] if isinstance(w["moe_gate"], dict) else w["moe_gate"].shape[0]
    gates = gates_of(u, w["router"], top_k)[:, first:first + held]

    def expert(acc, we):  # one at a time: three 67 MB planes in float32
        g, up, dn, gate = we
        return acc + gate[:, None] * swiglu(u, dense(g), dense(up), dense(dn)), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(u),
                          (w["moe_gate"], w["moe_up"], w["moe_down"], gates.T))
    return out


def shared_part(u, w, dense, *, n_shared: int):
    """The MEAN of the shared experts' outputs, each computed alone."""
    gate, up, down = (dense(w[k]) for k in ("shared_gate", "shared_up", "shared_down"))
    f = gate.shape[1] // n_shared
    outs = [swiglu(u, gate[:, i * f:(i + 1) * f], up[:, i * f:(i + 1) * f], down[i * f:(i + 1) * f])
            for i in range(n_shared)]
    return sum(outs) / n_shared


def attention_part(u, pos, w, dense, *, nq, nkv, theta, window, sliding: bool):
    """Wo . Attn(...) over a whole sequence of normed inputs u (T, d)."""
    T = u.shape[0]
    q = (u @ dense(w["wq"])).reshape(T, nq, -1)
    k = (u @ dense(w["wk"])).reshape(T, nkv, -1)
    v = (u @ dense(w["wv"])).reshape(T, nkv, -1)
    hd = q.shape[-1]
    if sliding:
        q, k = rope_pairs(q, pos, theta), rope_pairs(k, pos, theta)
    i, j = pos[:, None], pos[None, :]
    visible = (j <= i) & (j > i - window) if sliding else (j <= i)

    def one_kv_head(qkv):  # a K/V head at a time: 16 x T x T scores, not 128
        qh, kh, vh = qkv  # (T, g, hd), (T, hd), (T, hd)
        scores = jnp.einsum("tgh,sh->gts", qh, kh) * hd ** -0.5
        scores = jnp.where(visible[None], scores, -jnp.inf)
        return jnp.einsum("gts,sh->tgh", jax.nn.softmax(scores, axis=-1), vh)

    attn = jax.lax.map(one_kv_head, (q.reshape(T, nkv, nq // nkv, hd).transpose(1, 0, 2, 3),
                                     k.transpose(1, 0, 2), v.transpose(1, 0, 2)))  # (nkv, T, g, hd)
    attn = attn.transpose(1, 0, 2, 3).reshape(T, nq * hd)
    return attn @ dense(w["wo"])


@partial(jax.jit, static_argnames=("nq", "nkv", "eps", "theta", "window", "sliding", "top_k",
                                   "first", "n_shared", "fake_bits"))
def layer(x, pos, w, *, nq, nkv, eps, theta, window, sliding, top_k, first, n_shared,
          fake_bits=None):
    """One parallel block over a whole sequence x (T, d); ``w`` holds this
    layer's weights (int8 leaves are dequantised here, in float32)."""
    with jax.default_matmul_precision("highest"):
        dense = partial(dense_ref.dense, fake_bits=fake_bits)
        u = layer_norm(x, w["attn_norm"].astype(F32), eps)
        a = attention_part(u, pos, w, dense, nq=nq, nkv=nkv, theta=theta, window=window,
                           sliding=sliding)
        m = routed_part(u, w, dense, top_k=top_k, first=first)
        if n_shared:
            m = m + shared_part(u, w, dense, n_shared=n_shared)
        return x + a + m


@partial(jax.jit, static_argnames=("eps", "count", "scale"))
def head(x, start, final_norm, table, *, eps, count, scale):
    """``table``: the served int8 ``lm_head`` (d, V), or the embedding (V, d)."""
    with jax.default_matmul_precision("highest"):
        rows = layer_norm(jax.lax.dynamic_slice_in_dim(x, start, count, axis=0),
                          final_norm.astype(F32), eps)
        w = dense_ref.dense(table) if isinstance(table, dict) else table.astype(F32).T
        return scale * (rows @ w)


def model_kw(model: dict) -> dict:
    """``layer``'s sizes from the configuration's own keys."""
    return dict(nq=int(model["num_attention_heads"]), nkv=int(model["num_key_value_heads"]),
                eps=float(model["layer_norm_eps"]), theta=float(model["rope_theta"]),
                window=int(model["sliding_window"]), top_k=int(model["num_experts_per_tok"]),
                first=int(model.get("first_expert", 0)), n_shared=int(model["num_shared_experts"]))


def forward(params: dict, tokens, model: dict, *, last: int, fake_bits=None,
            pad_to: int | None = None):
    """Logits (last, V) of the final ``last`` positions of ONE sequence
    ``tokens`` (T,), full causal forward, no cache; ``params`` is the served
    tree (stacked layers; int8 leaves allowed), each layer's weights sliced
    out and dequantised inside that layer's call only. ``pad_to`` appends
    padding AFTER the sequence (causal attention cannot reach back)."""
    n = len(tokens)
    tokens = jnp.asarray(list(tokens) + [0] * max(0, (pad_to or n) - n), jnp.int32)
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = params["embed"][tokens].astype(F32)
    kw = model_kw(model)
    for li in range(int(model["num_hidden_layers"])):
        w = jax.tree.map(lambda leaf: leaf[li], params["layers"])
        x = layer(x, pos, w, sliding=layer_is_sliding(li, model), fake_bits=fake_bits, **kw)
    return head(x, jnp.int32(n - last), params["final_norm"], params.get("lm_head", params["embed"]),
                eps=kw["eps"], count=last, scale=float(model["logit_scale"]))


# ---- what the comparison reads (lib/refcheck.py) ----

SAMPLE = "paged_decoder"  # the served rows: refcheck.SAMPLERS["paged_decoder"]
CONTROL = "int4"          # the precision below the configuration's int8 weights
# Worst row's max|served - reference| / max|reference| that still counts as
# the same model. The served path holds the int8 weights exactly (``dense``
# dequantises the same q and s) and differs by bf16 activations and bf16 K/V
# through 8 parallel blocks with f32 accumulation, the Pallas attention and
# grouped-matmul kernels included. The two readings it is set from (my chip
# runs, PR 34, TPU v5e, the configuration's own weights at its served widths):
# the served engine 0.52-3.70 % of the logit range over 12 seeds
# (tools/compare_seeds.py) and 0.53-3.10 % in the cell's runs; the int4
# control 30.4-38.3 %, and it has to land ABOVE the tolerance in the same
# run. 8 % is 2.2 times the sound runs' largest and under a quarter of the
# control's smallest; at the published window (tools/window_check.py: a
# 4712-token sequence, every sliding layer masking 616 positions) the served
# path read 2.38 %, the control 24.7 % and this reference WITHOUT its window
# 10.5 %. Why a sound reading passes the dense decoder's 1.7 % (32 layers):
# the eight gates of a token are renormalised sigmoids, each about an eighth,
# so where the router, running on bf16 activations, picks another eighth
# expert on a near tie than this float32 one, an eighth of the expert layer's
# output is swapped (OLMoE's un-normalised softmax gates make the eighth pick
# a small term: 1.8 %); and the seeded q/k projections carry a gain of 1.5
# (builders/cohere2moe_stack.make_params), which multiplies the bf16 rounding
# of K in every score. Seeds without a flipped pick read 0.5-0.6 %.
TOLERANCE = 0.08


def logits(params: dict, model: dict, sample: dict, control: bool = False):
    """The reference's rows for a served sample ``{"tokens", "rows"}``; with
    ``control`` the same forward on weights re-quantised to int4. Every size
    comes from the configuration's own keys."""
    toks = sample["tokens"]
    return forward(params, toks, model, last=sample["rows"],
                   pad_to=dense_ref.pad_len(len(toks)), fake_bits=4 if control else None)
