"""Plain reference: the Ouro-2.6B (``model_type`` ``ouro``; arXiv:2510.25741,
"Scaling Latent Reasoning via Looped Language Models") decoder's forward pass in
straightforward ``jax.numpy``, float32, ``default_matmul_precision("highest")`` —
no kernels, no cache, no batching: ONE full causal forward over the whole
sequence, one layer at a time, every pass computed for every position.

Equations (the published ``config.json``'s keys; d = ``hidden_size``, L =
``num_hidden_layers`` layers of weights ``W_l`` that ALL passes share, U =
``total_ut_steps``; N = RMSNorm with a learned gain, eps ``rms_norm_eps``):

    h = E[tokens]
    for u in 0 .. U-1:
        for l in 0 .. L-1:
            a = N(h; g1_l)                                   input_layernorm
            q, k, v = a Wq_l, a Wk_l, a Wv_l; rotate q, k    halves-paired rotary, ``rope_theta``
            o = softmax(q k^T / sqrt(head_dim), causal) v  Wo_l
                    the keys and values are THIS pass's (a serving cache holds a
                    plane of its own for every (pass, layer): index u L + l)
            h = h + N(o; g2_l)                               input_layernorm_2: on the sub-layer's OUTPUT
            m = N(h; g3_l)                                   post_attention_layernorm
            h = h + N((silu(m Wg_l) * (m Wu_l)) Wd_l; g4_l)  post_attention_layernorm_2
        h = N(h; g_final); s_u = h                           the model's norm after EVERY pass; s_u feeds pass u+1
        lam_u = sigmoid(s_u . w_gate + b_gate)               early_exit_gate: d -> 1
    p_u = lam_u prod_{j<u}(1 - lam_j) for u < U-1;  p_{U-1} = prod_{j<U-1}(1 - lam_j)
    t = the first u with sum_{j<=u} p_j >= ``early_exit_threshold``, else U-1
    logits = s_t W_head                                      untied; s_t is normed already

The parameters are the served tree: ``layers`` stacked over L with
``attn_norm`` = g1, ``attn_post_norm`` = g2, ``mlp_norm`` = g3, ``mlp_post_norm`` =
g4; ``final_norm``; ``exit_gate`` {``w`` (d,), ``b`` ()} in float32; ``lm_head``.

``rms_norm``, ``rope``, ``dense`` (with the control's re-quantisation) and
``pad_len`` are ``reference/decoder.py``'s. Its ``attention`` adds the residual
inside and its ``head`` applies the final norm inside: this block norms the
attention's OUTPUT before the residual and its head reads a state that its pass
already normed, so both are written out here (``attention_out``, ``head``).

What this module owes the comparison (``lib/refcheck.py``): ``SAMPLE``,
``TOLERANCE``, ``CONTROL`` and ``logits`` at the end of the file.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import decoder as dense_ref

F32 = jnp.float32
rms_norm, rope = dense_ref.rms_norm, dense_ref.rope


def attention_out(a, pos, w, dense, *, nq, nkv, hd, theta):
    """softmax(q k^T / sqrt(hd), causal) v Wo over a whole sequence of normed
    inputs a (T, d): the sub-layer's output, before any norm or residual."""
    T = a.shape[0]
    q = rope((a @ dense(w["wq"])).reshape(T, nq, hd), pos, theta)
    k = rope((a @ dense(w["wk"])).reshape(T, nkv, hd), pos, theta)
    v = (a @ dense(w["wv"])).reshape(T, nkv, hd)
    scores = jnp.einsum("tkgh,skh->kgts", q.reshape(T, nkv, nq // nkv, hd), k) * hd ** -0.5
    scores = jnp.where((pos[None, :] <= pos[:, None])[None, None], scores, -jnp.inf)
    o = jnp.einsum("kgts,skh->tkgh", jax.nn.softmax(scores, axis=-1), v)
    return o.reshape(T, nq * hd) @ dense(w["wo"])


@partial(jax.jit, static_argnames=("nq", "nkv", "hd", "eps", "theta", "fake_bits"))
def layer(h, pos, w, *, nq, nkv, hd, eps, theta, fake_bits=None):
    """One block under the sandwich norm over a whole sequence h (T, d); ``w``
    holds this layer's weights (int8 leaves are dequantised here, in float32)."""
    with jax.default_matmul_precision("highest"):
        dense = partial(dense_ref.dense, fake_bits=fake_bits)
        g = lambda name: w[name].astype(F32)
        o = attention_out(rms_norm(h, g("attn_norm"), eps), pos, w, dense,
                          nq=nq, nkv=nkv, hd=hd, theta=theta)
        h = h + rms_norm(o, g("attn_post_norm"), eps)
        m = rms_norm(h, g("mlp_norm"), eps)
        y = (jax.nn.silu(m @ dense(w["w_gate"])) * (m @ dense(w["w_up"]))) @ dense(w["w_down"])
        return h + rms_norm(y, g("mlp_post_norm"), eps)


@partial(jax.jit, static_argnames=("eps",))
def close_pass(h, final_norm, gate_w, gate_b, *, eps):
    """-> (s_u = N(h; g_final), lam_u = sigmoid(s_u . w + b)), float32."""
    with jax.default_matmul_precision("highest"):
        s = rms_norm(h, final_norm.astype(F32), eps)
        return s, jax.nn.sigmoid(s @ gate_w.astype(F32) + gate_b.astype(F32))


def select(states, lams, threshold: float):
    """The published selection over U passes' states (U, T, d) and gates (U, T):
    -> (s_t (T, d), t (T,) the pass each position's logits read)."""
    U = lams.shape[0]
    left = jnp.cumprod(jnp.concatenate([jnp.ones_like(lams[:1]), 1.0 - lams[:-1]]), axis=0)
    p = jnp.concatenate([lams[:-1] * left[:-1], left[-1:]])  # the LAST pass takes what is left
    reached = jnp.cumsum(p, axis=0) >= threshold
    t = jnp.where(jnp.any(reached, axis=0), jnp.argmax(reached, axis=0), U - 1)
    return jnp.take_along_axis(states, t[None, :, None], axis=0)[0], t


@jax.jit
def head(s, lm_head):
    with jax.default_matmul_precision("highest"):
        return s @ dense_ref.dense(lm_head)


def forward(params: dict, tokens, model: dict, *, last: int, fake_bits=None,
            pad_to: int | None = None, threshold: float | None = None, picked: bool = False):
    """Logits (last, V) of the final ``last`` positions of ONE sequence
    ``tokens`` (T,): all U passes over the whole sequence, no cache; ``params``
    is the served tree, each layer's weights sliced out and dequantised inside
    that layer's call only. ``pad_to`` appends padding AFTER the sequence.
    ``threshold``: another than the configuration's (a test's); ``picked``: also
    the pass each row read, (last,) int."""
    n = len(tokens)
    tokens = jnp.asarray(list(tokens) + [0] * max(0, (pad_to or n) - n), jnp.int32)
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    kw = dict(nq=int(model["num_attention_heads"]), nkv=int(model["num_key_value_heads"]),
              hd=int(model["head_dim"]), eps=float(model["rms_norm_eps"]),
              theta=float(model["rope_theta"]))
    h = params["embed"][tokens].astype(F32)
    gate = params["exit_gate"]
    states, lams = [], []
    for _ in range(int(model["total_ut_steps"])):
        for li in range(int(model["num_hidden_layers"])):
            w = jax.tree.map(lambda leaf: leaf[li], params["layers"])
            h = layer(h, pos, w, fake_bits=fake_bits, **kw)
        h, lam = close_pass(h, params["final_norm"], gate["w"], gate["b"], eps=kw["eps"])
        states.append(h[n - last:n])
        lams.append(lam[n - last:n])
    s, t = select(jnp.stack(states), jnp.stack(lams),
                  float(model["early_exit_threshold"]) if threshold is None else threshold)
    out = head(s, params["lm_head"])
    return (out, t) if picked else out


# ---- what the comparison reads (lib/refcheck.py) ----

SAMPLE = "paged_decoder"  # the served rows: refcheck.SAMPLERS["paged_decoder"]
CONTROL = "int4"          # the precision below the configuration's int8 weights
# Worst row's max|served - reference| / max|reference| that still counts as the
# same model (PERF.md section 2 has every reading; my chip runs, PR 57, at the
# published widths, ``weights_seed`` 27, ``MIXER_GAIN`` 0.4). SOUND, over twelve of
# the comparison's samples (``tools/ouro_check.py --seeds 1,...,12``): 4.61-5.37 %
# (4.94 % in the cell's first run) — bf16 activations and K/V through 4 x 48 = 192
# layer applications: the residual stream is rounded to bf16 at each of 384
# additions (2^-9 a rounding x sqrt(384) ~ 3.8 %, which is what the smallest gains
# read: 3.1-3.6 % at ``MIXER_GAIN`` 0.15-0.3), the sub-layers' own roundings add
# with their size beside the stream (5.4 % at 0.4, 7.9 % at 0.6, 16.6 % at 1.0) —
# a Mistral forward has 64 additions and reads 1.5 %. The int4 control 113-120 %
# (its smallest row 85 %). FAULTS PLANTED in the served program at the served
# widths, the cached prefix the faulty program's too (``tools/ouro_check.py``,
# sample 1, worst row / smallest row): K/V SHARED across passes 82.9 / 66.7 %,
# three passes for four 91.1 / 74.9 %, the per-pass norm dropped 146.6 / 116.6 %,
# the output norms dropped 129.5 / 115.7 %, the selection forced to pass 0 152.0 /
# 129.6 % — every one refused. 9 % is 1.7 x the largest sound reading, a seventh
# of the smallest fault row and a ninth of the control's smallest row.
TOLERANCE = 0.09


def logits(params: dict, model: dict, sample: dict, control: bool = False):
    """The reference's rows for a served sample ``{"tokens", "rows"}``; with
    ``control`` the same forward on weights re-quantised to int4. Every size
    comes from the configuration's own keys."""
    toks = sample["tokens"]
    return forward(params, toks, model, last=sample["rows"],
                   pad_to=dense_ref.pad_len(len(toks)), fake_bits=4 if control else None)
