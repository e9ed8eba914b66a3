"""Plain reference: the OLMoE-1B-7B decoder's forward pass in
straightforward ``jax.numpy``, float32, ``default_matmul_precision
("highest")`` — no kernels, no cache, no batching, no dispatch, one layer at
a time so a full-width model fits beside the served one.

Equations (Muennighoff et al. 2024, "OLMoE: Open Mixture-of-Experts
Language Models", and the ``olmoe`` model definition that reads the
published ``config.json``). For block input ``h`` (T, d), RMSNorms ``n1``,
``n2``, ``qn``, ``kn`` of eps 1e-5:

    q = qn(Wq n1(h)),  k = kn(Wk n1(h)),  v = Wv n1(h)
        qn, kn: over the WHOLE projected vector (n_heads * head_dim wide, one
        learned gain each), BEFORE the split into heads and before RoPE
    a  = h + Wo . Attn(rope(q), rope(k), v)      causal softmax, scale hd^-0.5
    p  = softmax(Wr n2(a))                       over ALL experts, float32
    S  = the top-k experts of p;  g_e = p_e for e in S — NOT renormalised
         (``norm_topk_prob: false``; Mixtral divides by sum_{e in S} p_e)
    h' = a + sum_{e in S} g_e . Wd_e (silu(Wg_e n2(a)) * (Wu_e n2(a)))
    logits = Whead . nf(hL)                      untied head

Every expert is computed on every token and the unchosen ones are weighted
by zero: plain, exact, and free of any capacity, sort or dispatch order.
This module has its OWN attention (the q/k norm sits between the projection
and the split into heads, where ``reference/decoder.attention`` has
nothing); ``rms_norm``, ``rope``, ``head``, ``dense`` and the layer loop
``forward(block=...)`` are ``reference/decoder.py``'s.

Departures, each deliberate:
- rotary pairs are (i, i + hd/2) ("rotate-half"), as ``reference/decoder``
  and the Hugging Face port use; on seeded random weights a fixed
  permutation of Wq / Wk columns, the same model.
- ``clip_qkv`` is null in the published configuration: nothing is clipped.
- no sliding window (the architecture has none); the ``window`` argument of
  the shared layer loop is accepted and unused.
- ``norm_topk_prob`` and ``qk_norm`` are READ from the configuration, so the
  same file is the reference of a Mixtral-style model too.

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


def attention(h, pos, w, dense, *, nq, nkv, eps, theta, qk_norm):
    """h + Wo . Attn(...) over a whole sequence h (T, d), with OLMoE's norm
    on the whole q and k vectors."""
    T = h.shape[0]
    x = dense_ref.rms_norm(h, w["attn_norm"].astype(F32), eps)
    q, k, v = x @ dense(w["wq"]), x @ dense(w["wk"]), x @ dense(w["wv"])
    if qk_norm:
        q = dense_ref.rms_norm(q, w["q_norm"].astype(F32), eps)
        k = dense_ref.rms_norm(k, w["k_norm"].astype(F32), eps)
    q, k, v = q.reshape(T, nq, -1), k.reshape(T, nkv, -1), v.reshape(T, nkv, -1)
    hd = q.shape[-1]
    q, k = dense_ref.rope(q, pos, theta), dense_ref.rope(k, pos, theta)
    qg = q.reshape(T, nkv, nq // nkv, hd)
    scores = jnp.einsum("tkgh,skh->kgts", qg, k) * hd ** -0.5
    scores = jnp.where((pos[None, :] <= pos[:, None])[None, None], scores, -jnp.inf)
    attn = jnp.einsum("kgts,skh->tkgh", jax.nn.softmax(scores, axis=-1), v).reshape(T, nq * hd)
    return h + attn @ dense(w["wo"])


@partial(jax.jit, static_argnames=("nq", "nkv", "eps", "theta", "window", "top_k", "norm_topk",
                                   "qk_norm", "fake_bits"))
def layer(h, pos, w, *, nq, nkv, eps, theta, window, top_k, norm_topk, qk_norm, fake_bits=None):
    del window  # the architecture has none
    with jax.default_matmul_precision("highest"):
        dense = partial(dense_ref.dense, fake_bits=fake_bits)
        a = attention(h, pos, w, dense, nq=nq, nkv=nkv, eps=eps, theta=theta, qk_norm=qk_norm)
        x = dense_ref.rms_norm(a, w["mlp_norm"].astype(F32), eps)
        probs = jax.nn.softmax(x @ w["router"].astype(F32), axis=-1)  # (T, E); the router is never quantised
        top, chosen = jax.lax.top_k(probs, top_k)
        if norm_topk:
            top = top / jnp.sum(top, axis=-1, keepdims=True)
        gates = jnp.zeros_like(probs).at[jnp.arange(h.shape[0])[:, None], chosen].set(top)

        def expert(acc, we):  # one expert at a time: (T, E, f) at full width would be 1.4 GB a layer
            g, u, dn, gate = we
            y = (jax.nn.silu(x @ dense(g)) * (x @ dense(u))) @ dense(dn)
            return acc + gate[:, None] * y, None

        out, _ = jax.lax.scan(expert, jnp.zeros_like(a),
                              (w["moe_gate"], w["moe_up"], w["moe_down"], gates.T))
        return a + out


# ---- what the comparison reads (lib/refcheck.py) ----

SAMPLE = "paged_decoder"  # the served rows: refcheck.SAMPLERS["paged_decoder"]
CONTROL = "int4"          # the precision below the configuration's int8 weights
# Worst row's max|served - reference| / max|reference| that still counts as
# the same model. The served path holds the int8 weights exactly (``dense``
# dequantises the same q and s) and differs by bf16 activations and bf16 K/V
# through 16 layers with f32 accumulation, the Pallas attention and
# grouped-matmul kernels included — and by its ROUTER running on bf16
# activations: on a near tie it may pick another eighth expert than this
# float32 one, which with un-normalised gates swaps two nearly equal small
# terms. The two readings it is set from (my chip runs, PR 28, TPU v5e, full
# width, the configuration's own weights): the served engine 1.38-1.80 % of
# the logit range over 12 seeds (tools/compare_seeds.py) and 1.32-1.83 % in
# the cell's runs (PR 27's, a refused PR's: 1.19-1.90 %); the int4 control
# 12.6-13.3 %, and it has to land ABOVE the tolerance in the same run. 3 % is
# 1.6 times the sound runs' largest and a quarter of the control's smallest.
# What moves both readings is the seeded weights' scale, not the code (PERF.md
# section 6, PR 28): where the layers are small beside the residual stream one
# swapped expert reads 4-8 %, and the control falls with the embedding's scale
# (40 % at 1, 13 % at 3, 8 % at 4).
TOLERANCE = 0.03


def logits(params: dict, model: dict, sample: dict, control: bool = False):
    """The reference's rows for a served sample ``{"tokens", "rows"}``; with
    ``control`` the same forward on weights re-quantised to int4. Every size
    comes from the configuration's own keys."""
    toks = sample["tokens"]
    return dense_ref.forward(
        params, toks, last=sample["rows"], pad_to=dense_ref.pad_len(len(toks)),
        fake_bits=4 if control else None, block=layer,
        top_k=int(model["num_experts_per_tok"]), norm_topk=bool(model["norm_topk_prob"]),
        qk_norm=bool(model.get("qk_norm", False)), **dense_ref.model_kw(model))
