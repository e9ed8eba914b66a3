"""Plain reference: a Mistral-7B-style decoder's forward pass in
straightforward ``jax.numpy``, float32, ``default_matmul_precision
("highest")`` — no kernels, no cache, no batching, one layer at a time so a
full-width model fits beside the served one.

Equations (Jiang et al. 2023, "Mistral 7B", and the model's reference
implementation): pre-norm residual blocks; RMSNorm; grouped-query attention
with rotary embeddings and a sliding causal window; SwiGLU feed-forward;
untied output head.

    h0 = E[tokens]
    a  = h + Wo . Attn(rope(Wq n1(h)), rope(Wk n1(h)), Wv n1(h))
    h' = a + Wd . (silu(Wg n2(a)) * (Wu n2(a)))
    logits = Whead . nf(hL)

Departures, each deliberate:
- rotary pairs are (i, i + hd/2) ("rotate-half", as the Hugging Face port
  of the weights uses) where the original pairs (2i, 2i+1); the two differ
  by a fixed permutation of Wq / Wk columns, so on seeded random weights
  they are the same model.
- the sliding window IS applied here (key j visible to query i iff
  i - window < j <= i); the program does not implement it, which is exact
  while contexts stay under the window — the comparison would catch a
  context that is not.

What this module owes the comparison (``lib/refcheck.py``; README.md "What a
reference module owes"): ``SAMPLE``, ``TOLERANCE``, ``CONTROL`` and
``logits`` at the end of the file.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32


def dense(leaf, fake_bits=None):
    """A weight as float32: a raw array, or an int8 {"q", "s"} leaf
    dequantised (q * s, per output channel). ``fake_bits`` re-quantises it
    to fewer bits first — only the comparison's negative control passes it."""
    w = (leaf["q"].astype(F32) * leaf["s"].astype(F32)) if isinstance(leaf, dict) \
        else leaf.astype(F32)
    if fake_bits is not None:
        top = 2 ** (fake_bits - 1) - 1
        s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / top
        w = jnp.clip(jnp.round(w / jnp.where(s == 0, 1.0, s)), -top, top) * s
    return w


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, pos, theta):
    """x (T, H, hd), pos (T,) -> rotated, pairs (i, i + hd/2)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = pos.astype(F32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(h, pos, w, dense, *, nq, nkv, eps, theta, window):
    """h + Wo . Attn(...) over a whole sequence h (T, d): the attention half
    of a pre-norm block, for ``layer`` here and for a reference of another
    feed-forward (called inside that reference's own jit and precision)."""
    T = h.shape[0]
    x = rms_norm(h, w["attn_norm"].astype(F32), eps)
    q = (x @ dense(w["wq"])).reshape(T, nq, -1)
    k = (x @ dense(w["wk"])).reshape(T, nkv, -1)
    v = (x @ dense(w["wv"])).reshape(T, nkv, -1)
    hd = q.shape[-1]
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    qg = q.reshape(T, nkv, nq // nkv, hd)
    scores = jnp.einsum("tkgh,skh->kgts", qg, k) * hd ** -0.5
    i, j = pos[:, None], pos[None, :]
    visible = (j <= i) & (j > i - window)
    scores = jnp.where(visible[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("kgts,skh->tkgh", probs, v).reshape(T, nq * hd)
    return h + attn @ dense(w["wo"])


@partial(jax.jit, static_argnames=("nq", "nkv", "eps", "theta", "window", "fake_bits"))
def layer(h, pos, w, *, nq, nkv, eps, theta, window, fake_bits=None):
    """One decoder block over a whole sequence h (T, d); ``w`` holds this
    layer's weights (int8 leaves are dequantised here, in float32)."""
    with jax.default_matmul_precision("highest"):
        dense = partial(globals()["dense"], fake_bits=fake_bits)
        a = attention(h, pos, w, dense, nq=nq, nkv=nkv, eps=eps, theta=theta, window=window)
        x = rms_norm(a, w["mlp_norm"].astype(F32), eps)
        act = jax.nn.silu(x @ dense(w["w_gate"])) * (x @ dense(w["w_up"]))
        return a + act @ dense(w["w_down"])


@partial(jax.jit, static_argnames=("eps", "count"))
def head(h, start, final_norm, lm_head, *, eps, count):
    with jax.default_matmul_precision("highest"):
        rows = jax.lax.dynamic_slice_in_dim(h, start, count, axis=0)
        return rms_norm(rows, final_norm.astype(F32), eps) @ dense(lm_head)


def forward(params: dict, tokens, *, n_layers, nq, nkv, eps, theta, window, last: int,
            fake_bits=None, pad_to: int | None = None, block=layer, **block_kw):
    """Logits (last, V) of the final ``last`` positions of ONE sequence
    ``tokens`` (T,), full causal forward, no cache. ``params`` is the
    served tree (stacked layers; int8 leaves allowed): each layer's weights
    are sliced out and dequantised inside that layer's call only.
    ``pad_to`` appends padding AFTER the sequence (causal attention: it
    cannot reach back) so that every prompt length shares one compiled
    shape. ``block`` is the decoder block (``layer``: the dense one); a
    reference of another block passes its own, with ``block_kw`` for it."""
    n = len(tokens)
    tokens = jnp.asarray(list(tokens) + [0] * max(0, (pad_to or n) - n), jnp.int32)
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    h = params["embed"][tokens].astype(F32)
    L = params["layers"]
    for li in range(n_layers):
        w = {k: ({"q": v["q"][li], "s": v["s"][li]} if isinstance(v, dict) else v[li])
             for k, v in L.items()}
        h = block(h, pos, w, nq=nq, nkv=nkv, eps=eps, theta=theta, window=window,
                  fake_bits=fake_bits, **block_kw)
    return head(h, jnp.int32(n - last), params["final_norm"], params["lm_head"], eps=eps, count=last)


# ---- what the comparison reads (lib/refcheck.py) ----

SAMPLE = "paged_decoder"  # the served rows: refcheck.SAMPLERS["paged_decoder"]
CONTROL = "int4"          # the precision below the configuration's int8 weights
# Worst row's max|served - reference| / max|reference| that still counts as
# the same model. The served path holds the int8 weights exactly (``dense``
# dequantises the same q and s) and differs by bf16 activations and bf16 K/V
# through 32 layers with f32 accumulation, Pallas attention included. PR 21
# measured two served paths (pallas vs xla attention) 0.9-1.6 % of the range
# apart; against this float32 forward the served path measured 1.30-1.51 %
# (PR 23), 1.41-1.56 % (PR 24); the int4 control 80-86 %, and it has to land
# ABOVE the tolerance in the same run. 3 % is twice the sound runs' largest
# and a twenty-fifth of the control's smallest (my chip runs, TPU v5e, full
# width; PERF.md section 2 has the newest readings).
TOLERANCE = 0.03


def model_kw(model: dict) -> dict:
    """``forward``'s sizes from a configuration's own keys (the source's
    ``config.json`` names, as ``builders/parse_stack.model_dims`` gives them)."""
    return dict(n_layers=int(model["num_hidden_layers"]), nq=int(model["num_attention_heads"]),
                nkv=int(model["num_key_value_heads"]), eps=float(model["rms_norm_eps"]),
                theta=float(model["rope_theta"]), window=int(model.get("sliding_window", 1 << 30)))


def pad_len(n: int) -> int:
    """One compiled shape for every prompt of a cell: whole blocks of 128
    with room for the decoded tail."""
    return -(-(n + 32) // 128) * 128


def logits(params: dict, model: dict, sample: dict, control: bool = False):
    """The reference's rows for a served sample ``{"tokens": every token fed,
    prompt first; "rows": how many final positions were read}``; with
    ``control`` the same forward on weights re-quantised to int4."""
    toks = sample["tokens"]
    return forward(params, toks, last=sample["rows"], pad_to=pad_len(len(toks)),
                   fake_bits=4 if control else None, **model_kw(model))
