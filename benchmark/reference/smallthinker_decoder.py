"""Plain reference: the SmallThinker-21BA3B-Instruct (``model_name``
``smallthinker_21b_instruct``) decoder's forward pass in straightforward
``jax.numpy``, float32, ``default_matmul_precision("highest")`` — no kernels,
no cache, no batching, no dispatch: one layer at a time and one query head at
a time, so that ~8.3 k positions fit beside the served model. Window and
causality are MASKS over T x T scores; every expert is computed on every
token, one at a time, and weighted by its gate or by zero.

Equations (the published ``config.json``'s keys; x the layer's input (T, d),
d = ``hidden_size``; arXiv 2507.20984 and the published implementation):

    r    = x W_r                     ``moe_num_primary_experts`` logits, float32:
                                     the router reads the layer's INPUT, the
                                     residual BEFORE ``input_layernorm``
    S    = the ``moe_num_active_primary_experts`` largest of r
    g    = softmax(r[S])             (``moe_primary_router_apply_softmax`` and
                                     ``norm_topk_prob``: = softmax over all the
                                     experts, renormalised over S)
    h    = RMSNorm(x; ``rms_norm_eps``)
    q, k, v = h W_q (``num_attention_heads`` x ``head_dim``), h W_k, h W_v
              (``num_key_value_heads`` x ``head_dim``); no bias, no q/k norm
    layout[l] = 1 (``rope_layout`` = ``sliding_window_layout``): q, k rotated
              (``rope_theta``, the head split in halves); a query sees its own
              position and the ``sliding_window_size`` - 1 before it
    layout[l] = 0: no rotation; a query sees every earlier position
    x1   = x + Attn(q, k, v) W_o     scale head_dim^-0.5
    h2   = RMSNorm(x1)
    x'   = x1 + sum_{e in S} g_e W_down,e (relu(W_gate,e h2) * W_up,e h2)
    logits = RMSNorm(x_L) W_head     untied

``layer_kinds`` (a letter a layer, F full | S sliding) states the two layouts
as a scalar; the parameters are the served tree (``layers`` stacked).

Departures a test or a check may ask for, each a reading of the block this
model does NOT take (``forward(**departures)``): ``router_on="ffn"`` (the
router reads h2, the usual placement), ``act="silu"``, ``rotate_full=True``,
``windowed=False``, ``renorm=False`` (the softmax over all the experts, the
chosen ones' weights as they come).

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

ACTS = {"relu": jax.nn.relu, "silu": jax.nn.silu}


def gates_of(r, top_k: int, renorm: bool = True):
    """(T, E) gates from router logits r (T, E): softmax over the ``top_k``
    largest logits (``renorm``), zero elsewhere; without ``renorm`` the
    chosen experts' softmax weights over ALL the experts as they come."""
    top, chosen = jax.lax.top_k(r, top_k)
    g = jax.nn.softmax(top, axis=-1) if renorm else jnp.take_along_axis(
        jax.nn.softmax(r, axis=-1), chosen, axis=-1)
    return jnp.zeros_like(r).at[jnp.arange(r.shape[0])[:, None], chosen].set(g)


def attention_part(h, pos, w, dense, *, nq, nkv, hd, theta, rotate, window):
    """Attn(q, k, v) W_o over a whole sequence of normed inputs h (T, d);
    ``window`` None: every earlier position."""
    T = h.shape[0]
    q = (h @ dense(w["wq"])).reshape(T, nq, hd)
    k = (h @ dense(w["wk"])).reshape(T, nkv, hd)
    v = (h @ dense(w["wv"])).reshape(T, nkv, hd)
    if rotate:
        q, k = rope(q, pos, theta), rope(k, pos, theta)
    visible = pos[None, :] <= pos[:, None]
    if window is not None:
        visible = visible & (pos[None, :] > pos[:, None] - window)
    group = nq // nkv

    def one_head(i):  # a query head at a time: T x T scores
        scores = (q[:, i] @ k[:, i // group].T) * hd ** -0.5
        return jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1) @ v[:, i // group]

    o = jax.lax.map(one_head, jnp.arange(nq)).transpose(1, 0, 2)  # (T, nq, hd)
    return o.reshape(T, nq * hd) @ dense(w["wo"])


_STATIC = ("nq", "nkv", "hd", "eps", "theta", "top_k", "rotate", "window", "fake_bits",
           "router_on", "act", "renorm")


@partial(jax.jit, static_argnames=_STATIC)
def layer(x, pos, w, *, nq, nkv, hd, eps, theta, top_k, rotate, window, fake_bits=None,
          router_on="layer", act="relu", renorm=True):
    """One block over a whole sequence x (T, d); ``w`` holds this layer's
    weights (int8 leaves are dequantised here, in float32)."""
    with jax.default_matmul_precision("highest"):
        dense = partial(dense_ref.dense, fake_bits=fake_bits)
        router = w["router"].astype(F32)  # never quantised
        h = rms_norm(x, w["attn_norm"].astype(F32), eps)
        x1 = x + attention_part(h, pos, w, dense, nq=nq, nkv=nkv, hd=hd, theta=theta,
                                rotate=rotate, window=window)
        h2 = rms_norm(x1, w["mlp_norm"].astype(F32), eps)
        gates = gates_of((x if router_on == "layer" else h2) @ router, top_k, renorm)

        def expert(acc, we):  # one at a time
            g, up, dn, gate = we
            y = (ACTS[act](h2 @ dense(g)) * (h2 @ dense(up))) @ dense(dn)
            return acc + gate[:, None] * y, None

        out, _ = jax.lax.scan(expert, jnp.zeros_like(x1),
                              (w["moe_gate"], w["moe_up"], w["moe_down"], gates.T))
        return x1 + out


def forward(params: dict, tokens, model: dict, *, last: int, fake_bits=None,
            pad_to: int | None = None, rotate_full: bool = False, windowed: bool = True,
            **departures):
    """Logits (last, V) of the final ``last`` positions of ONE sequence
    ``tokens`` (T,), full causal forward, no cache; ``params`` is the served
    tree, each layer's weights sliced out and dequantised inside that layer's
    call only. ``pad_to`` appends padding AFTER the sequence."""
    n = len(tokens)
    tokens = jnp.asarray(list(tokens) + [0] * max(0, (pad_to or n) - n), jnp.int32)
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = params["embed"][tokens].astype(F32)
    kw = dict(nq=int(model["num_attention_heads"]), nkv=int(model["num_key_value_heads"]),
              hd=int(model["head_dim"]), eps=float(model["rms_norm_eps"]),
              theta=float(model["rope_theta"]), top_k=int(model["moe_num_active_primary_experts"]))
    kinds = str(model["layer_kinds"])
    for li in range(int(model["num_hidden_layers"])):
        w = jax.tree.map(lambda leaf: leaf[li], params["layers"])
        sliding = kinds[li] == "S"
        x = layer(x, pos, w, rotate=sliding or rotate_full,
                  window=int(model["sliding_window_size"]) if sliding and windowed else None,
                  fake_bits=fake_bits, **kw, **departures)
    return dense_ref.head(x, jnp.int32(n - last), params["final_norm"], params["lm_head"],
                          eps=kw["eps"], count=last)


# ---- what the comparison reads (lib/refcheck.py) ----

SAMPLE = "paged_decoder"  # the served rows: refcheck.SAMPLERS["paged_decoder"]
CONTROL = "int4"          # the precision below the configuration's int8 weights
# Worst row's max|served - reference| / max|reference| that still counts as
# the same model (PERF.md section 2 has every reading; my chip runs, PR 50, at
# the published widths behind the 8192-token head, ``weights_seed`` 62,
# ``ROUTED_GAIN`` 0.7). SOUND, over ALL 64 samples the comparison can draw
# (``tools/compare_seeds.py``: it reads corpus text ``seed % 64``): 1.45-3.75 %,
# median 2.0 %, 31 of 64 over 2 %, the five largest 3.17 / 3.25 / 3.38 / 3.43 /
# 3.75 (2.31 / 2.51 in the cell's first two runs, each its sample's) — bf16
# activations and K/V through 24 layers, and a router that reads the bf16
# RESIDUAL (logits of standard deviation ~3: a sixth pick flips on a near tie in
# a row or two). The int4 control 29.95-31.17 % (its smallest row 23.6 %).
# FAULTS PLANTED in the served program at the served widths, the cached head
# the faulty program's too (``tools/smallthinker_check.py``, sample 1, worst row
# / smallest row): gates not renormalised 7.0 / 4.4 %, no window 12.6 / 9.0 %,
# rotation on the full layers 18.3 / 13.4 %, silu for relu 19.3 / 13.5 %, THE
# ROUTER ON h2 (the usual placement) 31.9 / 22.9 % — every one refused. 5 % is
# 1.33 x the largest sound reading, 0.71 of the smallest fault and a sixth of
# the control's smallest. No fault this block can have is named as not
# refusable; ``tests/test_smallthinker.py`` holds each in float32 all the same.
TOLERANCE = 0.05


def logits(params: dict, model: dict, sample: dict, control: bool = False):
    """The reference's rows for a served sample ``{"tokens", "rows"}``; with
    ``control`` the same forward on weights re-quantised to int4. Every size
    comes from the configuration's own keys."""
    toks = sample["tokens"]
    return forward(params, toks, model, last=sample["rows"],
                   pad_to=dense_ref.pad_len(len(toks)), fake_bits=4 if control else None)
