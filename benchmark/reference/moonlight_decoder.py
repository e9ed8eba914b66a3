"""Plain reference: the Moonlight-16B-A3B (``model_type`` ``deepseek_v3``)
decoder's forward pass in straightforward ``jax.numpy``, float32,
``default_matmul_precision("highest")`` — no kernels, no cache, no batching,
no dispatch, one layer at a time so that it fits beside the served one. It
computes DECOMPRESSED attention (per-head keys and values from the latent)
and knows nothing of absorption or of a latent cache.

Equations (the published ``config.json``'s keys). For block input x (T, d),
h = RMSNorm(x; eps ``rms_norm_eps``), H = ``num_attention_heads``,
dn = ``qk_nope_head_dim``, dr = ``qk_rope_head_dim``, dv = ``v_head_dim``,
C = ``kv_lora_rank`` (``q_lora_rank`` null: the query is ONE projection):

    q = h W_q                        H heads of [q_n (dn) | q_r (dr)]
    [c' (C) | r' (dr)] = h W_kva
    c = RMSNorm(c'; g_kv, eps ``latent_norm_eps``)
    r = RoPE(r'),  q_r = RoPE(q_r)   pairs (x[2i], x[2i+1]) by pos * theta^(-2i/dr);
                                     ONE r, shared by all heads
    [k_n | v]_head = c W_kvb         C -> H x (dn + dv)
    score = (q_n . k_n + q_r . r) (dn + dr)^-0.5;  causal softmax (no
                                     ``rope_scaling`` in the source: no mscale)
    o_head = sum p v;   x <- x + concat(o) W_o

    layers 0 .. ``first_k_dense_replace`` - 1:
        x <- x + W_down (silu(h' W_gate) * (h' W_up))     h' = RMSNorm(x), ``intermediate_size``
    the others:
        s = sigmoid(h' W_g)                       (T, E), float32
        T6 = the ``num_experts_per_tok`` experts with the largest s + b
             (``e_score_correction_bias``; ``n_group`` 1: the group step is void)
        g_e = s_e (WITHOUT b) for e in T6;  g /= sum g + 1e-20;  g *= ``routed_scaling_factor``
        x <- x + sum_{e in T6} g_e Exp(h'; W_e) + sum_i Exp(h'; S_i)
             the ``n_shared_experts`` shared experts ADDED, each alone

    logits = RMSNorm(x_L) W_head     (an untied head)

Every expert is computed on every token and weighted by its gate or by zero:
plain, exact, free of any capacity, sort or dispatch order. The shared experts
lie side by side in ``shared_*`` (n_shared x f columns); they are taken apart
here and each is computed alone. ``dense`` (int8 leaves dequantised, the int4
control) and ``pad_len`` are ``reference/decoder.py``'s; everything else is
this file's own.

Departures, each deliberate:
- the latent's RMSNorm uses eps 1e-6 (``latent_norm_eps`` in the
  configuration's file): the published implementation constructs it with its
  default, not with ``rms_norm_eps``. Program and reference alike.
- ``num_hidden_layers`` of the file (a cut in depth) are run: layer 0 and the
  routed layers that follow, in order.

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
rms_norm = dense_ref.rms_norm


def rope_pairs(x, pos, theta):
    """x (T, H, w), pos (T,) -> rotated, pairs (2i, 2i + 1)."""
    w = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, w, 2, dtype=F32) / w))
    ang = pos.astype(F32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * c - odd * s, odd * c + even * s], axis=-1).reshape(x.shape)


def attention_part(h, pos, w, dense, *, H, dn, dr, dv, C, theta, latent_eps):
    """W_o . Attn over a whole sequence of normed inputs h (T, d)."""
    T = h.shape[0]
    q = (h @ dense(w["wq"])).reshape(T, H, dn + dr)
    cr = h @ dense(w["w_kva"])
    c = rms_norm(cr[:, :C], w["kv_norm"].astype(F32), latent_eps)
    r = rope_pairs(cr[:, None, C:], pos, theta)[:, 0]  # (T, dr): one for all heads
    q_n, q_r = q[..., :dn], rope_pairs(q[..., dn:], pos, theta)
    kv = (c @ dense(w["w_kvb"])).reshape(T, H, dn + dv)
    k_n, v = kv[..., :dn], kv[..., dn:]
    visible = pos[None, :] <= pos[:, None]

    def one_head(qkv):  # a head at a time: T x T scores
        qn, qr, kn, vh = qkv
        scores = (qn @ kn.T + qr @ r.T) * (dn + dr) ** -0.5
        return jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1) @ vh

    heads = lambda a: a.transpose(1, 0, 2)
    o = jax.lax.map(one_head, (heads(q_n), heads(q_r), heads(k_n), heads(v)))  # (H, T, dv)
    return o.transpose(1, 0, 2).reshape(T, H * dv) @ dense(w["wo"])


def swiglu(u, gate, up, down):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def gates_of(u, router, bias, top_k: int, scale: float):
    """(T, E) float32: g_e over the k chosen BY s + b, weighted by s alone,
    renormalised and scaled; zero elsewhere."""
    s = jax.nn.sigmoid(u @ router.astype(F32))  # the router is never quantised
    _, chosen = jax.lax.top_k(s + bias.astype(F32)[None, :], top_k)
    g = jnp.take_along_axis(s, chosen, axis=-1)
    g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20) * scale
    return jnp.zeros_like(s).at[jnp.arange(u.shape[0])[:, None], chosen].set(g)


def routed_part(u, w, dense, *, top_k: int, scale: float):
    gates = gates_of(u, w["router"], w["router_bias"], top_k, scale)

    def expert(acc, we):  # one at a time
        g, up, dn, gate = we
        return acc + gate[:, None] * swiglu(u, dense(g), dense(up), dense(dn)), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(u),
                          (w["moe_gate"], w["moe_up"], w["moe_down"], gates.T))
    return out


def shared_part(u, w, dense, *, n_shared: int):
    """The SUM of the shared experts' outputs, each computed alone."""
    gate, up, down = (dense(w[k]) for k in ("shared_gate", "shared_up", "shared_down"))
    f = gate.shape[1] // n_shared
    return sum(swiglu(u, gate[:, i * f:(i + 1) * f], up[:, i * f:(i + 1) * f],
                      down[i * f:(i + 1) * f]) for i in range(n_shared))


@partial(jax.jit, static_argnames=("H", "dn", "dr", "dv", "C", "eps", "latent_eps", "theta",
                                   "top_k", "scale", "n_shared", "fake_bits"))
def layer(x, pos, w, *, H, dn, dr, dv, C, eps, latent_eps, theta, top_k, scale, n_shared,
          fake_bits=None):
    """One block over a whole sequence x (T, d); ``w`` holds this layer's
    weights (int8 leaves are dequantised here, in float32). A layer whose
    weights carry ``w_gate`` is a leading DENSE one."""
    with jax.default_matmul_precision("highest"):
        dense = partial(dense_ref.dense, fake_bits=fake_bits)
        h = rms_norm(x, w["attn_norm"].astype(F32), eps)
        x = x + attention_part(h, pos, w, dense, H=H, dn=dn, dr=dr, dv=dv, C=C, theta=theta,
                               latent_eps=latent_eps)
        u = rms_norm(x, w["mlp_norm"].astype(F32), eps)
        if "w_gate" in w:
            return x + swiglu(u, dense(w["w_gate"]), dense(w["w_up"]), dense(w["w_down"]))
        m = routed_part(u, w, dense, top_k=top_k, scale=scale)
        if n_shared:
            m = m + shared_part(u, w, dense, n_shared=n_shared)
        return x + m


def model_kw(model: dict) -> dict:
    """``layer``'s sizes from the configuration's own keys."""
    return dict(H=int(model["num_attention_heads"]), dn=int(model["qk_nope_head_dim"]),
                dr=int(model["qk_rope_head_dim"]), dv=int(model["v_head_dim"]),
                C=int(model["kv_lora_rank"]), eps=float(model["rms_norm_eps"]),
                latent_eps=float(model["latent_norm_eps"]), theta=float(model["rope_theta"]),
                top_k=int(model["num_experts_per_tok"]),
                scale=float(model["routed_scaling_factor"]),
                n_shared=int(model["n_shared_experts"]))


def forward(params: dict, tokens, model: dict, *, last: int, fake_bits=None,
            pad_to: int | None = None):
    """Logits (last, V) of the final ``last`` positions of ONE sequence
    ``tokens`` (T,), full causal forward, no cache; ``params`` is the served
    tree (``dense_layers`` then ``layers``, each stacked; int8 leaves
    allowed), each layer's weights sliced out and dequantised inside that
    layer's call only. ``pad_to`` appends padding AFTER the sequence (causal
    attention cannot reach back)."""
    n = len(tokens)
    tokens = jnp.asarray(list(tokens) + [0] * max(0, (pad_to or n) - n), jnp.int32)
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = params["embed"][tokens].astype(F32)
    kw = model_kw(model)
    n_dense = int(model["first_k_dense_replace"])
    for li in range(int(model["num_hidden_layers"])):
        stack, i = (("dense_layers", li) if li < n_dense else ("layers", li - n_dense))
        w = jax.tree.map(lambda leaf: leaf[i], params[stack])
        x = layer(x, pos, w, fake_bits=fake_bits, **kw)
    return dense_ref.head(x, jnp.int32(n - last), params["final_norm"], params["lm_head"],
                          eps=kw["eps"], count=last)


# ---- what the comparison reads (lib/refcheck.py) ----

SAMPLE = "paged_decoder"  # the served rows: refcheck.SAMPLERS["paged_decoder"]
CONTROL = "int4"          # the precision below the configuration's int8 weights
# Worst row's max|served - reference| / max|reference| that still counts as
# the same model. The served path holds the int8 weights exactly (``dense``
# dequantises the same q and s) and differs by bf16 activations, a bf16
# latent cache behind ABSORBED attention (the reference decompresses) and the
# Pallas latent-attention and grouped-matmul kernels, through 17 layers with
# f32 accumulation. The readings it is set from (my chip runs, PR 38, TPU
# v5e, the configuration's own weights at its served widths). SOUND: the
# comparison reads corpus text ``seed % 64``, so there are 64 samples in all,
# and tools/compare_seeds.py read every one: 1.73-4.38 % of the logit range
# (the cell's own runs read theirs of these). NOT SOUND: the int4 control
# 36.3-38.3 % (it has to land ABOVE the tolerance in the same run), and the
# faults of this block's own mechanisms planted in the served program
# (tools/recipe_check.py --faults, four samples): the experts chosen by the
# score alone 17.9-19.1 %, the gates without ``routed_scaling_factor``
# 10.7-11.3 %, the shared experts averaged 47.4-48.1 %. 7 % is 1.6 times the
# largest sound reading and two thirds of the smallest fault. What it CANNOT
# refuse: gates that carry the bias read 3.6-4.5 %, inside the sound readings,
# at any spread of the bias tried (renormalising six nearly equal gates
# divides it out) — tests/test_moonlight.py holds that one in float32. Why a
# sound reading passes the dense decoder's 1.7 %: a row WITHOUT a flipped pick
# reads 1.1-1.5 % (tools/recipe_check.py prints the rows), and a sixth pick
# that the bf16 router makes otherwise than this float32 one on a near tie —
# six sigmoid gates renormalised and times 2.446 put 0.41 of an expert on
# each — adds 1-3 % at that row with the experts' down projections seeded at
# 0.2 of fan_in^-0.5 (10-40 % at 1: builders/moonlight_stack.make_params).
TOLERANCE = 0.07


def logits(params: dict, model: dict, sample: dict, control: bool = False):
    """The reference's rows for a served sample ``{"tokens", "rows"}``; with
    ``control`` the same forward on weights re-quantised to int4. Every size
    comes from the configuration's own keys."""
    toks = sample["tokens"]
    return forward(params, toks, model, last=sample["rows"],
                   pad_to=dense_ref.pad_len(len(toks)), fake_bits=4 if control else None)
