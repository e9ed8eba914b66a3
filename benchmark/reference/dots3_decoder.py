"""Plain reference: the dots3-note-prev (``model_type`` ``dots3_note``)
decoder's forward pass in straightforward ``jax.numpy``, float32,
``default_matmul_precision("highest")`` — no kernels, no cache, no batching,
no dispatch, no gather of selected keys: one layer at a time and one head at
a time, so that ~8.3 k positions fit beside the served model. Attention is
DECOMPRESSED (per-head keys and values from the latent); the selection of a
full layer and the window of a sliding one are MASKS over T x T scores.

Equations (the published ``config.json``'s keys; d = ``hidden_size``, h =
RMSNorm(x; ``rms_norm_eps``)). A layer of either kind at its own sizes — a
full layer ``num_attention_heads`` H, ``qk_nope_head_dim`` dn,
``qk_rope_head_dim`` dr, ``v_head_dim`` dv, ``q_lora_rank`` Cq,
``kv_lora_rank`` C, ``rope_theta``; a sliding layer the ``swa_*`` keys:

    cq = RMSNorm(h W_qa; g_q; eps ``latent_norm_eps``) rho_q    rho_q  = (d / Cq)^0.5
    q  = cq W_qb                                                 H heads of [q_n | q_r]
    [c' | r'] = h W_kva;  c = RMSNorm(c'; g_kv) rho_kv           rho_kv = (d / C)^0.5
    r = RoPE(r'), q_r = RoPE(q_r)        pairs (x[2i], x[2i+1]); ONE r for all heads
    [k_n | v]_head = c W_kvb
    score[t, s] = (q_n . k_n + q_r . r)(dn + dr)^-0.5 where M[t, s], else -inf
    o_head = softmax(score) v;  g = sigmoid(h W_g) (H a token)
    x <- x + concat(g_head o_head) W_o

M of a sliding layer: t - (``sliding_window_size`` - 1) <= s <= t. M of a full
layer, with Hi = ``index_n_heads`` heads of di = ``index_head_dim``:

    qI = cq W_qI;  kI = LayerNorm(h W_kI; gain, eps 1e-6)   ONE key a token
    RoPE on the first dr values of each qI head and of kI
    w = h W_w Hi^-0.5 di^-0.5
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])           s <= t, else -inf
    M[t, s] = s <= t and I[t, s] among the ``index_topk`` largest of I[t, :]
              (every s <= t while t < ``index_topk``; of equal scores the earliest)

Feed-forward: ``moonlight_decoder``'s (layer 0 a SwiGLU of
``intermediate_size``; the others sigmoid scores, the ``num_experts_per_tok``
experts with the largest s + b, gates s renormalised x
``routed_scaling_factor``, the shared expert ADDED) — GIVEN THE SAME SHARE as
the served chip: the router is as wide as published, the expert planes hold
``n_routed_experts`` of them from id ``first_expert``, every HELD expert is
computed on every token and weighted by its gate or by zero, and a pick held
elsewhere adds nothing. logits = RMSNorm(x_L) W_head over the rows the chip
holds.

``layer_kinds`` (a letter a layer, F | S) says which layers are which; the
parameters are the served tree (``attn_full`` / ``attn_swa`` stacked by kind,
``dense_layers`` / ``layers`` the feed-forward halves and the norms).

What this module owes the comparison (``lib/refcheck.py``): ``SAMPLE``,
``TOLERANCE``, ``CONTROL`` and ``logits`` at the end of the file.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import decoder as dense_ref
from .moonlight_decoder import gates_of, rope_pairs, shared_part, swiglu

F32 = jnp.float32
rms_norm = dense_ref.rms_norm


def layer_norm(x, gain, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * gain


def rope_first(x, pos, theta, dr):
    """RoPE on the first ``dr`` values of x (T, H, w)."""
    return jnp.concatenate([rope_pairs(x[..., :dr], pos, theta), x[..., dr:]], axis=-1)


def index_mask(h, cq, pos, w, dense, *, Hi, di, dr, theta, topk):
    """M (T, T) of a full layer: the keys each position's indexer selects."""
    T = h.shape[0]
    qi = rope_first((cq @ dense(w["w_iq"])).reshape(T, Hi, di), pos, theta, dr)
    ki = layer_norm(h @ dense(w["w_ik"]), w["ik_norm"].astype(F32), 1e-6)
    ki = rope_first(ki[:, None, :], pos, theta, dr)[:, 0]
    wi = (h @ dense(w["w_iw"])) * (Hi ** -0.5 * di ** -0.5)  # (T, Hi)

    def head(acc, qw):  # one index head at a time: T x T
        qj, wj = qw
        return acc + wj[:, None] * jax.nn.relu(qj @ ki.T), None

    score, _ = jax.lax.scan(head, jnp.zeros((T, T), F32), (qi.transpose(1, 0, 2), wi.T))
    causal = pos[None, :] <= pos[:, None]
    score = jnp.where(causal, score, -jnp.inf)
    k = min(topk, T)
    kth = jax.lax.top_k(score, k)[0][:, -1:]
    # exactly k keys: of those that TIE at the k-th score the earliest, as
    # ``top_k`` orders equal elements (a score is exactly 0 wherever no index
    # head's dot is positive)
    above = score > kth
    ties = causal & (score == kth)
    room = k - jnp.sum(above, axis=1, keepdims=True)
    return above | (ties & (jnp.cumsum(ties, axis=1) <= room))


def attention_part(h, pos, w, dense, *, d, H, dn, dr, dv, Cq, C, theta, latent_eps, window,
                   index, rescale=True, gated=True):
    """W_o . gate . Attn over a whole sequence of normed inputs h (T, d)."""
    T = h.shape[0]
    rho = lambda rank: (d / rank) ** 0.5 if rescale else 1.0
    cq = rms_norm(h @ dense(w["w_qa"]), w["q_norm"].astype(F32), latent_eps) * rho(Cq)
    cr = h @ dense(w["w_kva"])
    c = rms_norm(cr[:, :C], w["kv_norm"].astype(F32), latent_eps) * rho(C)
    r = rope_pairs(cr[:, None, C:], pos, theta)[:, 0]  # (T, dr): one for all heads
    if index is not None:
        visible = index_mask(h, cq, pos, w, dense, dr=dr, theta=theta, **index)
    else:
        visible = (pos[None, :] <= pos[:, None]) & (pos[None, :] > pos[:, None] - window)
    w_qb = dense(w["w_qb"]).reshape(Cq, H, dn + dr).transpose(1, 0, 2)
    w_kvb = dense(w["w_kvb"]).reshape(C, H, dn + dv).transpose(1, 0, 2)

    def one_head(ws):  # a head at a time: T x T scores
        wq, wkv = ws
        q, kv = cq @ wq, c @ wkv
        qr = rope_pairs(q[:, None, dn:], pos, theta)[:, 0]
        scores = (q[:, :dn] @ kv[:, :dn].T + qr @ r.T) * (dn + dr) ** -0.5
        return jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1) @ kv[:, dn:]

    o = jax.lax.map(one_head, (w_qb, w_kvb)).transpose(1, 0, 2)  # (T, H, dv)
    if gated:
        o = o * jax.nn.sigmoid(h @ dense(w["w_hgate"]))[:, :, None]
    return o.reshape(T, H * dv) @ dense(w["wo"])


def routed_part(u, w, dense, *, top_k: int, scale: float, first: int):
    """The HELD experts' share of the routed sum: gates over the whole router."""
    gates = gates_of(u, w["router"], w["router_bias"], top_k, scale)  # (T, E published)
    held = w["moe_gate"]["q"].shape[0] if isinstance(w["moe_gate"], dict) else w["moe_gate"].shape[0]

    def expert(acc, we):  # one at a time
        g, up, dn, gate = we
        return acc + gate[:, None] * swiglu(u, dense(g), dense(up), dense(dn)), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(u),
                          (w["moe_gate"], w["moe_up"], w["moe_down"],
                           gates[:, first:first + held].T))
    return out


_STATIC = ("d", "eps", "latent_eps", "top_k", "scale", "first", "n_shared", "fake_bits", "attn",
           "rescale", "gated")


@partial(jax.jit, static_argnames=_STATIC)
def layer(x, pos, w, *, d, eps, latent_eps, top_k, scale, first, n_shared, attn, fake_bits=None,
          rescale=True, gated=True):
    """One block over a whole sequence x (T, d); ``w`` holds this layer's
    weights (int8 leaves are dequantised here, in float32), ``attn`` its
    kind's sizes as a tuple of items. A layer whose weights carry ``w_gate``
    is a leading DENSE one."""
    with jax.default_matmul_precision("highest"):
        dense = partial(dense_ref.dense, fake_bits=fake_bits)
        a = dict(attn)
        index = a.pop("index")
        index = dict(index) if index else None
        h = rms_norm(x, w["attn_norm"].astype(F32), eps)
        x = x + attention_part(h, pos, w, dense, d=d, latent_eps=latent_eps, index=index,
                               rescale=rescale, gated=gated, **a)
        u = rms_norm(x, w["mlp_norm"].astype(F32), eps)
        if "w_gate" in w:
            return x + swiglu(u, dense(w["w_gate"]), dense(w["w_up"]), dense(w["w_down"]))
        m = routed_part(u, w, dense, top_k=top_k, scale=scale, first=first)
        if n_shared:
            m = m + shared_part(u, w, dense, n_shared=n_shared)
        return x + m


def kind_sizes(model: dict) -> dict:
    """The attention sizes of the two kinds, from the configuration's keys
    (hashable: ``layer`` takes them as static arguments)."""
    m = model
    full = dict(H=int(m["num_attention_heads"]), dn=int(m["qk_nope_head_dim"]),
                dr=int(m["qk_rope_head_dim"]), dv=int(m["v_head_dim"]), Cq=int(m["q_lora_rank"]),
                C=int(m["kv_lora_rank"]), theta=float(m["rope_theta"]), window=0,
                index=tuple(dict(Hi=int(m["index_n_heads"]), di=int(m["index_head_dim"]),
                                 topk=int(m["index_topk"])).items()))
    swa = dict(H=int(m["swa_num_attention_heads"]), dn=int(m["swa_qk_nope_head_dim"]),
               dr=int(m["swa_qk_rope_head_dim"]), dv=int(m["swa_v_head_dim"]),
               Cq=int(m["swa_q_lora_rank"]), C=int(m["swa_kv_lora_rank"]),
               theta=float(m["swa_rope_theta"]), window=int(m["sliding_window_size"]), index=None)
    return {"F": tuple(full.items()), "S": tuple(swa.items())}


def forward(params: dict, tokens, model: dict, *, last: int, fake_bits=None,
            pad_to: int | None = None, **departures):
    """Logits (last, V) of the final ``last`` positions of ONE sequence
    ``tokens`` (T,), full causal forward, no cache; ``params`` is the served
    tree, each layer's weights sliced out and dequantised inside that layer's
    call only. ``pad_to`` appends padding AFTER the sequence. ``departures``
    (``rescale=False``, ``gated=False``): a reading of the block this model
    does NOT take, for the tests that tell the readings apart."""
    n = len(tokens)
    tokens = jnp.asarray(list(tokens) + [0] * max(0, (pad_to or n) - n), jnp.int32)
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = params["embed"][tokens].astype(F32)
    sizes = kind_sizes(model)
    n_dense = int(model["first_k_dense_replace"])
    kw = dict(d=int(model["hidden_size"]), eps=float(model["rms_norm_eps"]),
              latent_eps=float(model["latent_norm_eps"]), top_k=int(model["num_experts_per_tok"]),
              scale=float(model["routed_scaling_factor"]), first=int(model.get("first_expert", 0)),
              n_shared=int(model["n_shared_experts"]))
    seen = {"F": 0, "S": 0}
    kinds = str(model["layer_kinds"])
    for li in range(int(model["num_hidden_layers"])):
        kind = kinds[li]
        stack, i = (("dense_layers", li) if li < n_dense else ("layers", li - n_dense))
        w = {**jax.tree.map(lambda leaf: leaf[i], params[stack]),
             **jax.tree.map(lambda leaf: leaf[seen[kind]],
                            params["attn_full" if kind == "F" else "attn_swa"])}
        seen[kind] += 1
        x = layer(x, pos, w, attn=sizes[kind], fake_bits=fake_bits, **kw, **departures)
    return dense_ref.head(x, jnp.int32(n - last), params["final_norm"], params["lm_head"],
                          eps=kw["eps"], count=last)


# ---- what the comparison reads (lib/refcheck.py) ----

SAMPLE = "paged_decoder"  # the served rows: refcheck.SAMPLERS["paged_decoder"]
CONTROL = "int4"          # the precision below the configuration's int8 weights
# Worst row's max|served - reference| / max|reference| that still counts as
# the same model (PERF.md section 2 has every reading; my chip runs, PR 43, at
# the published widths behind the 8192-token head). SOUND, over ALL 64
# samples the comparison can draw (``tools/compare_seeds.py``, the file's
# ``weights_seed`` 67): 0.91-3.97 %, median 2.7 % — under 1 % where no pick
# flips, 2-4 % where one of a token's eight experts flips on a near tie (the
# kernels and their XLA twins alike; 0.84-5.95 % at ``weights_seed`` 75).
# FAULTS PLANTED in the served program (``tools/sparse_check.py``): experts
# chosen by the score alone 10.5 %, no selection 14.5 %, the first 2048 keys
# 14.8 %, no window 15.8 %, no rank rescale 20.1 %, no gate 28.1 %, gates not
# renormalised 41.7 %; the int4 control 19.4-20.2 %. 7 % is 1.76 x the
# largest sound reading and two thirds of the smallest fault that moves the
# logits. Gates that carry the bias read 1.4 %, inside the sound readings,
# and CANNOT be refused here (eight nearly equal gates renormalised divide
# the bias out): ``tests/test_moonlight.py`` holds that rule in float32.
TOLERANCE = 0.07


def logits(params: dict, model: dict, sample: dict, control: bool = False):
    """The reference's rows for a served sample ``{"tokens", "rows"}``; with
    ``control`` the same forward on weights re-quantised to int4. Every size
    comes from the configuration's own keys."""
    toks = sample["tokens"]
    return forward(params, toks, model, last=sample["rows"],
                   pad_to=dense_ref.pad_len(len(toks)), fake_bits=4 if control else None)
