"""Plain reference: the GLM-5.2 (``model_type`` ``glm_moe_dsa``) decoder's
forward pass in straightforward ``jax.numpy``, float32,
``default_matmul_precision("highest")`` — no kernels, no cache, no batching,
no dispatch, no gather of selected keys, nothing carried but a MASK: one
layer at a time and one head at a time, so that ~8.3 k positions fit beside
the served model. Attention is DECOMPRESSED (per-head keys and values from the
latent); a layer's selection is a mask over T x T scores, COMPUTED in the
layers ``indexer_types`` names "full" and REUSED, as it is, in the "shared"
layers behind them.

Equations (the published ``config.json``'s keys; d = ``hidden_size``, h =
RMSNorm(x; ``rms_norm_eps``); H = ``num_attention_heads``, dn =
``qk_nope_head_dim``, dr = ``qk_rope_head_dim``, dv = ``v_head_dim``, Cq =
``q_lora_rank``, C = ``kv_lora_rank``):

    cq = RMSNorm(h W_qa; g_q; eps ``latent_norm_eps``);  q = cq W_qb     H heads of [q_n | q_r]
    [c' | r'] = h W_kva;  c = RMSNorm(c'; g_kv)
    r = RoPE(r'), q_r = RoPE(q_r)        pairs (x[2i], x[2i+1]); ONE r for all heads
    [k_n | v]_head = c W_kvb             k_n dn wide, v dv wide (dv is NOT dn here)
    score[t, s] = (q_n . k_n + q_r . r)(dn + dr)^-0.5 where M_l[t, s], else -inf
    x <- x + concat(softmax(score) v) W_o

M of a "full" layer f (``dots3_decoder.index_mask``: DeepSeek-V3.2's indexer,
Hi = ``index_n_heads`` heads of di = ``index_head_dim``):

    qI = cq W_qI;  kI = LayerNorm(h W_kI; gain, eps 1e-6);  RoPE on the first dr values of both
    w = h W_w Hi^-0.5 di^-0.5;   I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])
    M_f[t, s] = s <= t and I[t, s] among the ``index_topk`` largest of I[t, :]

M of a "shared" layer l: M_l = M_f(l), f(l) the nearest "full" layer before l.
It has no indexer weights and computes no score.

Feed-forward: ``moonlight_decoder``'s (the leading ``first_k_dense_replace``
layers a SwiGLU of ``intermediate_size``; the others sigmoid scores, the
``num_experts_per_tok`` experts with the largest s + b, gates s renormalised x
``routed_scaling_factor``, the shared expert ADDED) — GIVEN THE SAME SHARE as
the served chip (``dots3_decoder.routed_part``): the router is as wide as
published, the expert planes hold ``n_routed_experts`` of them from id
``first_expert``, and a pick held elsewhere adds nothing. logits =
RMSNorm(x_L) W_head over the rows the chip holds.

``indexer_kinds`` (a letter a served layer, F full | S shared) says which
layers are which; the parameters are the served tree (``attn_full`` /
``attn_shared`` stacked by kind, ``dense_layers`` / ``layers`` the
feed-forward halves and the norms).

What this module owes the comparison (``lib/refcheck.py``): ``SAMPLE``,
``TOLERANCE``, ``CONTROL`` and ``logits`` at the end of the file.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import decoder as dense_ref
from .dots3_decoder import index_mask, routed_part
from .moonlight_decoder import rope_pairs, shared_part, swiglu

F32 = jnp.float32
rms_norm = dense_ref.rms_norm


def attention_part(h, pos, w, dense, visible, *, H, dn, dr, dv, Cq, C, theta, latent_eps, index):
    """(W_o . Attn over a whole sequence of normed inputs h (T, d), the mask it
    attended under): ``index`` (a "full" layer: the indexer's sizes) makes the
    mask from this layer's own h and cq; None (a "shared" layer) attends under
    ``visible`` as it was handed in."""
    T = h.shape[0]
    cq = rms_norm(h @ dense(w["w_qa"]), w["q_norm"].astype(F32), latent_eps)
    cr = h @ dense(w["w_kva"])
    c = rms_norm(cr[:, :C], w["kv_norm"].astype(F32), latent_eps)
    r = rope_pairs(cr[:, None, C:], pos, theta)[:, 0]  # (T, dr): one for all heads
    if index is not None:
        visible = index_mask(h, cq, pos, w, dense, dr=dr, theta=theta, **index)
    w_qb = dense(w["w_qb"]).reshape(Cq, H, dn + dr).transpose(1, 0, 2)
    w_kvb = dense(w["w_kvb"]).reshape(C, H, dn + dv).transpose(1, 0, 2)

    def one_head(ws):  # a head at a time: T x T scores
        wq, wkv = ws
        q, kv = cq @ wq, c @ wkv
        qr = rope_pairs(q[:, None, dn:], pos, theta)[:, 0]
        scores = (q[:, :dn] @ kv[:, :dn].T + qr @ r.T) * (dn + dr) ** -0.5
        return jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1) @ kv[:, dn:]

    o = jax.lax.map(one_head, (w_qb, w_kvb)).transpose(1, 0, 2)  # (T, H, dv)
    return o.reshape(T, H * dv) @ dense(w["wo"]), visible


_STATIC = ("eps", "latent_eps", "top_k", "scale", "first", "n_shared", "fake_bits", "attn", "index")


@partial(jax.jit, static_argnames=_STATIC)
def layer(x, pos, w, visible, *, eps, latent_eps, top_k, scale, first, n_shared, attn, index,
          fake_bits=None):
    """One block over a whole sequence x (T, d) -> (x, the mask its attention
    used); ``w`` holds this layer's weights (int8 leaves are dequantised here,
    in float32), ``attn`` the attention's sizes and ``index`` the indexer's (a
    tuple of items each; ``index`` None: a "shared" layer, which attends under
    ``visible``). A layer whose weights carry ``w_gate`` is a leading DENSE one."""
    with jax.default_matmul_precision("highest"):
        dense = partial(dense_ref.dense, fake_bits=fake_bits)
        h = rms_norm(x, w["attn_norm"].astype(F32), eps)
        a, visible = attention_part(h, pos, w, dense, visible, latent_eps=latent_eps,
                                    index=dict(index) if index else None, **dict(attn))
        x = x + a
        u = rms_norm(x, w["mlp_norm"].astype(F32), eps)
        if "w_gate" in w:
            return x + swiglu(u, dense(w["w_gate"]), dense(w["w_up"]), dense(w["w_down"])), visible
        m = routed_part(u, w, dense, top_k=top_k, scale=scale, first=first)
        if n_shared:
            m = m + shared_part(u, w, dense, n_shared=n_shared)
        return x + m, visible


def sizes(model: dict) -> tuple[tuple, tuple]:
    """(the attention's sizes, the indexer's), from the configuration's keys
    (hashable: ``layer`` takes them as static arguments)."""
    m = model
    attn = dict(H=int(m["num_attention_heads"]), dn=int(m["qk_nope_head_dim"]),
                dr=int(m["qk_rope_head_dim"]), dv=int(m["v_head_dim"]), Cq=int(m["q_lora_rank"]),
                C=int(m["kv_lora_rank"]), theta=float(m["rope_theta"]))
    index = dict(Hi=int(m["index_n_heads"]), di=int(m["index_head_dim"]), topk=int(m["index_topk"]))
    return tuple(attn.items()), tuple(index.items())


# what a "shared" layer attends: the nearest full layer's set (the model), or a
# reading the model does NOT take, for the tests that tell the readings apart
SHARED = ("carried", "first", "rescored", "all")
INDEX_LEAVES = ("w_iq", "w_ik", "w_iw", "ik_norm")


def forward(params: dict, tokens, model: dict, *, last: int, fake_bits=None,
            pad_to: int | None = None, shared: str = "carried"):
    """Logits (last, V) of the final ``last`` positions of ONE sequence
    ``tokens`` (T,), full causal forward, no cache; ``params`` is the served
    tree, each layer's weights sliced out and dequantised inside that layer's
    call only. ``pad_to`` appends padding AFTER the sequence. ``shared``
    (``SHARED``): "first" — every shared layer under the FIRST full layer's
    mask; "rescored" — a shared layer scores its OWN input with its full
    layer's indexer weights; "all" — it attends every earlier key."""
    if shared not in SHARED:
        raise ValueError(f"shared {shared!r}: one of {SHARED}")
    n = len(tokens)
    tokens = jnp.asarray(list(tokens) + [0] * max(0, (pad_to or n) - n), jnp.int32)
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = params["embed"][tokens].astype(F32)
    attn, index = sizes(model)
    n_dense = int(model["first_k_dense_replace"])
    kw = dict(eps=float(model["rms_norm_eps"]), latent_eps=float(model["latent_norm_eps"]),
              top_k=int(model["num_experts_per_tok"]), scale=float(model["routed_scaling_factor"]),
              first=int(model.get("first_expert", 0)), n_shared=int(model["n_shared_experts"]),
              attn=attn, fake_bits=fake_bits)
    causal = pos[None, :] <= pos[:, None]
    seen = {"F": 0, "S": 0}
    visible = first_mask = indexer = None
    for li, kind in enumerate(str(model["indexer_kinds"])[:int(model["num_hidden_layers"])]):
        stack, i = (("dense_layers", li) if li < n_dense else ("layers", li - n_dense))
        own = jax.tree.map(lambda leaf: leaf[seen[kind]],
                           params["attn_full" if kind == "F" else "attn_shared"])
        w = {**jax.tree.map(lambda leaf: leaf[i], params[stack]), **own}
        seen[kind] += 1
        if kind == "F":
            indexer = {n: own[n] for n in INDEX_LEAVES}
        rescore = kind == "S" and shared == "rescored"
        if rescore:
            w = {**w, **indexer}
        handed = {"carried": visible, "first": first_mask, "rescored": visible, "all": causal}[
            shared if kind == "S" else "carried"]
        x, visible = layer(x, pos, w, causal if handed is None else handed,
                           index=index if kind == "F" or rescore else None, **kw)
        first_mask = visible if first_mask is None else first_mask
    return dense_ref.head(x, jnp.int32(n - last), params["final_norm"], params["lm_head"],
                          eps=kw["eps"], count=last)


# ---- what the comparison reads (lib/refcheck.py) ----

SAMPLE = "paged_decoder"  # the served rows: refcheck.SAMPLERS["paged_decoder"]
CONTROL = "int4"          # the precision below the configuration's int8 weights
# Worst row's max|served - reference| / max|reference| that still counts as
# the same model (PERF.md sections 2 and 6 have every reading; my chip runs,
# PR 61, at the published widths behind the 8192-token head, ``weights_seed``
# 65). SOUND, over ALL 64 samples the comparison can draw
# (``tools/compare_seeds.py --seeds 0..63``): 0.83-2.89 %, median 0.97 % — 46
# samples under 1.2 %, 18 at 1.2-2.9 % where one of a token's eight experts
# flips on a near tie (the twelve runs of the cell read 0.87-2.10 %). FAULTS
# PLANTED in the served program (``tools/indexshare_check.py``, comparison seed
# 1): experts chosen by the score alone 6.8 %, a shared layer over every key
# 24.2 %, the value cut to 192 27.5 %, every shared layer on the FIRST full
# layer's set 29.2 %, the first 2048 keys 30.8 %, a selection read at the
# neighbour's slot 30.9 %, no selection 31.3 %, the index key unrotated 43.8 %;
# the int4 control 25.1-27.9 % (its smallest ROW 17.6 %). 5 % is 1.73 x the
# largest sound reading of all 64 and 0.73 of the smallest fault it refuses.
# TWO faults it CANNOT refuse: ``routed_scaling_factor`` dropped reads 3.6 % —
# this chip holds a sixteenth of a token's picks, so the dropped factor moves
# the worst row about as far as ONE pick that flips moves a sound one (2.9 %);
# no single limit parts them — and gates that carry the bias 1.9 % (eight
# nearly equal gates renormalised divide the bias out). Both rules are held in
# float32 on the CPU: ``tests/test_glm_dsa.py`` (the reference under
# ``routed_scaling_factor`` 1 is another model), ``tests/test_moonlight.py``.
TOLERANCE = 0.05


def logits(params: dict, model: dict, sample: dict, control: bool = False):
    """The reference's rows for a served sample ``{"tokens", "rows"}``; with
    ``control`` the same forward on weights re-quantised to int4. Every size
    comes from the configuration's own keys."""
    toks = sample["tokens"]
    return forward(params, toks, model, last=sample["rows"],
                   pad_to=dense_ref.pad_len(len(toks)), fake_bits=4 if control else None)
