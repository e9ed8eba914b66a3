"""Plain reference: the LFM2 decoder with routed experts (LFM2-8B-A1B,
``model_type`` ``lfm2_moe``) in straightforward ``jax.numpy``, float32,
``default_matmul_precision("highest")`` — no kernels, no cache, no tail carried
between calls, no packing, no batching, no dispatch; one layer at a time and,
inside a routed layer, ONE EXPERT at a time (a layer's 32 experts are 1.4 GB in
float32: computed in blocks so that a full-width model fits beside the served
one), the head a block of vocabulary rows at a time.

Equations, for a whole sequence x (T, d). A layer's mixer is its entry of
``layer_types`` (the configuration file states the list once more as
``layer_kinds``, a letter a layer — C conv, F full_attention —, because the
harness hands a reference the file's scalar keys alone); its MLP is dense for
the first ``num_dense_layers`` layers and routed behind them. Pre-norm:

    h = x + Mixer_i(RMSNorm(x; operator_norm_i))
    x = h + MLP_i(RMSNorm(h; ffn_norm_i))
    logits = RMSNorm(x_L; final gain) E^T              (the head is the embedding)

- ``conv``, gated short convolution of width L = ``conv_L_cache``, no bias, NO
  activation: [B | C | u] = z W_in, split in that order; g = B * u;
  c_t = sum_{j < L} w[j] * g_{t-L+1+j} with g = 0 before position 0;
  out = (C * c) W_out.
- ``full_attention``: q = z W_q (``num_attention_heads`` x hd), k = z W_k, v =
  z W_v (``num_key_value_heads`` x hd); q and k through an RMSNorm over EACH
  head (one gain of hd shared by the heads), THEN the half-split rotation
  (``decoder.rope``) at ``rope_theta``; a T x T causal mask; softmax at hd^-0.5.
- dense MLP: (silu(h W_1) * (h W_3)) W_2 at ``intermediate_size``.
- routed MLP: s = sigmoid(h W_r) in float32; chosen = the
  ``num_experts_per_tok`` largest of s + b (``expert_bias``: in the SELECTION
  alone); w_e = s_e / (sum of the chosen s + 1e-6) * ``routed_scaling_factor``;
  out = sum over the chosen of w_e * (silu(h W1_e) * (h W3_e)) W2_e.

This module knows the served tree's four stacks (``shortconv``, ``attn``: the
mixers in layer order of their kind; ``dense``, ``experts``: the MLPs in layer
order of theirs; ``in_proj``'s columns are B | C | u, ``wqkv``'s W_q | W_k | W_v,
``conv_w``'s rows the taps oldest first) and nothing else of the program — not
its pools, its tails, its packed rows or paired heads, its router or dispatch.

Departures from the published description, each deliberate:
- every expert runs on every position and the gate matrix (zero off the chosen)
  weights them: the sum is the chosen experts' alone, in another order.
- the 1e-6 in the gates' denominator is HERE and not in the program, whose
  shared router (``models/moe.route_topk_flat``) divides by the bare sum: 5e-7
  of a gate, a hundredth of bf16's rounding.
- the head multiplies by the SERVED int8 copy of the embedding, dequantised
  (``lm_head``: int8 is the configuration's weight precision, as
  ``cohere2moe_decoder``'s), where the tree keeps one; the bf16 embedding itself
  where it does not.

WHAT THE COMPARISON'S LIMIT CANNOT REFUSE is named beside ``TOLERANCE`` below.

What this module owes the comparison (``lib/refcheck.py``; README.md "What a
reference module owes"): ``SAMPLE``, ``TOLERANCE``, ``CONTROL`` and
``logits`` at the end of the file.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import decoder as dense_ref
from .decoder import rms_norm, rope
from .olmo_hybrid_decoder import head  # the head a block of vocabulary columns at a time

F32 = jnp.float32
MIXERS = {"C": "shortconv", "F": "attn"}  # ``layer_kinds``: a letter a layer of ``layer_types``


def short_conv(z, w, dense):
    T, d = z.shape
    K = w["conv_w"].shape[0]
    bcu = z @ dense(w["in_proj"])
    g = bcu[:, :d] * bcu[:, 2 * d:]
    gp = jnp.concatenate([jnp.zeros((K - 1, d), F32), g])
    c = sum(gp[j:j + T] * w["conv_w"][j].astype(F32) for j in range(K))
    return (bcu[:, d:2 * d] * c) @ dense(w["out_proj"])


def attention(z, pos, w, dense, *, nq, nkv, eps, theta):
    T = z.shape[0]
    qkv = z @ dense(w["wqkv"])
    hd = qkv.shape[1] // (nq + 2 * nkv)
    q = rms_norm(qkv[:, :nq * hd].reshape(T, nq, hd), w["q_norm"].astype(F32), eps)
    k = rms_norm(qkv[:, nq * hd:(nq + nkv) * hd].reshape(T, nkv, hd), w["k_norm"].astype(F32), eps)
    v = qkv[:, (nq + nkv) * hd:].reshape(T, nkv, hd)
    q, k = rope(q, pos, theta).reshape(T, nkv, nq // nkv, hd), rope(k, pos, theta)
    s = jnp.einsum("tkgh,skh->kgts", q, k) * hd ** -0.5
    i, j = pos[:, None], pos[None, :]
    p = jax.nn.softmax(jnp.where((j <= i)[None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("kgts,skh->tkgh", p, v).reshape(T, -1) @ dense(w["wo"])


def gate_matrix(h, router, bias, *, top_k, scale):
    """(T, E): w_e on the chosen experts, zero elsewhere."""
    s = jax.nn.sigmoid(h @ router.astype(F32))
    _, chosen = jax.lax.top_k(s + bias.astype(F32), top_k)
    hot = jnp.sum(jax.nn.one_hot(chosen, s.shape[1], dtype=F32), axis=1)
    picked = s * hot
    return picked / (jnp.sum(picked, axis=1, keepdims=True) + 1e-6) * scale


def routed_mlp(h, w, dense, *, top_k, scale):
    gates = gate_matrix(h, w["router"], w["router_bias"], top_k=top_k, scale=scale)

    def one(acc, ew):
        planes, g = ew
        y = (jax.nn.silu(h @ dense(planes["moe_gate"])) * (h @ dense(planes["moe_up"]))) @ dense(planes["moe_down"])
        return acc + g[:, None] * y, None

    planes = {k: w[k] for k in ("moe_gate", "moe_up", "moe_down")}
    return jax.lax.scan(one, jnp.zeros_like(h), (planes, gates.T))[0]


def dense_mlp(h, w, dense):
    return (jax.nn.silu(h @ dense(w["w_gate"])) * (h @ dense(w["w_up"]))) @ dense(w["w_down"])


@partial(jax.jit, static_argnames=("kind", "routed", "kw", "fake_bits"))
def layer(x, pos, mixer, mlp, *, kind, routed, kw, fake_bits=None):
    """One layer over a whole sequence x (T, d): one compiled program a (mixer kind, MLP kind)."""
    kw = dict(kw)
    eps = kw["eps"]
    with jax.default_matmul_precision("highest"):
        dense = partial(dense_ref.dense, fake_bits=fake_bits)
        z = rms_norm(x, mixer["operator_norm"].astype(F32), eps)
        if kind == "C":
            h = x + short_conv(z, mixer, dense)
        else:
            h = x + attention(z, pos, mixer, dense, nq=kw["nq"], nkv=kw["nkv"], eps=eps, theta=kw["theta"])
        u = rms_norm(h, mlp["ffn_norm"].astype(F32), eps)
        if routed:
            return h + routed_mlp(u, mlp, dense, top_k=kw["top_k"], scale=kw["scale"])
        return h + dense_mlp(u, mlp, dense)


def forward(params: dict, tokens, *, kinds: tuple, n_dense: int, kw: tuple, last: int, fake_bits=None,
            pad_to: int | None = None):
    """Logits (last, V) of the final ``last`` positions of ONE sequence, full
    causal forward from an empty tail. Padding goes AFTER the sequence: nothing
    here reaches back, so every prompt length shares one compiled shape."""
    n = len(tokens)
    tokens = jnp.asarray(list(tokens) + [0] * max(0, (pad_to or n) - n), jnp.int32)
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = params["embed"][tokens].astype(F32)
    seen = {k: 0 for k in MIXERS}
    for i, kind in enumerate(kinds):
        mixer = jax.tree.map(lambda a: a[seen[kind]], params[MIXERS[kind]])
        seen[kind] += 1
        routed = i >= n_dense
        mlp = jax.tree.map(lambda a: a[i - n_dense if routed else i], params["experts" if routed else "dense"])
        x = layer(x, pos, mixer, mlp, kind=kind, routed=routed, kw=kw, fake_bits=fake_bits)
    lm_head = params.get("lm_head", params["embed"].T)  # the tied embedding, where no copy is kept
    return head(x, jnp.int32(n - last), params["final_norm"], lm_head, eps=dict(kw)["eps"], count=last,
                fake_bits=fake_bits)


# ---- what the comparison reads (lib/refcheck.py) ----

SAMPLE = "paged_decoder"  # the served rows: refcheck.SAMPLERS["paged_decoder"]
CONTROL = "int4"          # the precision below the configuration's int8 weights
# Worst row's max|served - reference| / max|reference| that still counts as the
# same model. The served path holds the int8 weights exactly (``dense``
# dequantises the same q and s) and differs by bf16 activations, a bf16
# residual, bf16 K/V and convolution tails through 24 layers with float32
# accumulation, a bf16 router input (a near-tied fourth pick may flip against
# the float32 reference's) and the grouped_matmul / block attention kernels.
# THIS BLOCK AMPLIFIES A ROUNDING — the mixer is cubic in its normed input (C *
# conv(B * u)) — by how large a mixer stands beside the stream, so the limit
# belongs to the configuration's recipe (``builders/lfm2_stack.make_params`` has
# the sweep: 0.4-10 % sound across five ratios). The readings it is set from (my
# chip runs, PR 64, TPU v5e, published widths, the configuration's own weights,
# EMBED_STD 0.7 : MIXER_GAIN 0.5): SOUND — tools/shortconv_check.py on twelve seeds 1.15-1.81 %
# of the logit range (every row of a sample 0.6-1.8 %: none stands out), the six
# runs' own comparisons inside that band. NOT SOUND: the int4 control
# 33.0-34.8 % over the same twelve, its smallest ROW 24.7-25.4 % (it has to land
# ABOVE the tolerance in the same run).
# 5 % is PR 61's limit: 2.8 times the largest sound reading and a fifth of the
# control's smallest row.
# WHAT IT CANNOT REFUSE FOR CERTAIN: the planted faults of
# ``tools/shortconv_check.py`` were NOT read on the chip — its time went to the
# compiler's fault in ``lfm2.pair_q`` and to the recipe, and the one call that
# held them ran out of its hour before it reached them —, so which of them this
# limit refuses at published widths is not measured. At the rehearsal's widths
# on the CPU (same recipe) the mixer's own (``no_in_gate``, ``no_out_gate``,
# ``taps_reversed``: 144-175 %) stand far over any limit; a fault of the router's
# (``bias_in_gates``, ``no_renorm``, ``softmax_router``) moves a routed layer that
# stands at ROUTED_GAIN 0.1 beside the stream and may pass under 5 %; a tail
# taken at the block's end (``tail_at_T``) is no fault at all in the sample,
# whose forwards are told no padding. The float32 tests of ``tests/test_lfm2.py``
# hold every one of the eleven at 1e-3 of the range over ragged blocks.
TOLERANCE = 0.05


def model_kw(model: dict) -> dict:
    n = int(model["num_hidden_layers"])
    kw = dict(nq=int(model["num_attention_heads"]), nkv=int(model["num_key_value_heads"]),
              eps=float(model["norm_eps"]), theta=float(model["rope_theta"]),
              top_k=int(model["num_experts_per_tok"]), scale=float(model["routed_scaling_factor"]))
    return dict(kinds=tuple(model["layer_kinds"][:n]), n_dense=int(model["num_dense_layers"]),
                kw=tuple(sorted(kw.items())))


def logits(params: dict, model: dict, sample: dict, control: bool = False):
    """The reference's rows for a served sample ``{"tokens", "rows"}``; with
    ``control`` the same forward on weights re-quantised to int4. Every size
    comes from the configuration's own keys or the weights' own shapes."""
    toks = sample["tokens"]
    return forward(params, toks, last=sample["rows"], pad_to=dense_ref.pad_len(len(toks)),
                   fake_bits=4 if control else None, **model_kw(model))
