"""OLMo hybrid decoder (``model_type`` ``olmo_hybrid``: Olmo-Hybrid-7B), the
served forward: Gated-DeltaNet layers with a MATRIX state a head beside
position-free full-attention layers, by the published ``layer_types`` (the
configuration's ``pattern``: ``L`` linear_attention, ``F`` full_attention —
three ``L`` and one ``F``, eight times).

Block, both kinds — the OLMo 2 / OLMo 3 REORDERED norm (ASSUMED: the
published ``config`` has no key for it), a norm on each sub-layer's OUTPUT and
none on its input:

    h = x + RMSNorm(Mixer(x))        y = h + RMSNorm(MLP(h))
    MLP(h) = (silu(h W_gate) * (h W_up)) W_down;   final RMSNorm, untied head

- ``F``, FULL ATTENTION: ``n_heads`` query heads over ``n_kv_heads`` K/V
  heads of ``head_size``; q = RMSNorm(x W_q), k = RMSNorm(x W_k) over the
  WHOLE projection (``llama.qk_norm``'s rule); NO rotary (``rope_theta``
  null: the recurrent layers carry order); causal softmax at head^-0.5; no bias.
- ``L``, GATED DELTANET (arXiv:2412.06464, with the negative-eigenvalue range
  of arXiv:2411.12537): H heads, d_k, d_v, convolution width K.

      q~ = x W_q (H d_k)   k~ = x W_k (H d_k)   v~ = x W_v (H d_v)
      each through ITS OWN causal depthwise convolution of width K, then silu
      per head:  q = q~ / ||q~||_2 * d_k^-0.5      k = k~ / ||k~||_2
      beta = 2 * sigmoid(x W_b)          (H,)  in (0, 2): ``neg_eigval``
      g    = -exp(A_log) * softplus(x W_a + dt_bias)      (H,)  log-decay <= 0
      S' = exp(g_t) S_{t-1}                      S: (d_k, d_v) a head, float32
      u  = beta_t (v_t - S'^T k_t)
      S_t = S' + k_t (x) u           o_t = S_t^T q_t
      out = [ RMSNorm_{d_v}(o_t; w) * silu(x W_g)_head ]_heads W_o   (norm, THEN gate)

  In the tree W_q | W_k | W_v | W_g are ONE leaf (``in_proj``: int8 scales are
  per output column, so the columns are the four matrices') and W_a | W_b
  another (``ab``, bf16); the three convolutions are one depthwise filter over
  the q | k | v columns (``conv_w``), which is the same thing. ASSUMED: no
  convolution bias, the l2 norm's eps 1e-6, the gate's silu.

WHAT A REQUEST HOLDS (``cache_spec``): K/V planes for the ``F`` layers alone
and, per SLOT, a convolution tail (K - 1 inputs of H (2 d_k + d_v), bf16,
side by side on the lanes: plane ``tail``) and a float32 state for each ``L`` layer (plane ``gdn``, in
``ops.gated_delta``'s dense layout: 2.21 MB a layer at the published sizes).
Pools and block tables as ``models.sambay``'s: the slot's state index one
column past a row's blocks.

MASKED ADVANCE as ``models.sambay``'s: ``n_real`` (B,) real positions a row;
state and tail advance over those alone (``ops.gated_delta_scan``: exact, a
row with none is not moved), K/V of the others is parked.

EVERYTHING POSITION-WISE OF A FAST-FORWARD BLOCK RUNS PACKED, as
``llama.forward_paged``'s two regions a layer do (``llama.FfnPack``: the real
positions gathered into ``ffn_pack`` rows where they fit — ~99 % of a flood's
forwards —, one conditional a region): the projections IN (``L``: in_proj and
a | b; ``F``: q, k, v and their norms), then, behind the convolution and scan
(or the K/V write and attention), which need a row's positions side by side,
the projection OUT with its norm and residual and the whole MLP with its. A
block no wider than ``ffn_pack`` (the compacted width, an admission) runs whole.

Layers: the leaves of a kind are STACKED on a leading axis and the layers are
unrolled, each slicing its leaves at a static index (a slice at a loop's
index is a copy of the plane: ``forward_paged`` says what it cost).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..ops.gated_delta import plane_shape
from .llama import (MAX_BLOCK_DECODE_T, _qe, _swiglu, cache_planes, conv_window, ffn_pack_index, gather_row_blocks,
                    quantize_leaf, rms_norm, rows_written, write_rows, write_walk)
from .sambay import _NO_WINDOW, StateNotCarried, _attend  # noqa: F401  (the family's error class)

F32 = jnp.float32

# what a forward counts beside the attention row-blocks: positions the states
# advanced over, positions computed, live rows x L layers (each moves its
# 2.21 MB once in and once out: the scan's floor)
HYBRID_STATS = ("gdn.positions_advanced", "gdn.positions", "gdn.state_rows_moved")

# the key that only this family's parameter tree has (``family.tree_owner``)
TREE_ROOT = "gdn"

# faults of this block's own mechanisms, planted in the served program for the
# comparison's limit to be set against (``benchmark/tools/gdn_check.py``, which
# plants one more by rebinding: a state not restored)
FAULTS = ("beta_not_doubled", "no_decay", "no_l2norm", "no_q_scale", "gate_before_norm",
          "prenorm_block", "rope_on_full", "bf16_state")


@dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int = 100352
    dim: int = 3840
    pattern: str = "LLLF" * 8  # a kind each layer: L linear_attention, F full_attention
    ffn_dim: int = 11008
    n_heads: int = 30
    n_kv_heads: int = 30
    head_size: int = 128
    gdn_heads: int = 30
    gdn_key_dim: int = 96
    gdn_value_dim: int = 192
    d_conv: int = 4
    neg_eigval: bool = True  # beta in (0, 2)
    norm_eps: float = 1e-6
    max_seq_len: int = 2048

    # what the engine reads of any model's configuration
    n_experts = 0

    def __post_init__(self):
        if set(self.pattern) - set("LF") or "L" not in self.pattern:
            raise ValueError(f"pattern {self.pattern!r}: L | F a layer, a linear layer among them")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("query heads in whole groups")

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def head_dim(self) -> int:
        return self.head_size

    @property
    def key_dim(self) -> int:
        return self.gdn_heads * self.gdn_key_dim

    @property
    def value_dim(self) -> int:
        return self.gdn_heads * self.gdn_value_dim

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_dim + self.value_dim

    @property
    def kv_heads_held(self) -> int:
        """K/V heads a pool plane holds: ``n_kv_heads`` up to whole sublane tiles.
        At the published 30 the device's own layout of a (..., 128, 30, 128)
        plane puts the heads BEFORE the block's positions (30 would pad to 32),
        and every program that scatters into it or hands it to the block kernel
        copied both pools in and out (3.1 GB beside a resident 12.8: compiled
        here for the chip, PR 54). Two heads of zeros a plane (+ 6.7 %) keep the
        plane row-major."""
        return -(-self.n_kv_heads // 8) * 8

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)


PRESETS = {
    # the published period twice; d_v = 1.5 d_k and no lane multiple, so two heads
    # stand side by side in a plane as at the published sizes
    "olmo-hybrid-test": OlmoHybridConfig(
        vocab_size=1024, dim=64, pattern="LLLF" * 2, ffn_dim=96, n_heads=4, n_kv_heads=4,
        head_size=16, gdn_heads=4, gdn_key_dim=16, gdn_value_dim=24, max_seq_len=256),
}


def cache_spec(cfg: OlmoHybridConfig) -> dict:
    """K/V planes by head for the full layers alone; a SLOT's convolution tail
    and float32 state for each linear layer (``models.family`` has the shape)."""
    kv = {"kv": (max(cfg.count("F"), 1), cfg.kv_heads_held, cfg.head_dim)}
    nl = cfg.count("L")
    return cache_planes(
        kv, kv, by_name=True,
        slot_k={"tail": ((nl, (cfg.d_conv - 1) * cfg.conv_dim), jnp.bfloat16)},
        slot_v={"gdn": ((nl, *plane_shape(cfg.gdn_heads, cfg.gdn_key_dim, cfg.gdn_value_dim)), F32)})


# ---------------------------------------------------------------- params

_INT8 = ("in_proj", "wqkv", "wo", "w_gate", "w_up", "w_down")
KINDS = {"L": "gdn", "F": "attn"}
_MLP = ("w_gate", "w_up", "w_down")


def init_params(cfg: OlmoHybridConfig, key, dtype=jnp.bfloat16, *, quant: bool = False,
                embed_std: float | None = None, mixer_gain: float = 1.0) -> dict:
    """Random init, the leaves of a kind stacked on a leading axis (``"gdn"``,
    ``"attn"``). Matrices normal(0, fan_in^-0.5); ``A_log`` / ``dt_bias`` by
    the PUBLISHED Gated-DeltaNet initialisation (A uniform in (0, 16) a head,
    dt_bias the inverse softplus of a log-uniform draw in [1e-3, 1e-1] floored
    at 1e-4) — a normal draw there makes the state vanish or explode; the
    convolution normal(0, K^-0.5), no bias; the gains of the norms on a
    sub-layer's OUTPUT ``mixer_gain`` (a reordered norm sets each sub-layer's
    size beside the residual stream whatever its matrices' scale: the gain IS
    that size), every other gain 1. With ``quant`` every large matrix becomes
    its int8 leaf AS IT IS DRAWN, layer by layer under ``lax.map``: a
    full-width model never exists unquantised."""
    d, f, hd = cfg.dim, cfg.ffn_dim, cfg.head_dim
    H, K, dv = cfg.gdn_heads, cfg.d_conv, cfg.gdn_value_dim
    vd, cd = cfg.value_dim, cfg.conv_dim
    nq, nkv = cfg.n_heads * hd, cfg.n_kv_heads * hd

    def mat(k, shape):
        w = jax.random.normal(k, shape, F32) * shape[0] ** -0.5
        return quantize_leaf(w) if quant else w.astype(dtype)

    def mlp(ks):
        return {"mixer_norm": jnp.full((d,), mixer_gain, dtype), "mlp_norm": jnp.full((d,), mixer_gain, dtype),
                "w_gate": mat(ks[0], (d, f)), "w_up": mat(ks[1], (d, f)), "w_down": mat(ks[2], (f, d))}

    def gdn(k):
        ks = jax.random.split(k, 9)
        dt = jnp.exp(jax.random.uniform(ks[2], (H,), F32) * (math.log(0.1) - math.log(1e-3))
                     + math.log(1e-3))
        dt = jnp.maximum(dt, 1e-4)
        return {"in_proj": mat(ks[0], (d, cd + vd)),  # q | k | v | g
                "ab": (jax.random.normal(ks[1], (d, 2 * H), F32) * d ** -0.5).astype(dtype),
                "conv_w": (jax.random.normal(ks[3], (K, cd), F32) * K ** -0.5).astype(dtype),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1
                "A_log": jnp.log(jax.random.uniform(ks[4], (H,), F32, 1e-3, 16.0)),
                "onorm": jnp.ones((dv,), dtype), "wo": mat(ks[5], (vd, d)), **mlp(ks[6:])}

    def attn(k):
        ks = jax.random.split(k, 5)
        return {"wqkv": mat(ks[0], (d, nq + 2 * nkv)), "q_norm": jnp.ones((nq,), dtype),
                "k_norm": jnp.ones((nkv,), dtype), "wo": mat(ks[1], (nq, d)), **mlp(ks[2:])}

    k_embed, k_head, *kk = jax.random.split(key, 4)
    std = d ** -0.5 if embed_std is None else embed_std
    params = {"embed": (jax.random.normal(k_embed, (cfg.vocab_size, d), F32) * std).astype(dtype),
              "final_norm": jnp.ones((d,), dtype), "lm_head": mat(k_head, (d, cfg.vocab_size))}
    for (kind, name), make, k in zip(KINDS.items(), (gdn, attn), kk):
        if cfg.count(kind):
            params[name] = jax.lax.map(make, jax.random.split(k, cfg.count(kind)))
    return params


def quantize_params(params: dict) -> dict:
    """``models.llama.quantize_params`` for this tree."""
    q = lambda t: {k: (quantize_leaf(v) if k in _INT8 else v) for k, v in t.items()}
    return {**params, **{n: q(params[n]) for n in KINDS.values() if n in params},
            "lm_head": quantize_leaf(params["lm_head"])}


def _leaf(t, i):
    """Layer ``i`` of a stacked leaf (an int8 leaf's planes alike)."""
    return jax.tree.map(lambda a: a[i], t)


# ---------------------------------------------------------------- blocks


def _rope_half(x, positions, theta: float = 10000.0):
    """(the ``rope_on_full`` fault alone) rotate-half over (B, T, heads, hd)."""
    hd = x.shape[-1]
    ang = positions.astype(F32)[..., None] / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    c, s = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :hd // 2].astype(F32), x[..., hd // 2:].astype(F32)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


def l2norm(x, eps: float = 1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def gdn_mix(p, proj, ab, tail, planes, sidx, li, n_real, cfg: OlmoHybridConfig, scan_impl: str,
            fault: str | None = None):
    """What of a Gated-DeltaNet mixer needs a row's positions side by side:
    the convolution, the recurrence and the gated norm, over (B, T, ...)
    projections ``proj`` (q | k | v | g columns) and ``ab`` (a | b). ``tail``
    (B, K-1, conv_dim) the convolution's inputs before position 0; ``planes``
    the stacked float32 states. -> (the gated heads (B, T, H d_v), the new
    tail, the planes with the live rows' ``sidx`` of ``li`` advanced over
    ``n_real``)."""
    from ..ops.gated_delta import gated_delta_scan

    B, T = proj.shape[:2]
    H, dk, dv, K = cfg.gdn_heads, cfg.gdn_key_dim, cfg.gdn_value_dim, cfg.d_conv
    kd, cd = cfg.key_dim, cfg.conv_dim
    with jax.named_scope("layer/gdn/conv"):
        qkv, gate = proj[..., :cd], proj[..., cd:]
        taps = lambda xp: jax.nn.silu(  # over (B, K-1+T, cd)
            sum(xp[:, j:j + T].astype(F32) * p["conv_w"][j].astype(F32) for j in range(K)))
        qkv, new_tail = conv_window(tail, qkv, n_real, taps)
    with jax.named_scope("layer/gdn/scan"):
        q = qkv[..., :kd].reshape(B, T, H, dk)
        k = qkv[..., kd:2 * kd].reshape(B, T, H, dk)
        v = qkv[..., 2 * kd:].reshape(B, T, H, dv)
        if fault != "no_l2norm":
            q, k = l2norm(q), l2norm(k)
        if fault != "no_q_scale":
            q = q * dk ** -0.5
        a, b = ab[..., :H].astype(F32), ab[..., H:].astype(F32)
        beta = jax.nn.sigmoid(b) * (2.0 if cfg.neg_eigval and fault != "beta_not_doubled" else 1.0)
        g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
        if fault == "no_decay":
            g = jnp.zeros_like(g)
        if fault == "bf16_state":  # the state a request carries, rounded where it is read
            planes = planes.at[li, sidx].set(planes[li, sidx].astype(jnp.bfloat16).astype(F32))
        o, planes = gated_delta_scan(planes, sidx, li, q, k, v, g, beta, n_real, scan_impl)
    with jax.named_scope("layer/gdn/norm"):
        gate = jax.nn.silu(gate.astype(F32)).reshape(B, T, H, dv)
        norm = lambda y: y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg.norm_eps)
        w = p["onorm"].astype(F32)
        o = norm(o * gate) * w if fault == "gate_before_norm" else norm(o) * w * gate
    return o.reshape(B, T, H * dv), new_tail.astype(tail.dtype), planes


# ---------------------------------------------------------------- forward


def forward_paged(params, cfg: OlmoHybridConfig, tokens, positions, k_pool, v_pool, block_tables, *,
                  attn_impl: str = "pallas", write_mask=None, trash_idx=None,
                  fresh_block: bool = False, gather_blocks: int | None = None, n_real=None,
                  logit_pos=None, ffn_pack: int = 0, hybrid_stats: bool = False,
                  attn_stats: bool = False, kv_stats: bool = False, fault: str | None = None):
    """``models.llama.forward_paged`` for this model (``fresh_block`` is a
    promise this forward does not need): ``k_pool`` / ``v_pool`` the pytrees
    of the module docstring, ``block_tables`` (B, max_blocks + 1) with the
    state index last; ``logit_pos`` (B,): the head on that one position a row.
    -> (logits, k_pool, v_pool, None, None), then in the family's order:
    ``HYBRID_STATS`` (3,), ``ops.ATTN_STATS``, ``llama.KV_STATS``, and LAST with ``ffn_pack``
    ``llama.FFN_STATS``. ``fault`` PLANTS one (``FAULTS``); None everywhere else."""
    from ..ops import common_block_split, paged_block_attention

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r}: one of {FAULTS}")
    B, T = tokens.shape
    kp, vp, tails, states = k_pool["kv"], v_pool["kv"], k_pool["tail"], v_pool["gdn"]
    bs = kp.shape[2]
    tables, sidx = block_tables[:, :-1].astype(jnp.int32), block_tables[:, -1].astype(jnp.int32)
    M = tables.shape[1]
    live = jnp.ones((B,), bool) if write_mask is None else write_mask
    told = n_real is not None
    n_real = jnp.where(live, n_real if told else T, 0).astype(jnp.int32)
    real = jnp.arange(T)[None, :] < n_real[:, None]
    nb = gather_blocks if gather_blocks is not None else M
    hd, nq, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    scale = hd ** -0.5
    block_decode = attn_impl == "pallas" and T <= MAX_BLOCK_DECODE_T
    scan_impl = "pallas" if attn_impl == "pallas" else "xla"
    P, eps = B * T, cfg.norm_eps
    prenorm = fault == "prenorm_block"

    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    dtype = x.dtype
    # where each position's K/V lands, as (block, offset) (``models.sambay``)
    blk = jnp.take_along_axis(tables, jnp.minimum(positions // bs, M - 1), axis=1)
    park = jnp.zeros((B,), jnp.int32) if trash_idx is None else trash_idx.astype(jnp.int32)
    w_blk = jnp.where(real, blk, park[:, None] // bs)
    w_off = jnp.where(real, positions % bs, park[:, None] % bs)
    # told its rows' real positions, the write walks tiles of them (``llama.write_rows``)
    with jax.named_scope("layer/kv_write"):
        write_tiles, write_at = write_walk(n_real if told else None, T, (w_blk, w_off))
    split = None
    if block_decode and cfg.count("F"):
        with jax.named_scope("layer/attn/split"):
            split = common_block_split(tables, positions, live, bs, n_real=n_real)
    # the real positions of a fast-forward block, packed into ``ffn_pack`` rows while they fit
    # (``llama.FfnPack``: ONE predicate a forward decides every region of every layer)
    pack = None
    if ffn_pack and P > ffn_pack:
        with jax.named_scope("layer/ffn/pack"):
            pack = ffn_pack_index(n_real, T, ffn_pack)

    def rowwise(fn, ins, scope: str):
        """``fn`` over (b, t, w) inputs, position-wise: over the whole block, or —
        with a ``pack`` — over its real positions alone where they fit (gathered to
        (1, P, w), every position reading its slot back). A conditional, not a
        loop over tiles: a leaf sliced inside a branch is its matmul's operand,
        inside a ``while`` body it is hoisted out and COPIED (a layer's int8
        planes once a layer: 7.6 ms of a 48 ms forward, my chip run, PR 54).
        The conditional's own time reads under ``scope``."""
        if pack is None:
            return fn(*ins)
        with jax.named_scope(scope):
            packed = lambda *a: jax.tree.map(pack.block, fn(*map(pack.rows, a)))
            return jax.lax.cond(pack.fits, packed, fn, *ins)

    def out_and_mlp(stack, i, scope: str):
        """A layer's second region: h = x + norm(mixer W_o); y = h + norm(MLP(h)).
        The layer's leaves are sliced INSIDE the branch that reads them
        (``rowwise``), at a static index."""
        def fn(x, mixed):
            p = _leaf(stack, i)
            with jax.named_scope(scope):
                out = _qe("btf,fd->btd", mixed, p["wo"]).astype(dtype)
                h = x + out if prenorm else x + rms_norm(out, p["mixer_norm"], eps)
            with jax.named_scope("layer/ffn"):
                u = rms_norm(h, p["mlp_norm"], eps) if prenorm else h
                y = _swiglu(p, u, _MLP).astype(dtype)
                return h + y if prenorm else h + rms_norm(y, p["mlp_norm"], eps)

        return fn

    # a slot's row of this forward, for the tails' write-back: a layer's (slots, w) slice
    # is updated WHOLE (a select of rows, one in-place update) — a scatter by slot into
    # the stacked plane ran as a loop over the rows, 6.8 ms a forward (my chip run, PR 54)
    hit = sidx[None, :] == jnp.arange(tails.shape[1], dtype=jnp.int32)[:, None]  # (slots, B)
    row_of, named = jnp.argmax(hit, axis=1), jnp.any(hit, axis=1)
    small = {k: params["gdn"][k] for k in ("conv_w", "A_log", "dt_bias", "onorm")}

    def l_layer(x, tails, states, li):
        def project(x):
            p = _leaf(params["gdn"], li)
            with jax.named_scope("layer/gdn/in_proj"):
                u = rms_norm(x, p["mixer_norm"], eps) if prenorm else x
                proj = _qe("btd,de->bte", u, p["in_proj"]).astype(dtype)
                ab = jnp.einsum("btd,de->bte", u, p["ab"], preferred_element_type=F32).astype(dtype)
                return proj, ab

        proj, ab = rowwise(project, (x,), "layer/gdn/in_proj")
        with jax.named_scope("layer/gdn/conv"):
            held = jax.lax.dynamic_index_in_dim(tails, li, 0, keepdims=False)  # (slots, w)
            tail = held[sidx].reshape(B, cfg.d_conv - 1, cfg.conv_dim)
        mixed, tail, states = gdn_mix(_leaf(small, li), proj, ab, tail, states, sidx, li, n_real, cfg,
                                      scan_impl, fault)
        with jax.named_scope("layer/gdn/conv"):
            held = jnp.where(named[:, None], tail.reshape(B, -1)[row_of], held)
            tails = jax.lax.dynamic_update_index_in_dim(tails, held, li, 0)
        x = rowwise(out_and_mlp(params["gdn"], li, "layer/gdn/out_proj"), (x, mixed.astype(dtype)),
                    "layer/rows")
        return x, tails, states

    def f_layer(x, kp, vp, ai):
        def project(x):
            p = _leaf(params["attn"], ai)
            with jax.named_scope("layer/attn_qkv"):
                u = rms_norm(x, p["mixer_norm"], eps) if prenorm else x
                qkv = _qe("btd,dh->bth", u, p["wqkv"]).astype(dtype)
                q = rms_norm(qkv[..., :nq * hd], p["q_norm"], eps)
                k = rms_norm(qkv[..., nq * hd:(nq + nkv) * hd], p["k_norm"], eps)
                return jnp.concatenate([q, k, qkv[..., (nq + nkv) * hd:]], axis=-1)

        qkv = rowwise(project, (x,), "layer/attn_qkv")
        with jax.named_scope("layer/attn_qkv"):
            q = qkv[..., :nq * hd].reshape(B, T, nq, hd)
            k = qkv[..., nq * hd:(nq + nkv) * hd].astype(kp.dtype).reshape(B, T, nkv, hd)
            v = qkv[..., (nq + nkv) * hd:].astype(vp.dtype).reshape(B, T, nkv, hd)
            if fault == "rope_on_full":
                q, k = _rope_half(q, positions), _rope_half(k, positions)
        held = ((0, 0), (0, 0), (0, cfg.kv_heads_held - nkv), (0, 0))  # the planes' heads of zeros
        with jax.named_scope("layer/kv_write"):
            kp, vp = write_rows(kp, vp, ai, jnp.pad(k, held), jnp.pad(v, held), write_at, write_tiles)
        with jax.named_scope("layer/attn/full"):
            if block_decode:  # a group of query heads of zeros for each K/V head of zeros
                qh = jnp.pad(q, ((0, 0), (0, 0), (0, (cfg.kv_heads_held - nkv) * (nq // nkv)), (0, 0)))
                a = paged_block_attention(qh, kp, vp, tables, positions, ai, live, split, None,
                                          n_real, scale=scale, out_dtype=F32)[:, :, :nq]
            else:
                with jax.named_scope("kv_gather"):
                    tbl = tables[:, :nb]
                    kl = gather_row_blocks(kp, ai, tbl).reshape(B, nb * bs, -1, hd)[:, :, :nkv]
                    vl = gather_row_blocks(vp, ai, tbl).reshape(B, nb * bs, -1, hd)[:, :, :nkv]
                a = _attend(q, kl, vl, positions, _NO_WINDOW, scale)
        x = rowwise(out_and_mlp(params["attn"], ai, "layer/attn_out"),
                    (x, a.astype(dtype).reshape(B, T, nq * hd)), "layer/rows")
        return x, kp, vp

    # the layers UNROLLED, each leaf sliced at a static index: a slice at a loop's index
    # is a COPY of the int8 plane before the matmul that reads it (in_proj, both out
    # projections and w_down: 7.6 ms of a 48 ms forward, my chip run, PR 54 — as PR 34 found
    # for scanned periods)
    seen = {"L": 0, "F": 0}
    for kind in cfg.pattern:
        i = seen[kind]
        seen[kind] += 1
        if kind == "L":
            x, tails, states = l_layer(x, tails, states, i)
        else:
            x, kp, vp = f_layer(x, kp, vp, i)

    with jax.named_scope("final_norm"):
        if logit_pos is not None:
            x = jnp.take_along_axis(x, logit_pos[:, None, None], axis=1)
        x = rms_norm(x, params["final_norm"], eps)
    with jax.named_scope("lm_head"):
        logits = _qe("btd,dv->btv", x, params["lm_head"])
    extra = ()
    nl_all, nf_all = cfg.count("L"), cfg.count("F")
    if hybrid_stats:
        extra += (jnp.stack([nl_all * jnp.sum(n_real), jnp.int32(nl_all * B * T),
                             nl_all * jnp.sum(n_real > 0)]).astype(jnp.int32),)
    if attn_stats:
        held = jnp.sum(jnp.where(live, jnp.max(positions, axis=1) // bs + 1, 0))
        common, handed = split.counts[::2] if split is not None else (jnp.int32(0),) * 2
        extra += (jnp.stack([nf_all * common, nf_all * held, nf_all * handed]).astype(jnp.int32),)
    if kv_stats:
        extra += (nf_all * rows_written(write_tiles, positions)[None],)
    if ffn_pack:
        extra += ((pack.stats if pack is not None else jnp.asarray([0, P], jnp.int32)),)
    return (logits, {"kv": kp, "tail": tails}, {"kv": vp, "gdn": states}, None, None, *extra)
