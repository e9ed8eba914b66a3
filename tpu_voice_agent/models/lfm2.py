"""LFM2 decoder with routed experts (``model_type`` ``lfm2_moe``: LFM2-8B-A1B),
the served forward: GATED SHORT-CONVOLUTION layers whose request state is a
tail of inputs and nothing else beside grouped-query attention layers, by the
published ``layer_types`` (the configuration's ``pattern``: ``C`` conv, ``F``
full_attention — NOT a period: the published list ends ``F C C``), and, on an
axis of its own, ``first_dense_layers`` leading dense SwiGLU layers before
layers of bias-selected sigmoid experts.

Block, every layer — pre-norm, RMSNorm, no bias anywhere:

    h = x + Mixer(RMSNorm(x; operator_norm))      y = h + MLP(RMSNorm(h; ffn_norm))
    logits = RMSNorm(x_L; final) E^T              (the head IS the embedding, ASSUMED tied)

- ``C``, GATED SHORT CONVOLUTION of width K = ``d_conv`` (``conv_L_cache`` 3), no
  activation: [B | C | u] = z W_in (d x 3d, split in THAT order);  g = B * u;
  c_t = sum_j w[j] * g_{t-K+1+j} (depthwise, causal, zeros before position 0);
  out = (C * c) W_out.
- ``F``, ATTENTION: ``n_heads`` query heads over ``n_kv_heads`` K/V heads of
  ``head_size``; q and k through an RMSNorm over EACH HEAD (one gain of
  ``head_size`` shared by the heads) BEFORE a half-split rotation at
  ``rope_theta``; causal softmax at head^-0.5.
- MLP, layer i < ``first_dense_layers``: (silu(h W_1) * (h W_3)) W_2 at
  ``dense_ffn_dim``; else ``n_experts`` SwiGLU experts of ``ffn_dim``, ``top_k`` a
  token: s = sigmoid(h W_r); chosen = top-k of s + b (the bias in the SELECTION
  alone); gates s / sum of the chosen s, times ``router_scale`` —
  ``moe._select_topk(bias=...)`` through ``llama._moe_ffn``, the router and the
  dispatch every routed model here shares. (The published module adds 1e-6 to
  that sum: 5e-7 of a gate, under bf16's rounding; the reference has it.)

WHAT A REQUEST HOLDS (``cache_spec``): K/V planes for the ``F`` layers alone
and, per SLOT, the convolution's tail for each ``C`` layer — the K - 1 last
GATED inputs g = B * u of its last real position, bf16: 2 x 2048 x 2 B = 8 KB a
layer, 147 KB a request at the published sizes — in the k pool (plane
``tail``). The v pool holds NO per-slot plane: the state is not a recurrence,
nothing is scanned, and the tail after a forward is a GATHER of the last K - 1
real inputs of a row (``short_conv``). Pools and block tables as
``models.sambay``'s: the slot's index one column past a row's blocks.

HEADS OF 64: two K/V heads stand side by side on one 128-lane row of a plane
((layers, N, block, n_kv_heads / 2, 128): ``kv_lanes``), which is the flat K
or V row viewed in pairs — no move. A query head rides the packed K/V head that
holds its own, its 64 values on that head's half of the lanes and zeros on the
other (``pair_q``), so the block kernels see heads of 128 and the scores and
values are the 64-wide head's own; the XLA path (an admission) views the
gathered rows as heads of 64 again.

MASKED ADVANCE as ``models.sambay``'s: ``n_real`` (B,) real positions a row; a
tail moves over those alone (a row with none keeps its tail bit for bit), K/V
of the others is parked.

EVERYTHING POSITION-WISE OF A FAST-FORWARD BLOCK RUNS PACKED, as
``models.olmo_hybrid``'s two regions a layer do (``llama.FfnPack``: the real
positions gathered into ``ffn_pack`` rows where they fit, one conditional a
region): the projections IN (``C``: W_in and the gate B * u; ``F``: q, k, v and
their norms), then, behind the taps (or the rotation, the K/V write and
attention), which need a row's positions side by side, the projection OUT with
its residual and the whole MLP with its — the routed experts told how many
packed rows are real (``FfnPack.n_rows``: a filler row goes to no expert).

Layers: the leaves of a kind are STACKED on a leading axis (``shortconv``,
``attn``: the mixers; ``dense``, ``experts``: the MLPs — mixer kind and MLP
kind are independent axes of a layer) and the layers are unrolled, each slicing
its leaves at a static index inside the branch that reads them; the expert
planes stay stacked and ``ops.grouped_matmul`` picks a layer's by index.
"""

from __future__ import annotations

from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp

from .llama import (MAX_BLOCK_DECODE_T, _moe_ffn, _moe_ffn_dense, _moe_ffn_grouped, _qe, _swiglu,
                    apply_rope, cache_planes, conv_window, ffn_pack_index, gather_row_blocks, moe_stat_names,
                    quantize_leaf, rms_norm, rope_tables, rows_written, write_rows, write_walk)
from .sambay import _NO_WINDOW, StateNotCarried, _attend  # noqa: F401  (the family's error class)

F32 = jnp.float32

# what a forward counts beside the routed rows and the attention row-blocks:
# positions the tails advanced over, positions computed, live rows x C layers
# (each moves its K - 1 rows once in and once out)
HYBRID_STATS = ("conv.positions_advanced", "conv.positions", "conv.tail_rows_moved")

# the key that only this family's parameter tree has (``family.tree_owner``)
TREE_ROOT = "shortconv"

# faults of this block's own mechanisms, planted in the served program for the
# comparison's limit to be set against (``benchmark/tools/shortconv_check.py``,
# which plants one more by rebinding: a tail not restored)
FAULTS = ("no_in_gate", "no_out_gate", "taps_reversed", "tail_of_x", "tail_at_T", "bias_in_gates",
          "no_renorm", "softmax_router", "no_qk_norm", "norm_after_rope", "dense_everywhere")
# those that are another configuration of the shared router (``dense_everywhere``: the
# selection off — every token through every expert of a routed layer)
_FAULT_CFG = {"no_renorm": lambda c: {"norm_topk": False},
              "softmax_router": lambda c: {"router_fn": "softmax"},
              "dense_everywhere": lambda c: {"top_k": c.n_experts}}

KINDS = {"C": "shortconv", "F": "attn"}


@dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 65536
    dim: int = 2048
    pattern: str = "CCFCCCFCCCFCCCFCCCFCCFCC"  # a kind each layer: C conv, F full_attention
    n_heads: int = 32
    n_kv_heads: int = 8
    head_size: int = 64
    d_conv: int = 3  # ``conv_L_cache``: the taps; a request holds d_conv - 1 inputs
    rope_theta: float = 1_000_000.0
    norm_eps: float = 1e-5
    first_dense_layers: int = 2
    dense_ffn_dim: int = 7168
    n_experts: int = 32
    top_k: int = 4
    ffn_dim: int = 1792  # a routed expert's width
    norm_topk: bool = True
    router_scale: float = 1.0
    router_fn: str = "sigmoid"
    max_seq_len: int = 2048
    moe_impl: str = "auto"

    # what else ``llama._moe_ffn`` reads of a routed model's configuration
    router_bias = True
    expert_form = "swiglu"
    experts_held = 0
    first_expert = 0

    def __post_init__(self):
        if set(self.pattern) - set("CF") or "C" not in self.pattern:
            raise ValueError(f"pattern {self.pattern!r}: C | F a layer, a convolution layer among them")
        if self.n_heads % self.n_kv_heads or self.n_kv_heads % self.kv_lanes[1]:
            raise ValueError("query heads in whole groups; K/V heads of 64 or less in pairs")
        if self.d_conv < 2:
            raise ValueError(f"d_conv {self.d_conv}: a convolution holds at least one input back")
        if not 0 <= self.first_dense_layers < self.n_layers or not 0 < self.top_k <= self.n_experts:
            raise ValueError("leading dense layers before at least one routed layer, top_k of n_experts")

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def head_dim(self) -> int:
        return self.head_size

    @property
    def n_held(self) -> int:
        return self.n_experts

    @property
    def capacity_factor(self) -> float:
        """The dense dispatch's (a bare forward, the grouped kernel's twin): drop-free."""
        return self.n_experts / self.top_k

    @property
    def kv_lanes(self) -> tuple[int, int]:
        """(rows a plane holds a position, K/V heads side by side on a row): a
        head of 64 or less stands beside its neighbour — 128 lanes at the published 64."""
        side = 2 if self.head_size <= 64 else 1
        return self.n_kv_heads // side, side

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)

    def routed(self, layer: int) -> bool:
        return layer >= self.first_dense_layers


PRESETS = {
    # both mixers with the published list's irregular end, a dense then routed layers (the
    # switch inside the first run of C layers), heads of 16 in pairs
    "lfm2-test": Lfm2Config(
        vocab_size=1024, dim=64, pattern="CCFCFCC", n_heads=8, n_kv_heads=4, head_size=16,
        first_dense_layers=1, dense_ffn_dim=160, n_experts=8, top_k=2, ffn_dim=48, max_seq_len=256),
}


def cache_spec(cfg: Lfm2Config) -> dict:
    """K/V planes for the attention layers alone, two heads a 128-lane row; a
    SLOT's convolution tail for each conv layer in the k pool and NOTHING per
    slot in the v pool (``models.family`` has the shape)."""
    rows, side = cfg.kv_lanes
    kv = {"kv": (max(cfg.count("F"), 1), rows, side * cfg.head_dim)}
    return cache_planes(
        kv, kv, by_name=True,
        slot_k={"tail": ((cfg.count("C"), (cfg.d_conv - 1) * cfg.dim), jnp.bfloat16)})


# ---------------------------------------------------------------- params

_INT8 = ("in_proj", "out_proj", "wqkv", "wo", "w_gate", "w_up", "w_down", "moe_gate", "moe_up",
         "moe_down")
_STACKS = ("shortconv", "attn", "dense", "experts")
_DENSE = ("w_gate", "w_up", "w_down")
_EXPERT_PLANES = ("moe_gate", "moe_up", "moe_down")


def init_params(cfg: Lfm2Config, key, dtype=jnp.bfloat16, *, quant: bool = False,
                embed_std: float | None = None, bias_std: float = 0.1, routed_gain: float = 1.0,
                mixer_gain: float = 1.0) -> dict:
    """Random init, the leaves of a kind stacked on a leading axis
    (``"shortconv"``, ``"attn"``: the mixers, each with its ``operator_norm``;
    ``"dense"``, ``"experts"``: the MLPs, each with its ``ffn_norm``). Matrices
    normal(0, fan_in^-0.5); the taps normal(0, K^-0.5); the q and k gains
    uniform in (0.5, 1.5) — a norm ahead of a rotation is told from one behind
    it by its gain alone —, every other gain 1; the router's selection bias
    normal(0, ``bias_std``), float32; a routed expert's down projection times
    ``routed_gain``, a mixer's out projection (``out_proj``, ``wo``) times
    ``mixer_gain``. With ``quant`` every large matrix becomes its int8 leaf AS
    IT IS DRAWN, layer by layer and expert by expert under ``lax.map``, and the
    tied head an int8 copy of the embedding (``quantize_params``): a full-width
    model never exists unquantised."""
    d, hd, K = cfg.dim, cfg.head_dim, cfg.d_conv
    nq, nkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    f, df, E = cfg.ffn_dim, cfg.dense_ffn_dim, cfg.n_experts

    def mat(k, shape, gain: float = 1.0):
        w = jax.random.normal(k, shape, F32) * (shape[0] ** -0.5 * gain)
        return quantize_leaf(w) if quant else w.astype(dtype)

    def shortconv(k):
        ks = jax.random.split(k, 3)
        return {"operator_norm": jnp.ones((d,), dtype), "in_proj": mat(ks[0], (d, 3 * d)),  # B | C | u
                "conv_w": (jax.random.normal(ks[1], (K, d), F32) * K ** -0.5).astype(dtype),
                "out_proj": mat(ks[2], (d, d), mixer_gain)}

    def attn(k):
        ks = jax.random.split(k, 4)
        gain = lambda kk: jax.random.uniform(kk, (hd,), F32, 0.5, 1.5).astype(dtype)
        return {"operator_norm": jnp.ones((d,), dtype), "wqkv": mat(ks[0], (d, nq + 2 * nkv)),
                "q_norm": gain(ks[1]), "k_norm": gain(ks[2]), "wo": mat(ks[3], (nq, d), mixer_gain)}

    def dense(k):
        ks = jax.random.split(k, 3)
        return {"ffn_norm": jnp.ones((d,), dtype), "w_gate": mat(ks[0], (d, df)),
                "w_up": mat(ks[1], (d, df)), "w_down": mat(ks[2], (df, d))}

    def experts(k):
        ks = jax.random.split(k, 3)

        def one(ke):
            kg, ku, kd = jax.random.split(ke, 3)
            return {"moe_gate": mat(kg, (d, f)), "moe_up": mat(ku, (d, f)),
                    "moe_down": mat(kd, (f, d), routed_gain)}

        return {"ffn_norm": jnp.ones((d,), dtype),
                "router": (jax.random.normal(ks[0], (d, E), F32) * d ** -0.5).astype(dtype),
                "router_bias": bias_std * jax.random.normal(ks[1], (E,), F32),
                **jax.lax.map(one, jax.random.split(ks[2], E))}

    k_embed, *kk = jax.random.split(key, 5)
    std = d ** -0.5 if embed_std is None else embed_std
    embed = (jax.random.normal(k_embed, (cfg.vocab_size, d), F32) * std).astype(dtype)
    params = {"embed": embed, "final_norm": jnp.ones((d,), dtype)}
    n = {"shortconv": cfg.count("C"), "attn": cfg.count("F"), "dense": cfg.first_dense_layers,
         "experts": cfg.n_layers - cfg.first_dense_layers}
    for name, make, k in zip(_STACKS, (shortconv, attn, dense, experts), kk):
        if n[name]:
            params[name] = jax.lax.map(make, jax.random.split(k, n[name]))
    return {**params, "lm_head": quantize_leaf(embed.T)} if quant else params


def quantize_params(params: dict) -> dict:
    """``models.llama.quantize_params`` for this tree; the tied head becomes an
    int8 copy of the embedding, a scale a vocabulary row (as ``models.sambay``'s)."""
    q = lambda t: {k: (quantize_leaf(v) if k in _INT8 else v) for k, v in t.items()}
    return {**params, **{n: q(params[n]) for n in _STACKS if n in params},
            "lm_head": quantize_leaf(params["embed"].T)}


def _leaf(t, i):
    """Layer ``i`` of a stacked leaf (an int8 leaf's planes alike)."""
    return jax.tree.map(lambda a: a[i], t)


# ---------------------------------------------------------------- blocks


def short_conv(conv_w, gated, tail, n_real, fault: str | None = None):
    """What of the mixer needs a row's positions side by side: the taps and
    the tail (``llama.conv_window``: the concatenate and the gather every
    family's convolution shares). ``gated`` (B, T, 2d): g = B * u beside the out
    gate C (and, for the ``tail_of_x`` fault alone, u behind them); ``tail`` (B,
    K-1, d) -> ((C * c) (B, T, d) float32, the new tail)."""
    K, d = conv_w.shape
    T = gated.shape[1]
    g, c_gate = gated[..., :d], gated[..., d:2 * d]
    w = conv_w[::-1] if fault == "taps_reversed" else conv_w
    taps = lambda xp: sum(xp[:, j:j + T].astype(F32) * w[j].astype(F32) for j in range(K))
    c, new_tail = conv_window(tail, g, jnp.full_like(n_real, T) if fault == "tail_at_T" else n_real, taps)
    if fault == "tail_of_x":  # the tail holding u where it holds B * u
        new_tail = conv_window(tail, gated[..., 2 * d:], n_real, lambda xp: None)[1]
    return (c if fault == "no_out_gate" else c_gate.astype(F32) * c), new_tail.astype(tail.dtype)


def _own_place(cfg: Lfm2Config) -> jax.Array:
    """(n_heads,): which of its row's K/V heads a query head attends."""
    return (jnp.arange(cfg.n_heads) // (cfg.n_heads // cfg.n_kv_heads)) % cfg.kv_lanes[1]


def pair_q(q, cfg: Lfm2Config):
    """(B, T, n_heads, hd) -> (B, T, n_heads, lanes): each query head's values
    on the lanes its K/V head stands on in the packed row, zeros on the others.
    A tile and a multiply by a constant mask, ``unpair`` a multiply and a sum:
    written as slices, ``concatenate`` and ``stack``, the same pair came out of
    XLA's TPU compiler WRONG under ``jit`` (1.6 of the output's range at the
    cell's shapes, exact op by op: my chip runs, PR 64 —
    ``benchmark/tools/shortconv_check.py --pairs`` holds it on the chip)."""
    side, hd = cfg.kv_lanes[1], cfg.head_size
    on = jnp.arange(side * hd)[None, :] // hd == _own_place(cfg)[:, None]  # (heads, lanes)
    return jnp.tile(q, (1, 1, 1, side)) * on.astype(q.dtype)


def unpair(a, cfg: Lfm2Config):
    """The block kernel's (B, T, n_heads, lanes) output -> (B, T, n_heads, hd):
    each head's own part of the packed value row."""
    side, hd = cfg.kv_lanes[1], cfg.head_size
    own = jnp.arange(side)[None, :] == _own_place(cfg)[:, None]  # (heads, side)
    return jnp.sum(a.reshape(*a.shape[:3], side, hd) * own.astype(a.dtype)[:, :, None], axis=3)


def expert_layer(p, h, cfg: Lfm2Config, fault: str | None = None, n_rows=None):
    """The routed MLP over normed rows ``h`` (b, t, d) -> (its sum, the layer's
    ``llama._moe_stats``). Position-wise; ``n_rows``: the leading rows that are
    real. ``p``: the router and its bias sliced, the expert planes as the
    dispatch wants them (``forward_paged``). The router, the dispatch and the
    kernel are ``models.llama``'s and ``models.moe``'s; nothing is routed here
    but the ``bias_in_gates`` fault."""
    cfg = replace(cfg, **_FAULT_CFG[fault](cfg)) if fault in _FAULT_CFG else cfg
    if fault != "bias_in_gates":
        return _moe_ffn(p, h, cfg, n_rows=n_rows)
    from .moe import _select_topk

    b, t, d = h.shape
    bias = p["router_bias"].astype(F32)
    _, eids, vals = _select_topk(p["router"], h.reshape(b * t, d), cfg.n_experts, cfg.top_k,
                                 cfg.router_fn, bias)
    vals = vals + bias[eids]  # the bias where it does not belong
    gates = vals / jnp.sum(vals, axis=1, keepdims=True) * cfg.router_scale
    picks = (eids.reshape(b, t, -1), gates.reshape(b, t, -1))
    if cfg.moe_impl == "grouped":
        return _moe_ffn_grouped(p, h, cfg, None, n_rows, picks)
    return _moe_ffn_dense(p, h, cfg, None, picks)


# ---------------------------------------------------------------- forward


def forward_paged(params, cfg: Lfm2Config, tokens, positions, k_pool, v_pool, block_tables, *,
                  attn_impl: str = "pallas", write_mask=None, trash_idx=None,
                  fresh_block: bool = False, gather_blocks: int | None = None, n_real=None,
                  logit_pos=None, ffn_pack: int = 0, hybrid_stats: bool = False,
                  moe_stats: bool = False, attn_stats: bool = False, kv_stats: bool = False,
                  fault: str | None = None):
    """``models.llama.forward_paged`` for this model (``fresh_block`` is a
    promise this forward does not need): ``k_pool`` {"kv", "tail"} / ``v_pool``
    {"kv"} the pytrees of the module docstring, ``block_tables`` (B, max_blocks
    + 1) with the slot's index last; ``logit_pos`` (B,): the head on that one
    position a row. -> (logits, k_pool, v_pool, None, None), then in the family's
    order: ``HYBRID_STATS`` (3,), the routed layers' ``llama.MOE_STATS``,
    ``ops.ATTN_STATS``, ``llama.KV_STATS``, and LAST with ``ffn_pack``
    ``llama.FFN_STATS``. ``fault`` PLANTS one (``FAULTS``); None everywhere else."""
    from ..ops import common_block_split, paged_block_attention

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r}: one of {FAULTS}")
    B, T = tokens.shape
    kp, vp, tails = k_pool["kv"], v_pool["kv"], k_pool["tail"]
    bs = kp.shape[2]
    tables, sidx = block_tables[:, :-1].astype(jnp.int32), block_tables[:, -1].astype(jnp.int32)
    M = tables.shape[1]
    live = jnp.ones((B,), bool) if write_mask is None else write_mask
    told = n_real is not None
    n_real = jnp.where(live, n_real if told else T, 0).astype(jnp.int32)
    real = jnp.arange(T)[None, :] < n_real[:, None]
    nb = gather_blocks if gather_blocks is not None else M
    d, hd, nq, nkv, K = cfg.dim, cfg.head_dim, cfg.n_heads, cfg.n_kv_heads, cfg.d_conv
    scale = hd ** -0.5
    block_decode = attn_impl == "pallas" and T <= MAX_BLOCK_DECODE_T
    P, eps = B * T, cfg.norm_eps

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
    if cfg.count("F"):
        with jax.named_scope("layer/attn_qkv"):
            cos, sin = rope_tables(positions, hd, cfg.rope_theta)
        if block_decode:
            with jax.named_scope("layer/attn/split"):
                split = common_block_split(tables, positions, live, bs, n_real=n_real)
    # the real positions of a fast-forward block, packed into ``ffn_pack`` rows while they fit
    # (``llama.FfnPack``: ONE predicate a forward decides every region of every layer)
    pack = None
    if ffn_pack and P > ffn_pack:
        with jax.named_scope("layer/ffn/pack"):
            pack = ffn_pack_index(n_real, T, ffn_pack)

    def rowwise(fn, ins, scope: str):
        """``fn(*ins, n_rows)`` over (b, t, w) inputs, position-wise: over the
        whole block (``n_rows`` None), or — with a ``pack`` — over its real
        positions alone where they fit (gathered to (1, P, w), ``n_rows`` of them
        real; every position reads its slot back; what ``fn`` returns that is no
        block of rows — a routed layer's counts — passes through). A conditional,
        not a loop over tiles, the leaves sliced inside the branch that reads them
        (``models.olmo_hybrid`` has the measurements). Its own time reads under ``scope``."""
        if pack is None:
            return fn(*ins, None)
        back = lambda a: pack.block(a) if a.ndim == 3 else a
        with jax.named_scope(scope):
            packed = lambda *a: jax.tree.map(back, fn(*map(pack.rows, a), pack.n_rows))
            return jax.lax.cond(pack.fits, packed, lambda *a: fn(*a, None), *ins)

    grouped = cfg.moe_impl == "grouped"
    no_stats = jnp.zeros((len(moe_stat_names(cfg)),), jnp.int32)

    def out_and_mlp(stack, i, w_out: str, scope: str, layer: int):
        """A layer's second region: h = x + mixed W_out; y = h + MLP(norm(h)) —
        the dense SwiGLU of the leading layers, or the routed experts (and their
        counts). The leaves are sliced INSIDE the branch that reads them, at a
        static index; the expert planes stay stacked for the grouped kernel."""
        mi = layer - cfg.first_dense_layers if cfg.routed(layer) else layer

        def fn(x, mixed, n_rows):
            with jax.named_scope(scope):
                h = x + _qe("btf,fd->btd", mixed, _leaf(stack[w_out], i)).astype(dtype)
            if not cfg.routed(layer):
                p = _leaf(params["dense"], mi)
                with jax.named_scope("layer/ffn"):
                    u = rms_norm(h, p["ffn_norm"], eps)
                    with jax.named_scope("dense"):
                        return h + _swiglu(p, u, _DENSE).astype(dtype), no_stats
            ex = params["experts"]
            p = {k: ex[k][mi] for k in ("ffn_norm", "router", "router_bias")}
            if grouped:  # the kernel picks the layer's planes out of the stack itself
                p.update({k: ex[k] for k in _EXPERT_PLANES}, layer=jnp.int32(mi))
            else:
                p.update({k: _leaf(ex[k], mi) for k in _EXPERT_PLANES})
            with jax.named_scope("layer/ffn"):
                y, st = expert_layer(p, rms_norm(h, p["ffn_norm"], eps), cfg, fault, n_rows)
                return h + y.astype(dtype), st

        return fn

    # a slot's row of this forward, for the tails' write-back: a layer's (slots, w) slice
    # is updated WHOLE (a select of rows, one in-place update; ``models.olmo_hybrid``)
    hit = sidx[None, :] == jnp.arange(tails.shape[1], dtype=jnp.int32)[:, None]  # (slots, B)
    row_of, named = jnp.argmax(hit, axis=1), jnp.any(hit, axis=1)

    def c_layer(x, tails, ci, layer):
        def project(x, n_rows):
            p = _leaf({k: params["shortconv"][k] for k in ("operator_norm", "in_proj")}, ci)
            with jax.named_scope("layer/conv/proj"):
                bcu = _qe("btd,de->bte", rms_norm(x, p["operator_norm"], eps), p["in_proj"])
                gate, u = bcu[..., :d], bcu[..., 2 * d:]
                g = u if fault == "no_in_gate" else gate * u
                cols = (g, bcu[..., d:2 * d]) + ((u,) if fault == "tail_of_x" else ())
                return jnp.concatenate(cols, axis=-1).astype(dtype)

        gated = rowwise(project, (x,), "layer/conv/proj")
        with jax.named_scope("layer/conv/mix"):
            held = jax.lax.dynamic_index_in_dim(tails, ci, 0, keepdims=False)  # (slots, w)
            mixed, tail = short_conv(params["shortconv"]["conv_w"][ci], gated,
                                     held[sidx].reshape(B, K - 1, d), n_real, fault)
            held = jnp.where(named[:, None], tail.reshape(B, -1)[row_of], held)
            tails = jax.lax.dynamic_update_index_in_dim(tails, held, ci, 0)
        x, st = rowwise(out_and_mlp(params["shortconv"], ci, "out_proj", "layer/conv/out", layer),
                        (x, mixed.astype(dtype)), "layer/rows")
        return x, tails, st

    def f_layer(x, kp, vp, ai, layer):
        def project(x, n_rows):
            p = _leaf({k: params["attn"][k] for k in ("operator_norm", "wqkv", "q_norm", "k_norm")}, ai)
            with jax.named_scope("layer/attn_qkv"):
                qkv = _qe("btd,dh->bth", rms_norm(x, p["operator_norm"], eps), p["wqkv"]).astype(dtype)
                if fault in ("no_qk_norm", "norm_after_rope"):
                    return qkv
                b, t = qkv.shape[:2]
                heads = lambda a, g: rms_norm(a.reshape(b, t, -1, hd), g, eps).reshape(b, t, -1)
                return jnp.concatenate([heads(qkv[..., :nq * hd], p["q_norm"]),
                                        heads(qkv[..., nq * hd:(nq + nkv) * hd], p["k_norm"]),
                                        qkv[..., (nq + nkv) * hd:]], axis=-1)

        qkv = rowwise(project, (x,), "layer/attn_qkv")
        with jax.named_scope("layer/attn_qkv"):
            q = apply_rope(qkv[..., :nq * hd].reshape(B, T, nq, hd), cos, sin)
            k = apply_rope(qkv[..., nq * hd:(nq + nkv) * hd].reshape(B, T, nkv, hd), cos, sin)
            if fault == "norm_after_rope":
                q = rms_norm(q, params["attn"]["q_norm"][ai], eps)
                k = rms_norm(k, params["attn"]["k_norm"][ai], eps)
            # two K/V heads a 128-lane row: the flat row in pairs, no move
            k = k.astype(kp.dtype).reshape(B, T, *kp.shape[3:])
            v = qkv[..., (nq + nkv) * hd:].astype(vp.dtype).reshape(B, T, *vp.shape[3:])
        with jax.named_scope("layer/kv_write"):
            kp, vp = write_rows(kp, vp, ai, k, v, write_at, write_tiles)
        with jax.named_scope("layer/attn/full"):
            if block_decode:
                a = unpair(paged_block_attention(pair_q(q, cfg), kp, vp, tables, positions, ai, live,
                                                 split, None, n_real, scale=scale, out_dtype=F32), cfg)
            else:
                with jax.named_scope("kv_gather"):
                    tbl = tables[:, :nb]
                    kl = gather_row_blocks(kp, ai, tbl).reshape(B, nb * bs, nkv, hd)
                    vl = gather_row_blocks(vp, ai, tbl).reshape(B, nb * bs, nkv, hd)
                a = _attend(q, kl, vl, positions, _NO_WINDOW, scale)
        x, st = rowwise(out_and_mlp(params["attn"], ai, "wo", "layer/attn_out", layer),
                        (x, a.astype(dtype).reshape(B, T, nq * hd)), "layer/rows")
        return x, kp, vp, st

    # the layers UNROLLED, each leaf sliced at a static index (``models.olmo_hybrid``: a
    # slice at a loop's index is a COPY of the int8 plane before the matmul that reads it)
    seen = {"C": 0, "F": 0}
    stats = no_stats
    for layer, kind in enumerate(cfg.pattern):
        i = seen[kind]
        seen[kind] += 1
        if kind == "C":
            x, tails, st = c_layer(x, tails, i, layer)
        else:
            x, kp, vp, st = f_layer(x, kp, vp, i, layer)
        stats = stats + st

    with jax.named_scope("final_norm"):
        if logit_pos is not None:
            x = jnp.take_along_axis(x, logit_pos[:, None, None], axis=1)
        x = rms_norm(x, params["final_norm"], eps)
    with jax.named_scope("lm_head"):
        if "lm_head" in params:  # the tied head's int8 copy
            logits = _qe("btd,dv->btv", x, params["lm_head"])
        else:
            logits = jnp.einsum("btd,vd->btv", x, params["embed"], preferred_element_type=F32)
    extra = ()
    nc, nf = cfg.count("C"), cfg.count("F")
    if hybrid_stats:
        extra += (jnp.stack([nc * jnp.sum(n_real), jnp.int32(nc * B * T),
                             nc * jnp.sum(n_real > 0)]).astype(jnp.int32),)
    if moe_stats:
        extra += (stats,)
    if attn_stats:
        held = jnp.sum(jnp.where(live, jnp.max(positions, axis=1) // bs + 1, 0))
        common, handed = split.counts[::2] if split is not None else (jnp.int32(0),) * 2
        extra += (jnp.stack([nf * common, nf * held, nf * handed]).astype(jnp.int32),)
    if kv_stats:
        extra += (nf * rows_written(write_tiles, positions)[None],)
    if ffn_pack:
        extra += ((pack.stats if pack is not None else jnp.asarray([0, P], jnp.int32)),)
    return (logits, {"kv": kp, "tail": tails}, {"kv": vp}, None, None, *extra)
