"""SambaY decoder-hybrid-decoder (Phi-4-mini-flash-reasoning, ``model_type``
``phi4flash``; Ren et al. 2025, arXiv:2507.06607), the served forward.

FIVE kinds of mixer in one model, so a request's state is no longer a list
of KV blocks alone. With d the hidden size, L layers (a multiple of 4),
h = L / 2, every layer l is

    x <- x + Mix_l(LN(x));   x <- x + W2 (silu(g) * u),  [g, u] = W1 LN'(x)

LN a LayerNorm with gain and bias, then a final LN and logits = x E^T with
E the (tied) embedding. ``Mix_l`` by layer index (``layer_kinds``):

- l even, l <= h — SELECTIVE STATE SPACE (Mamba-1). [x, z] = W_in u;
  x <- silu(conv_k(x) + b) causal, depthwise, k = d_conv;
  [delta, B, C] = W_x x; Delta = softplus(W_dt delta + b_dt);
  A = -exp(A_log); s_t = exp(Delta_t A) s_{t-1} + (Delta_t x_t) (x) B_t;
  y_t = s_t C_t + D x_t; out = W_out (y * silu(z)). Layer h also hands
  m_t = y_t (before the gate) to the gated memory units after it.
- l odd, l < h — WINDOWED DIFFERENTIAL ATTENTION: a query at t sees keys
  t - window + 1 .. t. l = h + 1 — the same, FULL causal. q, k, v =
  W_qkv u + b (n_heads / n_kv_heads / n_kv_heads of head_dim). The heads split
  in halves: q1, q2, k1, k2, v1, v2. P1 = softmax(q1 k1^T / sqrt(hd)),
  P2 = softmax(q2 k2^T / sqrt(hd)); a1 = [P1 v1 | P1 v2], a2 = [P2 v1 | P2 v2]
  ("double heads", 2 hd wide); lambda = exp(lq1 . lk1) - exp(lq2 . lk2) +
  lambda0, lambda0 = 0.8 - 0.6 exp(-0.3 l); out = W_o ((1 - lambda0)
  RMSNorm(a1 - lambda a2)) + b_o, the RMSNorm over a double head with a gain.
- l odd, l > h + 1 — CROSS-ATTENTION to layer h + 1's cache: only q = W_q u
  + b; k and v are what layer h + 1 wrote for positions <= t; the same
  differential form with this layer's own lambda, sub-norm and W_o.
- l even, l > h — GATED MEMORY UNIT: W_out' (m_t * silu(W_in' u)). No state.

No positional encoding of any kind.

WHAT A REQUEST HOLDS (``cache_spec``): K/V planes for the h / 2 + 1 layers
that write them — one PACKED head per pair of half-heads, [k1_g | k2_g] and
[v1_g | v2_g], 2 hd wide: a query half-head padded with zeros on the other
half scores against its own half alone, and P [v1_g | v2_g] IS its double
head. So ``ops.paged_block_attention`` serves differential attention
unchanged, at exactly the published K/V bytes — and, per SLOT, a
convolution tail (d_conv - 1 inputs) and a float32 state for each of the
h / 2 + 1 recurrent layers. The pools are pytrees
(``k_pool = {"kv", "conv"}``, ``v_pool = {"kv", "ssm"}``) and a row of the
block table carries its slot's state index in one column past the blocks, so
whatever gathers table rows (the compacted width) carries the state along,
and nothing of it is ever gathered or scattered by row.

MASKED ADVANCE: ``n_real`` (B,) says how many of a row's T positions are
real. The state and the tail advance over those alone (``ops.selective_scan``:
dt = 0 leaves a state bit-equal); K/V of the others is parked in the trash
block — a fast-forward block's unused tail repeats the last real POSITION,
and here its hidden state is not the real one's, so its write must not land.

The layers run as a scan over the h / 2 periods of (state space, windowed
attention), the one period of (state space, full attention) — the same
function, traced once more with no window — and a scan over the h / 2 - 1
periods of (memory unit, cross-attention).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .llama import (MAX_BLOCK_DECODE_T, _qe, cache_planes, conv_window, gather_row_blocks, quantize_leaf,
                    rows_written, write_rows, write_walk)

F32 = jnp.float32
_NO_WINDOW = 1 << 30

# what a hybrid forward counts (summed over layers by the forward, over
# forwards by the chunk loop; ``scheduler`` publishes each under its name)
HYBRID_STATS = ("ssm.positions_advanced", "ssm.positions",
                "attn.window_blocks_walked", "attn.window_blocks_held",
                "attn.window_common_row_blocks")


class StateNotCarried(ValueError):
    """A serving feature that moves, shares, rolls back or shards K/V blocks
    alone was asked of a model whose requests also hold a recurrent state."""


@dataclass(frozen=True)
class SambaYConfig:
    vocab_size: int = 4096
    dim: int = 2560
    n_layers: int = 32
    n_heads: int = 40
    n_kv_heads: int = 20
    ffn_dim: int = 10240
    max_seq_len: int = 2048
    norm_eps: float = 1e-5
    window: int = 512
    d_inner: int = 5120
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160

    # what the engines read of any decoder's configuration
    n_experts = 0
    top_k = 0
    moe_impl = "dense"

    def __post_init__(self):
        if self.n_layers % 4 or self.n_heads % 2 or self.n_kv_heads % 2:
            raise ValueError("SambaY: layers in fours, heads in halves")

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def n_front(self) -> int:
        """Periods of (state space, attention): the self-decoder's, and the
        pair the cross-decoder opens with."""
        return self.n_layers // 4 + 1

    @property
    def n_back(self) -> int:
        """Periods of (memory unit, cross-attention)."""
        return self.n_layers // 4 - 1


PRESETS = {
    # every kind of layer twice (12 layers: ssm 0 2 4 6, window 1 3 5, full 7,
    # gmu 8 10, cross 9 11), a window shorter than a test prompt
    "sambay-test": SambaYConfig(dim=64, n_layers=12, n_heads=4, n_kv_heads=2, ffn_dim=128,
                                max_seq_len=256, window=24, d_inner=128, d_state=8,
                                d_conv=4, dt_rank=4),
}


def layer_kinds(cfg: SambaYConfig) -> list[str]:
    """The kind of each layer, by index (the docstring's rule)."""
    h = cfg.n_layers // 2
    return [("ssm" if l <= h else "gmu") if l % 2 == 0 else
            ("window" if l < h else "full" if l == h + 1 else "cross")
            for l in range(cfg.n_layers)]


def cache_spec(cfg: SambaYConfig) -> dict:
    """What the engine keeps between forwards: K/V planes of packed heads for
    the layers that write them, and a SLOT's convolution tail and float32
    state for each recurrent layer (``models.family`` has the shape)."""
    kv = {"kv": (cfg.n_front, cfg.n_kv_heads // 2, 2 * cfg.head_dim)}
    return cache_planes(
        kv, kv, by_name=True,
        slot_k={"conv": ((cfg.n_front, cfg.d_conv - 1, cfg.d_inner), jnp.bfloat16)},
        slot_v={"ssm": ((cfg.n_front, cfg.d_state, cfg.d_inner), F32)})


# ---------------------------------------------------------------- params

_INT8 = ("in_proj", "out_proj", "wqkv", "wq", "wo", "w1", "w2")
# the key that only this family's parameter tree has (``family.tree_owner``)
TREE_ROOT = "front"


def _mix_shapes(cfg: SambaYConfig, kind: str) -> dict:
    d, di, hd = cfg.dim, cfg.d_inner, cfg.head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    if kind == "ssm":
        return {"in_proj": (d, 2 * di), "conv_w": (cfg.d_conv, di), "conv_b": (di,),
                "x_proj": (di, cfg.dt_rank + 2 * cfg.d_state), "dt_proj": (cfg.dt_rank, di),
                "dt_bias": (di,), "A_log": (di, cfg.d_state), "D": (di,), "out_proj": (di, d)}
    if kind == "gmu":
        return {"in_proj": (d, di), "out_proj": (di, d)}
    proj = ({"wqkv": (d, (nq + 2 * nkv) * hd), "bqkv": ((nq + 2 * nkv) * hd,)}
            if kind == "attn" else {"wq": (d, nq * hd), "bq": (nq * hd,)})
    return {**proj, "wo": (nq * hd, d), "bo": (d,), "lam": (4, hd), "subln": (2 * hd,)}


def init_layer(cfg: SambaYConfig, key, kind: str, dtype=jnp.bfloat16) -> dict:
    """One layer of ``kind`` ("ssm" | "attn" | "gmu" | "cross"), unquantised.
    Matrices normal(0, fan_in^-0.5); the state-space parameters by the
    published initialisation (A_log = log(1..d_state) a channel, dt_bias the
    inverse softplus of a log-uniform draw in [1e-3, 1e-1], D = 1) — a normal
    draw there makes the state explode or vanish; lambda vectors normal(0,
    0.1); LayerNorm gains 1 and biases 0."""
    d, f = cfg.dim, cfg.ffn_dim
    shapes = _mix_shapes(cfg, kind)
    k1, k2, *rest = jax.random.split(key, len(shapes) + 2)
    ks = dict(zip(shapes, rest))

    def mat(k, shape):
        return (jax.random.normal(k, shape, F32) * shape[0] ** -0.5).astype(dtype)

    mix = {}
    for name, shape in shapes.items():
        if name == "A_log":
            mix[name] = jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=F32)), shape)
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(ks[name], shape, F32) * (math.log(0.1) - math.log(1e-3))
                         + math.log(1e-3))
            mix[name] = dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1
        elif name == "D":
            mix[name] = jnp.ones(shape, F32)
        elif name == "lam":
            mix[name] = jax.random.normal(ks[name], shape, F32) * 0.1
        elif name == "subln":
            mix[name] = jnp.ones(shape, dtype)
        elif len(shape) == 1:
            mix[name] = jnp.zeros(shape, dtype)
        else:
            mix[name] = mat(ks[name], shape)
    return {"ln1_g": jnp.ones((d,), dtype), "ln1_b": jnp.zeros((d,), dtype),
            "ln2_g": jnp.ones((d,), dtype), "ln2_b": jnp.zeros((d,), dtype),
            "w1": mat(k1, (d, 2 * f)), "w2": mat(k2, (f, d)), "mix": mix}


def init_params(cfg: SambaYConfig, key, dtype=jnp.bfloat16, each=None) -> dict:
    """Random init: ``front`` stacks the (state space, windowed attention)
    periods on a leading axis, ``mid`` is the (state space, full attention)
    pair, ``back`` stacks the (memory unit, cross-attention) periods. No
    ``lm_head``: the embedding is tied (``quantize_params`` adds an int8
    copy of it). ``each`` is applied to every layer as it is drawn, one
    period at a time (``quantize_layer``: a full-width model then never
    exists unquantised)."""
    k_embed, k_front, k_mid, k_back = jax.random.split(key, 4)
    each = each or (lambda layer: layer)

    def period(kinds):
        def make(k):
            ka, kb = jax.random.split(k)
            return {"a": each(init_layer(cfg, ka, kinds[0], dtype)),
                    "b": each(init_layer(cfg, kb, kinds[1], dtype))}
        return make

    d = cfg.dim
    return {
        "embed": (jax.random.normal(k_embed, (cfg.vocab_size, d), F32) * d ** -0.5).astype(dtype),
        "front": jax.lax.map(period(("ssm", "attn")), jax.random.split(k_front, cfg.n_front - 1)),
        "mid": period(("ssm", "attn"))(k_mid),
        "back": jax.lax.map(period(("gmu", "cross")), jax.random.split(k_back, cfg.n_back)),
        "final_g": jnp.ones((d,), dtype), "final_b": jnp.zeros((d,), dtype),
    }


def quantize_layer(layer: dict) -> dict:
    """Weight-only int8 of one layer's (or one stacked period's) large
    projections, per output channel; everything else as it is."""
    q = lambda t: {k: (quantize_leaf(v) if k in _INT8 else v) for k, v in t.items()}
    return {**q(layer), "mix": q(layer["mix"])}


def quantize_params(params: dict) -> dict:
    """``models.llama.quantize_params`` for this tree; the head is an int8
    copy of the tied embedding, per output channel (a vocabulary row)."""
    halves = lambda t: {h: quantize_layer(t[h]) for h in ("a", "b")}
    return {**params, **{k: halves(params[k]) for k in ("front", "mid", "back")},
            "lm_head": quantize_leaf(params["embed"].T)}


# ---------------------------------------------------------------- layers


def layer_norm(x, g, b, eps: float):
    xf = x.astype(F32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * g + b


def _mlp(p, x, cfg):
    with jax.named_scope("layer/ffn"):
        h = layer_norm(x, p["ln2_g"], p["ln2_b"], cfg.norm_eps)
        gu = _qe("btd,df->btf", h, p["w1"])
        act = (jax.nn.silu(gu[..., :cfg.ffn_dim]) * gu[..., cfg.ffn_dim:]).astype(x.dtype)
        return x + _qe("btf,fd->btd", act, p["w2"]).astype(x.dtype)


def ssm_mix(p, u, tail, planes, sidx, li, n_real, cfg, scan_impl: str):
    """The state-space mixer over (B, T, d) inputs ``u``. ``tail`` (B, k-1,
    di) are the convolution's inputs before position 0; ``planes`` the
    stacked float32 states. -> (out, m = y before the gate, the new tail,
    the planes with rows ``sidx`` of ``li`` advanced over ``n_real``)."""
    from ..ops.selective_scan import selective_scan, selective_scan_reference

    B, T = u.shape[:2]
    di, ds, R, K = cfg.d_inner, cfg.d_state, cfg.dt_rank, cfg.d_conv
    with jax.named_scope("layer/ssm/in_proj"):
        xz = _qe("btd,de->bte", u, p["in_proj"])
        x, z = xz[..., :di].astype(u.dtype), xz[..., di:]
    with jax.named_scope("layer/ssm/conv"):
        taps = lambda xp: jax.nn.silu(  # over (B, K-1+T, di)
            sum(xp[:, j:j + T].astype(F32) * p["conv_w"][j].astype(F32) for j in range(K))
            + p["conv_b"].astype(F32))
        x, new_tail = conv_window(tail, x, n_real, taps)
    with jax.named_scope("layer/ssm/scan"):
        dbc = jnp.einsum("bte,er->btr", x.astype(u.dtype), p["x_proj"],
                         preferred_element_type=F32)
        dt = jnp.einsum("btr,re->bte", dbc[..., :R].astype(u.dtype), p["dt_proj"],
                        preferred_element_type=F32)
        dt = jax.nn.softplus(dt + p["dt_bias"])
        dt = jnp.where(jnp.arange(T)[None, :, None] < n_real[:, None, None], dt, 0.0)
        scan = selective_scan if scan_impl == "pallas" else selective_scan_reference
        y, planes = scan(x, dt, -jnp.exp(p["A_log"]).T, dbc[..., R:R + ds], dbc[..., R + ds:],
                         planes, sidx, li)
        y = y + p["D"] * x
    with jax.named_scope("layer/ssm/out_proj"):
        out = _qe("bte,ed->btd", (y * jax.nn.silu(z)).astype(u.dtype), p["out_proj"])
    return out.astype(u.dtype), y, new_tail.astype(tail.dtype), planes


def gmu_mix(p, u, m):
    with jax.named_scope("layer/gmu"):
        g = jax.nn.silu(_qe("btd,de->bte", u, p["in_proj"]))
        return _qe("bte,ed->btd", (m * g).astype(u.dtype), p["out_proj"]).astype(u.dtype)


def pack_q(q, cfg: SambaYConfig):
    """(B, T, n_heads * hd) -> (B, T, n_heads, 2 hd): packed head g's four
    queries [q1_2g | 0], [q1_2g+1 | 0], [0 | q2_2g], [0 | q2_2g+1]."""
    B, T = q.shape[:2]
    hd, G = cfg.head_dim, cfg.n_kv_heads // 2
    halves = q.reshape(B, T, 2, G, -1, hd)
    z = jnp.zeros_like(halves[:, :, 0])
    packed = jnp.concatenate([jnp.concatenate([halves[:, :, 0], z], -1),
                              jnp.concatenate([z, halves[:, :, 1]], -1)], axis=3)
    return packed.reshape(B, T, cfg.n_heads, 2 * hd)


def pack_kv(k, cfg: SambaYConfig):
    """(B, T, n_kv_heads * hd) -> (B, T, n_kv_heads / 2, 2 hd): [k1_g | k2_g]."""
    B, T = k.shape[:2]
    halves = k.reshape(B, T, 2, cfg.n_kv_heads // 2, cfg.head_dim)
    return jnp.concatenate([halves[:, :, 0], halves[:, :, 1]], axis=-1)


def diff_out(p, a, l, cfg: SambaYConfig, dtype):
    """The differential combine of packed attention output ``a`` (B, T,
    n_heads, 2 hd), a1 and a2 side by side in each packed head's four, for
    layer index ``l``; then W_o and its bias."""
    B, T = a.shape[:2]
    G = cfg.n_kv_heads // 2
    a = a.astype(F32).reshape(B, T, G, 2, -1, 2 * cfg.head_dim)
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * l.astype(F32))
    lam = (jnp.exp(jnp.sum(p["lam"][0] * p["lam"][1])) - jnp.exp(jnp.sum(p["lam"][2] * p["lam"][3]))
           + lam0)
    x = a[:, :, :, 0] - lam * a[:, :, :, 1]
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + cfg.norm_eps)
    x = (x * p["subln"].astype(F32) * (1.0 - lam0)).astype(dtype).reshape(B, T, -1)
    return (_qe("bth,hd->btd", x, p["wo"]) + p["bo"].astype(F32)).astype(dtype)


def _attend(q, kl, vl, positions, window, scale: float):
    """XLA attention of packed q (B, T, nq, w) over gathered (B, S, nkv, w)
    keys whose slot IS their position: causal, inside ``window``."""
    B, T, nq, w = q.shape
    S, nkv = kl.shape[1], kl.shape[2]
    qg = q.reshape(B, T, nkv, nq // nkv, w)
    s = jnp.einsum("btkgh,bskh->bkgts", qg, kl, preferred_element_type=F32) * scale
    key_pos = jnp.arange(S)[None, None, :]
    qp = positions[:, :, None]
    seen = (key_pos <= qp) & (key_pos > qp - window)
    s = jnp.where(seen[:, None, None], s, -1e30)
    # float32 probabilities, as the block kernel's: what follows takes a
    # difference of outputs, which a bf16 rounding here would come through
    out = jnp.einsum("bkgts,bskh->btkgh", jax.nn.softmax(s, axis=-1), vl.astype(F32))
    return out.reshape(B, T, nq, w)


# ---------------------------------------------------------------- forward


def forward_paged(params, cfg: SambaYConfig, tokens, positions, k_pool, v_pool, block_tables, *,
                  attn_impl: str = "pallas", write_mask=None, trash_idx=None,
                  fresh_block: bool = False, gather_blocks: int | None = None, n_real=None,
                  logit_pos=None, ffn_pack: int = 0, hybrid_stats: bool = False,
                  attn_stats: bool = False, kv_stats: bool = False):
    """``models.llama.forward_paged`` for this model (it hands on to the
    family's module, with the keywords every family takes: ``fresh_block`` is
    a promise this forward does not need, ``ffn_pack`` one its family's table
    refuses before it gets here): ``k_pool`` /
    ``v_pool`` are the pytrees of the module docstring, ``block_tables`` (B,
    max_blocks + 1) with the state index last. ``logit_pos`` (B,): the head
    runs on that one position of each row, logits (B, 1, V) — the chunk
    program reads one row of a 1 + W block, and the head is 200 064 wide.
    -> (logits, k_pool, v_pool, None, None), then ``HYBRID_STATS`` (5,) with
    ``hybrid_stats``, then ``ops.ATTN_STATS`` summed over the attention
    layers with ``attn_stats``, then ``llama.KV_STATS`` over the ``n_front`` K/V
    planes with ``kv_stats``.

    Attention: T <= ``MAX_BLOCK_DECODE_T`` under "pallas" goes through
    ``ops.paged_block_attention`` (T = 1 too), everything else gathers the
    row's covered blocks and attends in XLA — a fresh block as well: its
    K/V is scattered first, so the gather reads it back."""
    from ..ops import common_block_split, paged_block_attention

    B, T = tokens.shape
    kp, vp, conv, ssm = k_pool["kv"], v_pool["kv"], k_pool["conv"], v_pool["ssm"]
    N, bs, G, w = kp.shape[1:]
    tables, sidx = block_tables[:, :-1].astype(jnp.int32), block_tables[:, -1].astype(jnp.int32)
    M = tables.shape[1]
    live = jnp.ones((B,), bool) if write_mask is None else write_mask
    told = n_real is not None
    n_real = jnp.where(live, n_real if told else T, 0).astype(jnp.int32)
    real = jnp.arange(T)[None, :] < n_real[:, None]
    nb = gather_blocks if gather_blocks is not None else M
    scale = cfg.head_dim ** -0.5
    block_decode = attn_impl == "pallas" and T <= MAX_BLOCK_DECODE_T
    scan_impl = "pallas" if attn_impl == "pallas" else "xla"
    nq, nkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    full_plane = cfg.n_front - 1

    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    # where each position's K/V lands, as (block, offset): the pool is indexed
    # as it is shaped. Through a flat (N * bs) view, as the dense decoder
    # writes, XLA relaid these 10-head planes out around every scatter of a
    # prefill: four copies of a whole pool a layer, 50 of a 100 ms admission
    # (my chip run, PR 32)
    blk = jnp.take_along_axis(tables, jnp.minimum(positions // bs, M - 1), axis=1)
    park = jnp.zeros((B,), jnp.int32) if trash_idx is None else trash_idx.astype(jnp.int32)
    w_blk = jnp.where(real, blk, park[:, None] // bs)
    w_off = jnp.where(real, positions % bs, park[:, None] % bs)
    # told its rows' real positions, the write walks tiles of them (``llama.write_rows``)
    with jax.named_scope("layer/kv_write"):
        write_tiles, write_at = write_walk(n_real if told else None, T, (w_blk, w_off))

    # what the windowed layers walk, and what they would without a window
    qmin, qmax = jnp.min(positions, axis=1), jnp.max(positions, axis=1)
    held = jnp.sum(jnp.where(live, qmax // bs + 1, 0))
    first = jnp.maximum(qmin - (cfg.window - 1), 0) // bs
    walked = jnp.sum(jnp.where(live, qmax // bs - first + 1, 0))
    split_full = split_win = None
    if block_decode:
        with jax.named_scope("layer/attn/split"):
            split_full = common_block_split(tables, positions, live, bs, n_real=n_real)
            split_win = common_block_split(tables, positions, live, bs, window=cfg.window,
                                           n_real=n_real)

    def attend(q, kp, vp, plane, windowed: bool):
        if block_decode:
            return paged_block_attention(
                q, kp, vp, tables, positions, plane, live,
                split_win if windowed else split_full,
                jnp.int32(cfg.window) if windowed else None, n_real, scale=scale, out_dtype=F32)
        with jax.named_scope("kv_gather"):
            tbl = tables[:, :nb]
            kl = gather_row_blocks(kp, plane, tbl).reshape(B, nb * bs, G, w)
            vl = gather_row_blocks(vp, plane, tbl).reshape(B, nb * bs, G, w)
        return _attend(q, kl, vl, positions, cfg.window if windowed else _NO_WINDOW, scale)

    def period(x, kp, vp, conv, ssm, p, i, windowed: bool):
        """Layers 2 i (state space) and 2 i + 1 (attention, K/V plane i)."""
        pa, pb = p["a"], p["b"]
        u = layer_norm(x, pa["ln1_g"], pa["ln1_b"], cfg.norm_eps)
        out, m, tail, ssm = ssm_mix(pa["mix"], u, conv[i, sidx], ssm, sidx, i, n_real, cfg, scan_impl)
        conv = conv.at[i, sidx].set(tail)
        x = _mlp(pa, x + out, cfg)
        pm = pb["mix"]
        with jax.named_scope("layer/attn_qkv"):
            u = layer_norm(x, pb["ln1_g"], pb["ln1_b"], cfg.norm_eps)
            qkv = (_qe("btd,dh->bth", u, pm["wqkv"]) + pm["bqkv"].astype(F32)).astype(x.dtype)
            q = pack_q(qkv[..., :nq], cfg)
            k, v = pack_kv(qkv[..., nq:nq + nkv], cfg), pack_kv(qkv[..., nq + nkv:], cfg)
        with jax.named_scope("layer/kv_write"):
            kp, vp = write_rows(kp, vp, i, k.astype(kp.dtype), v.astype(vp.dtype), write_at, write_tiles)
        with jax.named_scope("layer/attn/window" if windowed else "layer/attn/full"):
            a = attend(q, kp, vp, i, windowed)
        with jax.named_scope("layer/attn_out"):
            x = x + diff_out(pm, a, 2 * i + 1, cfg, x.dtype)
        return _mlp(pb, x, cfg), kp, vp, conv, ssm, m

    def front(carry, xs):
        *carry, _ = period(*carry, *xs, windowed=True)
        return tuple(carry), None

    with jax.named_scope("layers"):
        (x, kp, vp, conv, ssm), _ = jax.lax.scan(
            front, (x, kp, vp, conv, ssm),
            (params["front"], jnp.arange(full_plane, dtype=jnp.int32)))
    with jax.named_scope("mid"):
        x, kp, vp, conv, ssm, m = period(x, kp, vp, conv, ssm, params["mid"],
                                         jnp.int32(full_plane), windowed=False)

    def back(x, xs):
        """Layers 2 n_front + 2 j (memory unit) and the next (cross-attention)."""
        p, j = xs
        pa, pb = p["a"], p["b"]
        u = layer_norm(x, pa["ln1_g"], pa["ln1_b"], cfg.norm_eps)
        x = _mlp(pa, x + gmu_mix(pa["mix"], u, m), cfg)
        pm = pb["mix"]
        with jax.named_scope("layer/attn_qkv"):
            u = layer_norm(x, pb["ln1_g"], pb["ln1_b"], cfg.norm_eps)
            q = pack_q((_qe("btd,dh->bth", u, pm["wq"]) + pm["bq"].astype(F32)).astype(x.dtype), cfg)
        with jax.named_scope("layer/attn/cross"):
            a = attend(q, kp, vp, jnp.int32(full_plane), False)
        with jax.named_scope("layer/attn_out"):
            x = x + diff_out(pm, a, 2 * cfg.n_front + 2 * j + 1, cfg, x.dtype)
        return _mlp(pb, x, cfg), None

    with jax.named_scope("layers_cross"):
        x, _ = jax.lax.scan(back, x, (params["back"], jnp.arange(cfg.n_back, dtype=jnp.int32)))

    with jax.named_scope("final_norm"):
        if logit_pos is not None:
            x = jnp.take_along_axis(x, logit_pos[:, None, None], axis=1)
        x = layer_norm(x, params["final_g"], params["final_b"], cfg.norm_eps)
    with jax.named_scope("lm_head"):
        if "lm_head" in params:
            logits = _qe("btd,dv->btv", x, params["lm_head"])
        else:
            logits = jnp.einsum("btd,vd->btv", x, params["embed"], preferred_element_type=F32)
    extra = ()
    # what the two splits' common passes took (row-blocks, positions handed): the full and the
    # cross layers' leading blocks; the windowed layers' range, off the rows' own walks
    nothing = (jnp.int32(0),) * 2
    common, handed = split_full.counts[::2] if block_decode else nothing
    ranged, win_handed = split_win.counts[::2] if block_decode else nothing
    if hybrid_stats:
        extra += (jnp.stack([cfg.n_front * jnp.sum(n_real), jnp.int32(cfg.n_front * B * T),
                             full_plane * walked, full_plane * held,
                             full_plane * ranged]).astype(jnp.int32),)
    if attn_stats:
        # row-blocks over ALL attention reads of the forward: the windowed
        # layers attend what lies inside their rows' windows (a common range of it
        # once for its riders: ``HYBRID_STATS``' to count), the full layer and the
        # cross layers ride the common pass where the split has one; the positions
        # handed to a common pass are every read's
        n_full = 1 + cfg.n_back
        extra += (jnp.stack([n_full * common, n_full * held + full_plane * walked,
                             n_full * handed + full_plane * win_handed]).astype(jnp.int32),)
    if kv_stats:
        extra += (cfg.n_front * rows_written(write_tiles, positions)[None],)
    return (logits, {"kv": kp, "conv": conv}, {"kv": vp, "ssm": ssm}, None, None, *extra)
