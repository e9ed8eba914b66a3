"""Nemotron-H hybrid decoder (``model_type`` ``nemotron_h``: Nemotron-3-Super-
120B-A12B), the served forward.

ONE block a layer — a mixer OR a feed-forward part, never both — by the
published ``hybrid_override_pattern`` (a string over ``M`` ``E`` ``*``; the
configuration's ``pattern`` is the characters served):

    x <- x + Block_l(RMSNorm(x))          final RMSNorm, untied head

- ``M``, MAMBA-2 (H heads of P channels, d_inner = H P; G groups of H / G
  heads, N states, convolution width K): [z | xBC | dt] = u W_in
  (d_inner | d_inner + 2 G N | H columns); xBC <- silu(conv_K(xBC) + b),
  causal and depthwise; [x | B | C] = xBC; Delta = softplus(dt + dt_bias) a
  head; A = -exp(A_log), ONE scalar a head; the state S (H, P, N):
  S_t = exp(Delta_t A) S_{t-1} + Delta_t x_t (x) B_t (a head reads its
  group's B and C); y_t = S_t C_t + D x_t; o = RMSNorm_group(y * silu(z)) g
  — the gate BEFORE the norm, over groups of d_inner / G — ; out = o W_out.
- ``*``, ATTENTION: grouped-query, causal softmax at head_dim^-0.5, no bias
  and NO rotary (the state-space layers carry order).
- ``E``, LATENT experts: s = sigmoid(h W_r) over all ``n_experts`` in
  float32; the ``top_k`` largest of s + b; gates s[picked] / sum s[picked]
  x ``router_scale``; l = h W_fc1 (dim -> ``moe_latent``); expert e:
  relu(l W_up,e)^2 W_down,e in the latent, TWO planes and no gate matrix;
  out = (sum g_e expert_e(l)) W_fc2 + relu(h W_s,up)^2 W_s,down. The router
  and the shared expert read the ``dim``-wide h; only the routed experts
  live in the latent. A chip that holds a SHARE (``experts_held`` from
  ``first_expert``) sums its own experts' part: ``fc2`` is linear, so the
  shares' outputs still add up.

WHAT A REQUEST HOLDS (``cache_spec``): K/V planes for the ``*`` layers alone
and, per SLOT, a convolution tail (K - 1 inputs of d_inner + 2 G N) and a
float32 state (H, P, N) for each ``M`` layer — 4.19 MB a layer at the
published sizes. Pools and block tables as ``models.sambay``'s: pytrees
``{"kv", "conv"}`` / ``{"kv", "ssm"}``, the slot's state index one column
past a row's blocks.

MASKED ADVANCE as ``models.sambay``'s: ``n_real`` (B,) real positions a row;
state and tail advance over those alone (``ops.ssd_scan``: dt = 0 is exact,
a row with none is not moved), K/V of the others is parked.

THE ``E`` LAYERS RUN ON THE REAL POSITIONS, always: a layer's input rows are
gathered by ``llama.RowTiles`` (tiles of ``ffn_pack`` packed rows — one tile
in nearly every decode forward —, of ``ADMIT_TILE`` where the caller names
none: an admission's block) and a tile's slots
behind the last real one are dispatched to no expert — the router never
routes a bucket's filler, and no filler row takes an expert's tile. The ``M`` layers run their projections at the
block's width: conv and scan need a row's positions side by side.

Layers: the runs of (``M``, ``E``) pairs are ONE loop each over the stacked
leaves of their kind (the pattern's ``MEMEME`` traces once), what stands
between them is unrolled.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp

from .llama import (EXPERT_ACTS, MAX_BLOCK_DECODE_T, _moe_ffn, _qe, cache_planes, conv_window, gather_row_blocks,
                    moe_stat_names, quantize_leaf, rms_norm, row_tiles, rows_written, write_rows, write_walk)
from .sambay import _NO_WINDOW, StateNotCarried, _attend  # noqa: F401  (the family's error class)

F32 = jnp.float32

# what a forward counts beside the routed rows and the attention row-blocks:
# positions the states advanced over, positions computed, live rows x M layers
# (each moves its 4.19 MB once in and once out: the scan's floor)
HYBRID_STATS = ("ssm.positions_advanced", "ssm.positions", "ssm.state_rows_moved")

# the key that only this family's parameter tree has (``family.tree_owner``)
TREE_ROOT = "mamba"

# packed rows a walk of the E layers takes where the caller names no width (an
# admission: a group's (4, 64) block whole; the prefix's chunk in four). Every
# tile of ~100 real positions x 22 picks touches nearly all 128 held experts,
# 0.7 GB of planes a layer: a group of ~100 real positions walked in two tiles
# of 96 read them twice, 24 ms a call where a decode forward is 22 (my chip
# run, PR 47)
ADMIT_TILE = 256

# faults of this block's own mechanisms, planted in the served program for the
# comparison's limit to be set against (``benchmark/tools/ssd_check.py``, which
# plants two more by rebinding: the bias in the gates' sum, a state not restored)
FAULTS = ("bf16_state", "no_scale", "no_renorm", "silu_experts", "norm_before_gate")
_FAULT_CFG = {"no_scale": {"router_scale": 1.0}, "no_renorm": {"norm_topk": False},
              "silu_experts": {"expert_form": "silu"}}


@dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 4096
    dim: int = 4096
    pattern: str = "MEMEMEM*EMEMEMEM*EMEME"  # the layers SERVED, a kind each
    n_heads: int = 32
    n_kv_heads: int = 2
    head_size: int = 128
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    d_state: int = 128
    d_conv: int = 4
    n_experts: int = 512  # the ROUTER's width
    top_k: int = 22
    experts_held: int = 0  # this chip's share, ids ``first_expert`` onward; 0 = all
    first_expert: int = 0
    moe_latent: int = 1024
    ffn_dim: int = 2688  # a routed expert's width, in the latent
    shared_ffn_dim: int = 5376
    norm_topk: bool = True
    router_scale: float = 5.0
    norm_eps: float = 1e-5
    group_norm_eps: float = 1e-5
    max_seq_len: int = 2048
    moe_impl: str = "auto"
    # relu(l W_up)^2 W_down: two planes an expert, no gate (``llama.EXPERT_ACTS``)
    expert_form: str = "relu2"

    # what else ``llama._moe_ffn_grouped`` reads of a routed model's configuration
    router_fn = "sigmoid"
    router_bias = True

    def __post_init__(self):
        if set(self.pattern) - set("ME*") or "M" not in self.pattern:
            raise ValueError(f"pattern {self.pattern!r}: M | E | * a layer, a state-space layer among them")
        if self.mamba_heads % self.n_groups or self.n_heads % self.n_kv_heads:
            raise ValueError("heads in whole groups")
        if self.experts_held and not self.first_expert + self.experts_held <= self.n_experts:
            raise ValueError(f"experts held {self.first_expert}..{self.first_expert + self.experts_held} "
                             f"of {self.n_experts}")

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def head_dim(self) -> int:
        return self.head_size

    @property
    def n_held(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)


PRESETS = {
    # every kind (the published order's opening, cut short), two groups of two heads, a
    # latent narrower than the hidden size, a share that starts past expert 0, top-k 3
    "nemotron-h-test": NemotronHConfig(
        vocab_size=1024, dim=64, pattern="MEME*EM", n_heads=4, n_kv_heads=2, head_size=16,
        mamba_heads=4, mamba_head_dim=8, n_groups=2, d_state=16, n_experts=16, top_k=3,
        experts_held=8, first_expert=4, moe_latent=32, ffn_dim=48, shared_ffn_dim=96,
        max_seq_len=256),
}


def segments(pattern: str) -> tuple[tuple[str, int, int, int], ...]:
    """The pattern as the forward walks it: ("pairs", first M, first E, n) for
    a run of n >= 2 (M, E) pairs — one loop —, else (kind, its index among
    its kind, -1, 1)."""
    out, seen = [], {"M": 0, "E": 0, "*": 0}
    for m in re.finditer(r"(?:ME){2,}|.", pattern):
        s = m.group()
        if len(s) > 1:
            n = len(s) // 2
            out.append(("pairs", seen["M"], seen["E"], n))
            seen["M"] += n
            seen["E"] += n
        else:
            out.append((s, seen[s], -1, 1))
            seen[s] += 1
    return tuple(out)


def cache_spec(cfg: NemotronHConfig) -> dict:
    """K/V planes by head for the attention layers alone; a SLOT's convolution
    tail and float32 state for each state-space layer (``models.family`` has
    the shape)."""
    kv = {"kv": (max(cfg.count("*"), 1), cfg.n_kv_heads, cfg.head_dim)}
    nm = cfg.count("M")
    return cache_planes(
        kv, kv, by_name=True,
        slot_k={"conv": ((nm, cfg.d_conv - 1, cfg.conv_dim), jnp.bfloat16)},
        slot_v={"ssm": ((nm, cfg.mamba_heads, cfg.mamba_head_dim, cfg.d_state), F32)})


# ---------------------------------------------------------------- params

_INT8 = ("in_proj", "out_proj", "wq", "wk", "wv", "wo", "fc1", "fc2", "moe_up", "moe_down",
         "shared_up", "shared_down")
KINDS = {"M": "mamba", "E": "experts", "*": "attn"}


def init_params(cfg: NemotronHConfig, key, dtype=jnp.bfloat16, *, quant: bool = False,
                embed_std: float | None = None, bias_std: float = 0.1, routed_gain: float = 1.0) -> dict:
    """Random init, the leaves of a kind stacked on a leading axis
    (``"mamba"``, ``"experts"``, ``"attn"``). Matrices normal(0, fan_in^-0.5);
    the state-space parameters by the PUBLISHED initialisation (A uniform in
    [1, 16] a head, dt_bias the inverse softplus of a log-uniform draw in
    [1e-3, 1e-1] floored at 1e-4, D = 1) — a normal draw there makes the
    state explode or vanish; the convolution normal(0, K^-0.5) with a bias
    normal(0, 0.1); the router's selection bias normal(0, ``bias_std``); a routed
    expert's down projection times ``routed_gain``; norm gains 1. With ``quant`` every large matrix becomes its int8 leaf AS IT IS
    DRAWN, layer by layer and expert by expert under ``lax.map``: a full-width
    model never exists unquantised."""
    d, di, cd = cfg.dim, cfg.d_inner, cfg.conv_dim
    H, K, hd = cfg.mamba_heads, cfg.d_conv, cfg.head_dim
    nq, nkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    lat, f, sf, E = cfg.moe_latent, cfg.ffn_dim, cfg.shared_ffn_dim, cfg.n_experts

    def mat(k, shape, gain: float = 1.0):
        w = jax.random.normal(k, shape, F32) * (shape[0] ** -0.5 * gain)
        return quantize_leaf(w) if quant else w.astype(dtype)

    def mamba(k):
        ks = jax.random.split(k, 7)
        dt = jnp.exp(jax.random.uniform(ks[2], (H,), F32) * (math.log(0.1) - math.log(1e-3))
                     + math.log(1e-3))
        dt = jnp.maximum(dt, 1e-4)
        return {"norm": jnp.ones((d,), dtype), "in_proj": mat(ks[0], (d, di + cd + H)),
                "conv_w": (jax.random.normal(ks[1], (K, cd), F32) * K ** -0.5).astype(dtype),
                "conv_b": (jax.random.normal(ks[3], (cd,), F32) * 0.1).astype(dtype),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1
                "A_log": jnp.log(jax.random.uniform(ks[4], (H,), F32, 1.0, 16.0)),
                "D": jnp.ones((H,), F32), "gnorm": jnp.ones((di,), dtype),
                "out_proj": mat(ks[5], (di, d))}

    def experts(k):
        ks = jax.random.split(k, 7)

        def one(ke):
            ku, kd = jax.random.split(ke)
            return {"moe_up": mat(ku, (lat, f)), "moe_down": mat(kd, (f, lat), routed_gain)}

        return {"norm": jnp.ones((d,), dtype),
                "router": (jax.random.normal(ks[0], (d, E), F32) * d ** -0.5).astype(dtype),
                "router_bias": bias_std * jax.random.normal(ks[1], (E,), F32),
                "fc1": mat(ks[2], (d, lat)), "fc2": mat(ks[3], (lat, d)),
                "shared_up": mat(ks[4], (d, sf)), "shared_down": mat(ks[5], (sf, d)),
                **jax.lax.map(one, jax.random.split(ks[6], cfg.n_held))}

    def attn(k):
        ks = jax.random.split(k, 4)
        return {"norm": jnp.ones((d,), dtype), "wq": mat(ks[0], (d, nq)), "wk": mat(ks[1], (d, nkv)),
                "wv": mat(ks[2], (d, nkv)), "wo": mat(ks[3], (nq, d))}

    k_embed, k_head, *kk = jax.random.split(key, 5)
    std = d ** -0.5 if embed_std is None else embed_std
    params = {"embed": (jax.random.normal(k_embed, (cfg.vocab_size, d), F32) * std).astype(dtype),
              "final_norm": jnp.ones((d,), dtype), "lm_head": mat(k_head, (d, cfg.vocab_size))}
    for (kind, name), make, k in zip(KINDS.items(), (mamba, experts, attn), kk):
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


relu2 = EXPERT_ACTS["relu2"]  # the shared expert's activation is the routed experts' own


def group_norm(y, g, groups: int, eps: float):
    """RMSNorm over each of ``groups`` equal slices of the last axis, one gain."""
    yf = y.astype(F32).reshape(*y.shape[:-1], groups, -1)
    yf = yf * jax.lax.rsqrt(jnp.mean(yf * yf, axis=-1, keepdims=True) + eps)
    return yf.reshape(y.shape) * g.astype(F32)


def mamba_mix(p, u, tail, planes, sidx, li, n_real, cfg: NemotronHConfig, scan_impl: str,
              fault: str | None = None):
    """The Mamba-2 mixer over (B, T, d) normed inputs ``u``; ``p`` one layer's
    leaves. ``tail`` (B, K-1, conv_dim) the convolution's inputs before
    position 0; ``planes`` the stacked float32 states. -> (out, the new tail,
    the planes with the live rows' ``sidx`` of ``li`` advanced over ``n_real``)."""
    from ..ops.ssd_scan import ssd_scan, ssd_scan_reference

    B, T = u.shape[:2]
    di, cd, K = cfg.d_inner, cfg.conv_dim, cfg.d_conv
    H, P, G, N = cfg.mamba_heads, cfg.mamba_head_dim, cfg.n_groups, cfg.d_state
    with jax.named_scope("layer/ssm/in_proj"):
        zxd = _qe("btd,de->bte", u, p["in_proj"])
        z, xbc, dt = zxd[..., :di], zxd[..., di:di + cd].astype(u.dtype), zxd[..., di + cd:]
    with jax.named_scope("layer/ssm/conv"):
        taps = lambda xp: jax.nn.silu(  # over (B, K-1+T, cd)
            sum(xp[:, j:j + T].astype(F32) * p["conv_w"][j].astype(F32) for j in range(K))
            + p["conv_b"].astype(F32))
        xbc, new_tail = conv_window(tail, xbc, n_real, taps)
    with jax.named_scope("layer/ssm/scan"):
        x = xbc[..., :di].reshape(B, T, H, P)
        bm = xbc[..., di:di + G * N].reshape(B, T, G, N)
        cm = xbc[..., di + G * N:].reshape(B, T, G, N)
        dt = jax.nn.softplus(dt + p["dt_bias"])
        dt = jnp.where(jnp.arange(T)[None, :, None] < n_real[:, None, None], dt, 0.0)
        if fault == "bf16_state":  # the state a request carries, rounded where it is read
            planes = planes.at[li, sidx].set(planes[li, sidx].astype(jnp.bfloat16).astype(F32))
        scan = ssd_scan if scan_impl == "pallas" else ssd_scan_reference
        y, planes = scan(x, dt, -jnp.exp(p["A_log"]), bm, cm, planes, sidx, li, n_real)
        y = (y + p["D"][:, None] * x).reshape(B, T, di)
    with jax.named_scope("layer/ssm/norm"):
        if fault == "norm_before_gate":
            o = group_norm(y, p["gnorm"], G, cfg.group_norm_eps) * jax.nn.silu(z)
        else:
            o = group_norm(y * jax.nn.silu(z), p["gnorm"], G, cfg.group_norm_eps)
    with jax.named_scope("layer/ssm/out_proj"):
        out = _qe("bte,ed->btd", o.astype(u.dtype), p["out_proj"])
    return out.astype(u.dtype), new_tail.astype(tail.dtype), planes


def expert_layer(p, h, cfg: NemotronHConfig, fault: str | None = None, n_rows=None):
    """The latent expert layer over normed rows ``h`` (b, t, d) -> (its sum,
    the layer's ``llama._moe_stats``). Position-wise; ``n_rows``: the leading
    rows that are real (the rest of a tile is filler and is dispatched
    nowhere). ``p``: the layer's small leaves sliced, the expert planes STACKED
    beside the layer's index (``llama._moe_ffn_grouped`` hands both to the
    kernel)."""
    cfg = replace(cfg, **_FAULT_CFG.get(fault, {}))
    with jax.named_scope("layer/ffn"):
        with jax.named_scope("latent_down"):
            lat = _qe("btd,dl->btl", h, p["fc1"]).astype(h.dtype)
        y, stats = _moe_ffn(p, h, cfg, lat=lat, n_rows=n_rows)
        with jax.named_scope("latent_up"):
            y = _qe("btl,ld->btd", y, p["fc2"])
        with jax.named_scope("shared"):
            act = relu2(_qe("btd,df->btf", h, p["shared_up"])).astype(h.dtype)
            y = y + _qe("btf,fd->btd", act, p["shared_down"])
    return y.astype(h.dtype), stats


# ---------------------------------------------------------------- forward


def forward_paged(params, cfg: NemotronHConfig, tokens, positions, k_pool, v_pool, block_tables, *,
                  attn_impl: str = "pallas", write_mask=None, trash_idx=None,
                  fresh_block: bool = False, gather_blocks: int | None = None, n_real=None,
                  logit_pos=None, ffn_pack: int = 0, hybrid_stats: bool = False,
                  moe_stats: bool = False, attn_stats: bool = False, kv_stats: bool = False,
                  fault: str | None = None):
    """``models.llama.forward_paged`` for this model (``fresh_block`` is a
    promise this forward does not need): ``k_pool`` / ``v_pool`` the pytrees
    of the module docstring, ``block_tables`` (B, max_blocks + 1) with the
    state index last; ``logit_pos`` (B,): the head on that one position a row.
    -> (logits, k_pool, v_pool, None, None), then in the family's order:
    ``HYBRID_STATS`` (3,), the routed layers' ``llama.MOE_SHARE_STATS`` (or
    ``MOE_STATS``), ``ops.ATTN_STATS``, ``llama.KV_STATS``, and LAST with ``ffn_pack``
    ``llama.FFN_STATS``. ``fault`` PLANTS one (``FAULTS``); None everywhere else."""
    from ..ops import common_block_split, paged_block_attention

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r}: one of {FAULTS}")
    B, T = tokens.shape
    kp, vp, conv, ssm = k_pool["kv"], v_pool["kv"], k_pool["conv"], v_pool["ssm"]
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
    P = B * T

    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    # where each position's K/V lands, as (block, offset) (``models.sambay``)
    blk = jnp.take_along_axis(tables, jnp.minimum(positions // bs, M - 1), axis=1)
    park = jnp.zeros((B,), jnp.int32) if trash_idx is None else trash_idx.astype(jnp.int32)
    w_blk = jnp.where(real, blk, park[:, None] // bs)
    w_off = jnp.where(real, positions % bs, park[:, None] % bs)
    # told its rows' real positions, the write walks tiles of them (``llama.write_rows``)
    with jax.named_scope("layer/kv_write"):
        write_tiles, write_at = write_walk(n_real if told else None, T, (w_blk, w_off))
    split = None
    if block_decode and cfg.count("*"):
        with jax.named_scope("layer/attn/split"):
            split = common_block_split(tables, positions, live, bs, n_real=n_real)
    # the real positions, packed: what every E layer runs on
    with jax.named_scope("layer/ffn/pack"):
        rows = row_tiles(n_real, T, ffn_pack or ADMIT_TILE)
        n_pos = jnp.sum(n_real)

    def m_layer(x, conv, ssm, mi):
        p = _leaf(params["mamba"], mi)
        with jax.named_scope("layer/ssm/in_proj"):
            u = rms_norm(x, p["norm"], cfg.norm_eps)
        out, tail, ssm = mamba_mix(p, u, conv[mi, sidx], ssm, sidx, mi, n_real, cfg, scan_impl, fault)
        with jax.named_scope("layer/ssm/conv"):
            conv = conv.at[mi, sidx].set(tail)
        return x + out, conv, ssm

    stacked = {k: params["experts"][k] for k in ("moe_up", "moe_down")} if cfg.count("E") else {}

    def e_layer(x, ei):
        small = {k: v for k, v in params["experts"].items() if k not in stacked}
        xf = x.reshape(P, -1)

        def tile(i, carry):
            out, st = carry
            p = {**_leaf(small, ei), **stacked, "layer": ei}
            with jax.named_scope("layer/ffn/pack"):
                h = rms_norm(xf[rows.cut(rows.idx, i)][None], p["norm"], cfg.norm_eps)
            first = jnp.minimum(i * rows.tile, P - rows.tile)  # (``RowTiles.cut`` clamps alike)
            y, s = expert_layer(p, h, cfg, fault, n_rows=jnp.clip(n_pos - first, 0, rows.tile))
            with jax.named_scope("layer/ffn/unpack"):
                return rows.put(out, y[0], i), st + s

        st0 = jnp.zeros((len(moe_stat_names(cfg)),), jnp.int32)
        with jax.named_scope("layer/ffn/walk"):  # the walk's ``while`` itself
            out, st = jax.lax.fori_loop(0, rows.n_tiles, tile, (jnp.zeros_like(xf), st0))
        with jax.named_scope("layer/ffn/unpack"):
            return x + out[rows.inv], st

    def a_layer(x, kp, vp, ai):
        p = _leaf(params["attn"], ai)
        with jax.named_scope("layer/attn_qkv"):
            u = rms_norm(x, p["norm"], cfg.norm_eps)
            q = _qe("btd,dh->bth", u, p["wq"]).astype(x.dtype).reshape(B, T, nq, hd)
            k = _qe("btd,dh->bth", u, p["wk"]).astype(kp.dtype).reshape(B, T, nkv, hd)
            v = _qe("btd,dh->bth", u, p["wv"]).astype(vp.dtype).reshape(B, T, nkv, hd)
        with jax.named_scope("layer/kv_write"):
            kp, vp = write_rows(kp, vp, ai, k, v, write_at, write_tiles)
        with jax.named_scope("layer/attn/full"):
            if block_decode:
                a = paged_block_attention(q, kp, vp, tables, positions, ai, live, split, None,
                                          n_real, scale=scale, out_dtype=F32)
            else:
                with jax.named_scope("kv_gather"):
                    tbl = tables[:, :nb]
                    kl = gather_row_blocks(kp, ai, tbl).reshape(B, nb * bs, nkv, hd)
                    vl = gather_row_blocks(vp, ai, tbl).reshape(B, nb * bs, nkv, hd)
                a = _attend(q, kl, vl, positions, _NO_WINDOW, scale)
        with jax.named_scope("layer/attn_out"):
            out = _qe("bth,hd->btd", a.astype(x.dtype).reshape(B, T, nq * hd), p["wo"])
        return x + out.astype(x.dtype), kp, vp

    st = jnp.zeros((len(moe_stat_names(cfg)),), jnp.int32)
    for kind, i0, e0, n in segments(cfg.pattern):
        if kind == "pairs":
            def pair(j, c):
                x, conv, ssm, st = c
                x, conv, ssm = m_layer(x, conv, ssm, i0 + j)
                x, s = e_layer(x, e0 + j)
                return x, conv, ssm, st + s

            with jax.named_scope("layers"):
                x, conv, ssm, st = jax.lax.fori_loop(0, n, pair, (x, conv, ssm, st))
        elif kind == "M":
            x, conv, ssm = m_layer(x, conv, ssm, jnp.int32(i0))
        elif kind == "E":
            x, s = e_layer(x, jnp.int32(i0))
            st = st + s
        else:
            x, kp, vp = a_layer(x, kp, vp, jnp.int32(i0))

    with jax.named_scope("final_norm"):
        if logit_pos is not None:
            x = jnp.take_along_axis(x, logit_pos[:, None, None], axis=1)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    with jax.named_scope("lm_head"):
        logits = _qe("btd,dv->btv", x, params["lm_head"])
    extra = ()
    nm, na = cfg.count("M"), cfg.count("*")
    if hybrid_stats:
        extra += (jnp.stack([nm * jnp.sum(n_real), jnp.int32(nm * B * T),
                             nm * jnp.sum(n_real > 0)]).astype(jnp.int32),)
    if moe_stats:
        extra += (st,)
    if attn_stats:
        held = jnp.sum(jnp.where(live, jnp.max(positions, axis=1) // bs + 1, 0))
        common, handed = split.counts[::2] if split is not None else (jnp.int32(0),) * 2
        extra += (jnp.stack([na * common, na * held, na * handed]).astype(jnp.int32),)
    if kv_stats:
        extra += (na * rows_written(write_tiles, positions)[None],)
    if ffn_pack:
        extra += (rows.stats,)
    return (logits, {"kv": kp, "conv": conv}, {"kv": vp, "ssm": ssm}, None, None, *extra)
