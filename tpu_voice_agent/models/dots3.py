"""A ``dots3_note`` block (dots3-note-prev), the served forward: LEARNED SPARSE
attention over a latent cache in its full layers, WINDOWED latent attention
of its own sizes in its sliding ones, a compressed query and a gate a head in
both, Moonlight's expert rule behind them. A ``LlamaConfig`` with
``kv_lora_rank`` AND ``index_topk``; ``llama.forward_paged`` hands its
arguments on to ``forward_paged`` here on that, as it does a plain latent
model's to ``models.mla``.

d the hidden size, h = RMSNorm(x; ``norm_eps``). A layer of either kind, at
ITS OWN sizes (``kinds``: a full layer ``n_heads`` / ``kv_lora_rank`` /
``q_lora_rank`` / ``qk_*`` / ``v_head_dim`` / ``rope_theta``, a sliding layer
the ``swa_*`` ones):

    cq = RMSNorm(h W_qa; g_q) rho_q        rho_q  = (d / Cq)^0.5 (``lora_rescale``)
    q  = cq W_qb                            H heads of [q_n (dn) | q_r (dr)]
    [c' | r'] = h W_kva;  c = RMSNorm(c'; g_kv) rho_kv,  rho_kv = (d / C)^0.5
    r = RoPE(r'), q_r = RoPE(q_r)           interleaved pairs, the kind's theta
    [k_n | v]_head = c W_kvb
    score[t, s] = (q_n . k_n + q_r . r)(dn + dr)^-0.5 over the keys s the
    query MAY SEE;  o_head = sum softmax v;  g = sigmoid(h W_g) (H wide);
    x += concat(g_head o_head) W_o

A SLIDING layer sees t - (``sliding_window`` - 1) <= s <= t. A FULL layer sees
the ``index_topk`` keys s <= t its indexer scores highest (all of them while
t < ``index_topk``): with Hi = ``index_n_heads`` heads of di =
``index_head_dim``,

    qI = cq W_qI;  kI = LayerNorm(h W_kI)   ONE key of di a token
    RoPE on the first dr values of each qI head and of kI (the layer's theta)
    w = h W_w Hi^-0.5 di^-0.5               Hi a token
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])

SERVED, every path attends ABSORBED as ``models.mla`` does (rho_kv is folded
into the cached c), through ONE kernel, ``ops.sparse_latent`` (``sparse_latent_attention`` / ``window_latent_attention``),
over a key set gathered for its queries:

- a full layer's positions, the REAL ones first (``llama.ffn_pack_index``),
  go tile by tile (16 slots) — as many tiles as hold real positions — through
  ``ops.indexer_scores`` (every pool block scored once for all of them), a
  read of each position's own blocks out of that by its table, the selection
  (``top_k``'s index set), and the chosen keys fetched one of TWO ways, by the
  shapes of the program alone (``ops.sparse_latent.walks``: the keys a row's
  table spans, ``index_topk``, the heads — no flag): where the context is a few
  times ``index_topk`` the tile WALKS its rows' table columns block by block
  straight out of the pool under the selection as a membership mask
  (``walked_latent_attention``: a column every slot holds is read once for all
  of them; every visible key is scored and an unchosen one masked — the same
  softmax over the same set, and no gather, which the chip charges ~16 ns a
  ROW: PERF.md section 6, PR 62) and the mask is made with NOTHING SORTED
  (``threshold_members``: the k-th score in ``top_k``'s order and the last tie
  it takes, by compare-and-count steps over the tile's keys in VMEM — ~11 us a
  tile of 16 where ``lax.top_k``'s full sort of (16, 8832) + the compares were
  ~141: PERF.md section 6, PR 63); everywhere else ``lax.top_k`` — the branch
  that SORTS: it needs the indices — and ONE gather of the chosen keys' rows
  [c | r] (one row a key where two planes paid for two: PERF.md section 6, PR
  45) and the kernel with a position's H heads as a group over that one tile
  of keys;
- a sliding layer's rows gather the few blocks that hold their window and
  the kernel takes a row's T x H queries as a group (a prefill wider than
  ``MAX_BLOCK_DECODE_T`` is cut into rows of 8 positions first).

One path whatever T is: a decode step, a fast-forward block, a suffix behind
the cached prefix and a chunk of the prefix itself.

A fast-forward block WIDER than ``ffn_pack`` rows whose real positions are
told (``n_real``: the full-width chunk program, 32 rows x 9 positions against
96) runs everything position-wise on the real positions alone (ISSUE 44):
the residual is packed once behind the embedding (``llama.RowTiles``: every
position has a slot, so there is no predicate and no whole-width twin) and
stays packed from layer to layer; a layer is two walks over tiles of
``ffn_pack`` packed rows — norm, q/k/v, the indexer's projections, the gate
and the cache writes of the tile's rows; then W_UV, the gates, W_o, the
residual and the MLP — around its attention. A full layer attends the packed
queries as they are; a sliding layer's tile rows are scattered to their
positions for the window kernel and its output gathered a tile.

The pool is a pytree a kind (``cache_spec``): ``k_pool`` {"kv": the full
layers' rows (Lf, N, bs, C + dr) — a token's latent c and its rotated key r
side by side, ONE row a token a layer, written by one scatter and gathered by
one —, "idx": the full layers' index keys (Lf, N, bs, di), a plane of their
own (the indexer reads it alone, whole), "swa": the sliding layers' latents
(Ls, N, bs, Cs)}, ``v_pool`` {"swa": the sliding layers' rotated keys (Ls, N,
bs, drs)} (their gather is by whole blocks: two planes cost it nothing) — no
plane where a full layer's r would live apart. All ride ONE block table.
Parameters: ``attn_full`` / ``attn_swa`` stack the attention leaves by kind,
``dense_layers`` / ``layers`` the feed-forward ones and the norms
(``models.mla``'s).

A selection CARRIED across layers (``glm_moe_dsa``: GLM-5.2, ``LlamaConfig.indexer_types``; no
gate, no rescale, no sliding layer): a "shared" layer is a full layer WITHOUT an indexer — a kind
of its own here, ``attn_shared`` its leaves (no W_qI / W_kI / W_w), ``k_pool["shared"]`` its rows
[c | r] (no index key cached) — that attends S_l[t] = S_f(l)[t], the keys the nearest full layer f
before it selected. A full layer's tiles hand their selection on as they make it, in the form the
fetch takes it — gathered: ``lax.top_k``'s chosen keys' sequence positions and their pool blocks, (P, K)
each; walked: the same set's members, ONE (P, nb * bs) mask, made by the threshold select with no sort
(``threshold_members``) — in the packed order of the forward's
positions (``layer/attn/carry``); a shared layer's tiles cut theirs out of that, fetch THEIR OWN
plane's rows at those coordinates and attend them through the same kernel. Nothing else differs:
one ``latent_qkv``, one indexer path, one ``attend_chosen``.
"""

from __future__ import annotations

from contextlib import nullcontext
from dataclasses import replace
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .llama import (MAX_BLOCK_DECODE_T, LlamaConfig, _attn_stats, _ffn, _qe, _scan_and_whole,
                    apply_rope_interleaved, cache_planes, ffn_pack_index, layer_norm, moe_stat_names,
                    rms_norm, rope_tables, row_tiles)
from .mla import _dense_ffn

F32 = jnp.float32

# what this forward counts behind ``mla.LATENT_STATS`` (one vector, summed
# and published like them, ``attn.<name>``): pool positions the indexer
# scored (positions x the plane), keys the real positions of full layers
# could see and the keys they attended, cached positions the sliding layers'
# window gathers read
SPARSE_STATS = ("index_keys_scored", "keys_visible", "keys_selected", "window_keys_read",
                # tile passes of selected attention (selected layers x tiles that hold a real
                # position), and those that WALKED their rows' blocks under the selection as a mask
                "selected_tiles", "selected_tiles_walked",
                # tile passes of INDEXED layers whose selection the threshold select made
                # (``ops.sparse_latent.threshold_members``: no sorted row), not ``lax.top_k``
                "selections_thresholded")
# behind them where a selection is carried (``indexer_types``): (real position, layer) pairs whose
# layer scored and selected, and pairs whose layer attended the set an earlier layer chose
CARRY_STATS = ("selections_made", "selections_carried")
INDEX_NORM_EPS = 1e-6


class Kind(NamedTuple):
    """One kind of layer's attention sizes."""

    H: int
    dn: int
    dr: int
    dv: int
    Cq: int
    C: int
    theta: float
    window: int | None
    indexed: bool  # runs an indexer (and caches an index key)
    stack: str = "attn_full"  # the kind's attention leaves in the parameter tree

    @property
    def selected(self) -> bool:
        """Attends a selected key set (its own indexer's, or one carried to it)."""
        return self.window is None


def layer_kinds(cfg: LlamaConfig) -> tuple[str, ...]:
    """The kind of every layer: "full" | "sliding", and "shared" where
    ``indexer_types`` says a full layer runs no indexer."""
    return tuple("shared" if i == "shared" else t
                 for t, i in zip(cfg.layer_types, cfg.indexer_types or cfg.layer_types))


def kinds(cfg: LlamaConfig) -> dict[str, Kind]:
    """The sizes of the kinds this model has layers of."""
    full = Kind(cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim,
                cfg.q_lora_rank, cfg.kv_lora_rank, cfg.rope_theta, None, True)
    every = {"full": full,
             "sliding": Kind(cfg.swa_n_heads, cfg.swa_qk_nope_dim, cfg.swa_qk_rope_dim,
                             cfg.swa_v_head_dim, cfg.swa_q_lora_rank or cfg.q_lora_rank,
                             cfg.swa_kv_lora_rank, cfg.swa_rope_theta, cfg.sliding_window, False,
                             "attn_swa"),
             "shared": full._replace(indexed=False, stack="attn_shared")}
    return {t: k for t, k in every.items() if t == "full" or t in layer_kinds(cfg)}


def layer_plan(cfg: LlamaConfig) -> tuple[tuple[str, int], ...]:
    """(kind, index among the layers of that kind) of every layer."""
    seen = {"full": 0, "sliding": 0, "shared": 0}
    plan = []
    for t in layer_kinds(cfg):
        plan.append((t, seen[t]))
        seen[t] += 1
    return tuple(plan)


def cache_spec(cfg: LlamaConfig) -> dict:
    """What a token holds in the pool, by layer KIND: each pool's planes as
    (layers of the kind, width) — a full layer's ONE row [c | r] and its index
    key, a sliding layer's c and r apart, a shared layer's ONE row [c | r] and
    no index key."""
    k = kinds(cfg)
    n = {t: layer_kinds(cfg).count(t) for t in ("full", "sliding", "shared")}
    planes = {"k": {"kv": (n["full"], k["full"].C + k["full"].dr), "idx": (n["full"], cfg.index_head_dim)},
              "v": {}}
    if n["sliding"]:
        planes["k"]["swa"] = (n["sliding"], k["sliding"].C)
        planes["v"]["swa"] = (n["sliding"], k["sliding"].dr)
    if n["shared"]:
        planes["k"]["shared"] = (n["shared"], k["shared"].C + k["shared"].dr)
    return cache_planes(planes["k"], planes["v"], by_name=True)


# ---------------------------------------------------------------- params


def attn_shapes(cfg: LlamaConfig, kind: str) -> dict:
    """The attention matrices of one layer of ``kind``, (fan_in, fan_out)."""
    d, k = cfg.dim, kinds(cfg)[kind]
    out = {"w_qa": (d, k.Cq), "w_qb": (k.Cq, k.H * (k.dn + k.dr)), "w_kva": (d, k.C + k.dr),
           "w_kvb": (k.C, k.H * (k.dn + k.dv)), "wo": (k.H * k.dv, d)}
    if cfg.attn_gate:
        out["w_hgate"] = (d, k.H)
    if k.indexed:
        out.update(w_iq=(k.Cq, cfg.index_n_heads * cfg.index_head_dim),
                   w_ik=(d, cfg.index_head_dim), w_iw=(d, cfg.index_n_heads))
    return out


def attn_norms(cfg: LlamaConfig, kind: str, L: int, dtype=jnp.bfloat16) -> dict:
    k = kinds(cfg)[kind]
    out = {"q_norm": jnp.ones((L, k.Cq), dtype), "kv_norm": jnp.ones((L, k.C), dtype)}
    if k.indexed:
        out["ik_norm"] = jnp.ones((L, cfg.index_head_dim), dtype)
    return out


def init_params(cfg: LlamaConfig, key: jax.Array, dtype=jnp.bfloat16) -> dict:
    """Random init in ``mla.init_params``' recipe (normal, fan_in^-0.5; gains
    1; a NONZERO router bias), the matrices behind a rescaled rank at d^-0.5."""
    k_embed, k_dense, k_routed, k_head, k_full, k_swa = jax.random.split(key, 6)
    d, f, E = cfg.dim, cfg.ffn_dim, cfg.n_experts
    n_dense, n_routed = cfg.first_dense_layers, cfg.n_layers - cfg.first_dense_layers

    def w(key, *shape, fan=None):
        return (jax.random.normal(key, shape, F32) * (fan or shape[-2]) ** -0.5).astype(dtype)

    # a matrix that reads a RESCALED rank has the hidden size's fan-in: its
    # input's mean square is d / rank, not 1
    rescaled = ("w_qb", "w_iq", "w_kvb") if cfg.lora_rescale else ()

    def stack(key, L, names: dict) -> dict:
        ks = jax.random.split(key, len(names))
        return {n: w(k, L, *s, fan=d if n in rescaled else None) for (n, s), k in zip(names.items(), ks)}

    norms = lambda L: {"attn_norm": jnp.ones((L, d), dtype), "mlp_norm": jnp.ones((L, d), dtype)}
    fd, sf = cfg.dense_ffn_dim, cfg.n_shared_experts * f
    routed = {**stack(k_routed, n_routed, {
        "router": (d, E), "moe_gate": (cfg.n_held, d, f), "moe_up": (cfg.n_held, d, f),
        "moe_down": (cfg.n_held, f, d),
        **({"shared_gate": (d, sf), "shared_up": (d, sf), "shared_down": (sf, d)} if sf else {})}),
        **norms(n_routed)}
    if cfg.router_bias:
        routed["router_bias"] = 0.1 * jax.random.normal(
            jax.random.fold_in(k_routed, 1), (n_routed, E), F32)
    params = {"embed": w(k_embed, cfg.vocab_size, d, fan=d), "layers": routed,
              "final_norm": jnp.ones((d,), dtype), "lm_head": w(k_head, d, cfg.vocab_size)}
    if n_dense:
        params["dense_layers"] = {**stack(k_dense, n_dense, {
            "w_gate": (d, fd), "w_up": (d, fd), "w_down": (fd, d)}), **norms(n_dense)}
    for kind, kk in (("full", k_full), ("sliding", k_swa), ("shared", jax.random.fold_in(k_full, 1))):
        L = layer_kinds(cfg).count(kind)
        if L:
            params[kinds(cfg)[kind].stack] = {
                **stack(kk, L, attn_shapes(cfg, kind)), **attn_norms(cfg, kind, L, dtype)}
    return params


# ---------------------------------------------------------------- layers


def _split_kvb(leaf, k: Kind):
    """W_kvb (C, H x (dn + dv)), int8 {"q", "s"} or plain -> (W_UK (C, H, dn),
    its per-column scale (H, dn) or None, W_UV (C, H, dv), its scale)."""
    heads = lambda a: a.reshape(*a.shape[:-1], k.H, k.dn + k.dv)
    if isinstance(leaf, dict):
        q, s = heads(leaf["q"]), heads(leaf["s"])[0]
        return q[..., :k.dn], s[:, :k.dn], q[..., k.dn:], s[:, k.dn:]
    w = heads(leaf)
    return w[..., :k.dn], None, w[..., k.dn:], None


def _rope_head(x, cos, sin, dr: int):
    """RoPE on the first ``dr`` values of the last axis of x (B, T, H, w)."""
    return jnp.concatenate([apply_rope_interleaved(x[..., :dr], cos, sin), x[..., dr:]], axis=-1)


def latent_qkv(p, x, cfg: LlamaConfig, k: Kind, cos, sin, hold: bool = False):
    """The front half of a layer -> (q_c (B, T, H, C) with W_UK absorbed, q_r
    (B, T, H, dr) rotated, c (B, T, C) normed and rescaled, r (B, T, dr)
    rotated, gate (B, T, H) float32 or None, the indexer's (qI (B, T, Hi,
    di), kI (B, T, di), w (B, T, Hi) float32) or None). ``hold`` (the packed
    walk): a projection's output is a buffer before it opens into heads —
    fused with that reshape, XLA:TPU wants the stacked plane transposed and
    copies it whole (PERF.md section 6, PRs 41 and 44)."""
    B, T = x.shape[:2]
    buffered = jax.lax.optimization_barrier if hold else (lambda a: a)
    rescale = lambda rank: (cfg.dim / rank) ** 0.5 if cfg.lora_rescale else 1.0
    with jax.named_scope("layer/attn_qkv"):
        h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
        with jax.named_scope("layer/attn/q_lora"):
            cq = rms_norm(_qe("btd,dc->btc", h, p["w_qa"]).astype(x.dtype), p["q_norm"],
                          cfg.latent_norm_eps)
            cq = (cq * rescale(k.Cq)).astype(x.dtype)
            q = buffered(_qe("btc,ch->bth", cq, p["w_qb"]).astype(x.dtype)).reshape(B, T, k.H, k.dn + k.dr)
        with jax.named_scope("kv_a"):
            cr = buffered(_qe("btd,dh->bth", h, p["w_kva"]).astype(x.dtype))
            c = rms_norm(cr[..., :k.C], p["kv_norm"], cfg.latent_norm_eps)
            c = (c * rescale(k.C)).astype(x.dtype)
            r = apply_rope_interleaved(cr[..., None, k.C:], cos, sin)[:, :, 0]
        q_r = apply_rope_interleaved(q[..., k.dn:], cos, sin)
        with jax.named_scope("q_absorb"):
            w_uk, s_k, _, _ = _split_kvb(p["w_kvb"], k)
            q_n = q[..., :k.dn]
            if s_k is not None:  # a scale a column of W_UK: on the query, which contracts it
                q_n = (q_n.astype(F32) * s_k).astype(x.dtype)
            q_c = jnp.einsum("bthn,chn->bthc", q_n, w_uk.astype(x.dtype),
                             preferred_element_type=F32).astype(x.dtype)
    gate = index = None
    if cfg.attn_gate:
        with jax.named_scope("layer/attn/gate"):
            gate = jax.nn.sigmoid(_qe("btd,dh->bth", h, p["w_hgate"]))
    if k.indexed:
        Hi, di = cfg.index_n_heads, cfg.index_head_dim
        with jax.named_scope("layer/attn/index"), jax.named_scope("project"):
            qi = buffered(_qe("btc,ch->bth", cq, p["w_iq"]).astype(x.dtype)).reshape(B, T, Hi, di)
            qi = _rope_head(qi, cos, sin, k.dr)
            ki = layer_norm(_qe("btd,dh->bth", h, p["w_ik"]).astype(x.dtype), p["ik_norm"],
                            INDEX_NORM_EPS)
            ki = _rope_head(ki[:, :, None, :], cos, sin, k.dr)[:, :, 0]
            wi = _qe("btd,dh->bth", h, p["w_iw"]) * (Hi ** -0.5 * di ** -0.5)
        index = (qi, ki, wi)
    return q_c, q_r, c, r, gate, index


def latent_out(p, a, k: Kind, gate, dtype):
    """(B, T, H, C) attended latents -> (B, T, H * dv): W_UV a head, then the
    head's gate."""
    with jax.named_scope("layer/attn_out"), jax.named_scope("v_up"):
        _, _, w_uv, s_v = _split_kvb(p["w_kvb"], k)
        o = jnp.einsum("bthc,chv->bthv", a.astype(dtype), w_uv.astype(dtype),
                       preferred_element_type=F32)
        if s_v is not None:
            o = o * s_v
    if gate is not None:
        with jax.named_scope("layer/attn/gate"):
            o = o * gate[..., None]
    return o.astype(dtype).reshape(*a.shape[:2], -1)


def _position_tile(P: int) -> int:
    """Positions a pass of a full layer's attention takes: the largest
    divisor of P up to 16 (a fast-forward block of 32 rows, a suffix group
    and the prefix's chunks: 16; the compacted width's 72: 12). Small,
    because a pass pays for every slot of its tile, real or not — a GATHERED
    pass ``index_topk`` rows of the cache a slot, a WALKED one the slot's heads
    as query rows of every key block: ~45 real positions of a block's 288
    take three tiles. (Sized when every pass gathered; a walked pass's fixed
    part — its items' steps, its slots' own blocks — would be paid once in a
    wider tile: PERF.md section 7, "Open after PR 62".)"""
    return max(t for t in range(1, min(P, 16) + 1) if P % t == 0)


# ---------------------------------------------------------------- forward


def forward_paged(params, cfg: LlamaConfig, tokens, positions, k_pool, v_pool, block_tables, *,
                  attn_impl: str = "pallas", write_mask=None, trash_idx=None,
                  fresh_block: bool = False, gather_blocks: int | None = None, n_real=None,
                  logit_pos=None, moe_stats: bool = False, attn_stats: bool = False,
                  latent_stats: bool = False, ffn_pack: int = 0, fault: str | None = None):
    """``llama.forward_paged`` for this model: ``k_pool`` / ``v_pool`` the
    pytrees the module's text names (``fresh_block`` is a promise this forward
    does not need: ONE attention path whatever T is). -> (logits, k_pool, v_pool, None, None),
    then with ``moe_stats`` the routed layers' counts, with ``attn_stats``
    ``ops.ATTN_STATS``, with ``latent_stats`` ``LATENT_STATS + SPARSE_STATS`` over all
    layers, where the block is walked packed (``ffn_pack`` under its B * T
    positions, with ``n_real``) ``llama.FFN_STATS``. ``fault`` PLANTS one, for
    the comparison's limit to be set against (``FAULTS``, ``CARRY_FAULTS``); None
    everywhere else."""
    from ..ops import sparse_latent as sl

    pallas = attn_impl == "pallas"
    index_fn = sl.indexer_scores if pallas else sl.indexer_scores_reference
    twin = sl.gathered_latent_attention_reference
    attend_full = sl.sparse_latent_attention if pallas else sl.sparse_latent_attention_reference
    attend_walk = sl.walked_latent_attention if pallas else sl.walked_latent_attention_reference
    members_of = sl.threshold_members if pallas else sl.chosen_mask
    attend_window = sl.window_latent_attention if pallas else twin
    if fault is not None and fault not in FAULTS + CARRY_FAULTS:
        raise ValueError(f"fault {fault!r}: one of {FAULTS + CARRY_FAULTS}")

    B, T = tokens.shape
    kvp, ip = k_pool["kv"], k_pool["idx"]
    cps, rps = k_pool.get("swa"), v_pool.get("swa")
    skv = k_pool.get("shared")
    N, bs = kvp.shape[1], kvp.shape[2]
    ncols = block_tables.shape[1]
    nb = min(gather_blocks, ncols) if gather_blocks is not None else ncols
    kd = kinds(cfg)
    if fault == "no_rescale":
        cfg = replace(cfg, lora_rescale=False)
    n_dense = cfg.first_dense_layers
    P = B * T
    alive = jnp.ones((B,), bool) if write_mask is None else write_mask

    with jax.named_scope("embed"):
        x = params["embed"][tokens]
        rope = {t: rope_tables(positions, k.dr, k.theta) for t, k in kd.items() if t != "shared"}
    blk = jnp.take_along_axis(block_tables, positions // bs, axis=1)
    off = positions % bs
    if write_mask is not None:
        park = jnp.zeros((B,), jnp.int32) if trash_idx is None else trash_idx.astype(jnp.int32)
        blk = jnp.where(write_mask[:, None], blk, park[:, None] // bs)
        off = jnp.where(write_mask[:, None], off, park[:, None] % bs)

    # a block wider than ``ffn_pack`` rows whose real positions are told: the
    # walk of its position-wise work, in tiles of that many packed rows
    rows = None
    live_n = None
    if n_real is not None:
        live_n = n_real if write_mask is None else jnp.where(write_mask, n_real, 0)
        if ffn_pack and P > ffn_pack:
            with jax.named_scope("layer/ffn/pack"):
                rows = row_tiles(live_n, T, ffn_pack)

    # ---- a full layer's geometry, once a forward: the real positions first
    K = min(cfg.index_topk, nb * bs)
    tile = _position_tile(P)
    # how a selected layer fetches its chosen keys — by the shapes of THIS program alone
    # (``ops.sparse_latent.walks``): its rows' blocks walked whole under the selection as a mask,
    # or one gathered row a chosen key
    walk = sl.walks(nb * bs, K, kd["full"].H)
    with jax.named_scope("layer/attn/split"):
        if live_n is not None:
            # every position has a slot: it always fits
            order = rows if rows is not None else ffn_pack_index(live_n, T, P)
            idx, inv = order.idx, order.inv.reshape(-1)
            n_pos = jnp.sum(jnp.clip(live_n, 0, T)).astype(jnp.int32)
            n_tiles = jnp.maximum(-(-n_pos // tile), 1)
        else:
            idx = inv = jnp.arange(P, dtype=jnp.int32)
            n_pos = jnp.sum(alive).astype(jnp.int32) * T
            n_tiles = P // tile
        pos_of = positions.reshape(-1)[idx]  # (P,)
        tbl_of = block_tables[idx // T, :nb]  # (P, nb)
        real = jnp.arange(P) < n_pos if live_n is not None else jnp.repeat(alive, T)[idx]
        # a walked tile's work: the kernel's items (its twin reads by none)
        items = sl.walk_split(tbl_of, pos_of, tile, bs, real) if walk and pallas else None

        # ---- a sliding layer's: rows of at most MAX_BLOCK_DECODE_T positions
        # and the blocks that hold their window
        Tq = 8 if T > MAX_BLOCK_DECODE_T and T % 8 == 0 else T
        G = P // Tq
        WB = 0
        if "sliding" in kd:
            pos_g = positions.reshape(G, Tq)
            tbl_g = jnp.repeat(block_tables, T // Tq, axis=0)
            window = kd["sliding"].window if fault != "no_window" else None
            reach = (window - 1) if window else ncols * bs
            WB = min(-(-(reach + Tq - 1) // bs) + 1, ncols)
            first = jnp.maximum(jnp.min(pos_g, axis=1) - reach, 0) // bs  # (G,)
            cols = first[:, None] + jnp.arange(WB, dtype=jnp.int32)[None, :]
            wblk = jnp.take_along_axis(tbl_g, jnp.minimum(cols, ncols - 1), axis=1)  # (G, WB)
            kpos_w = jnp.where(cols[:, :, None] < ncols,
                               cols[:, :, None] * bs + jnp.arange(bs, dtype=jnp.int32), -1)
            kpos_w = kpos_w.reshape(G, WB * bs)
            hi_w = jnp.repeat(pos_g, kd["sliding"].H, axis=1)  # (G, Tq * Hs), position-major
            lo_w = jnp.maximum(hi_w - reach, 0)

    k_sel = kd["full"]
    scale = (k_sel.dn + k_sel.dr) ** -0.5
    cut_tile = lambda a, i: jax.lax.dynamic_slice_in_dim(a, i * tile, tile)

    def attend_chosen(i, li, ps, qc_f, qr_f, plane, picked, out):
        """Tile ``i`` (positions ``ps``) of a selected layer behind its selection ``picked``, in
        the form its fetch takes it. Gathered, (sel, sblk): ONE row a chosen key, [c | r], out of
        the layer's own plane, and the kernel over them. Walked, (members,): the tile's blocks
        straight out of the pool, a key admitted iff the position chose it and may see it."""
        if walk:
            with jax.named_scope("layer/attn/select"):
                seen = picked[0] & (jnp.arange(nb * bs, dtype=jnp.int32)[None, :] <= ps[:, None])
                work = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), items)
            with jax.named_scope("layer/attn/full"):
                a = attend_walk(cut_tile(qc_f, i), cut_tile(qr_f, i), plane, li, seen, cut_tile(tbl_of, i),
                                work, scale=scale)
            return jax.lax.dynamic_update_slice_in_dim(out, a, i * tile, 0)
        sel, sblk = picked
        with jax.named_scope("layer/attn/select"):
            # straight out of the pool (a layer's plane sliced first is an HBM copy a tile behind a
            # ``while`` that carries the pools)
            kv_sel = plane[li, sblk, sel % bs]
        with jax.named_scope("layer/attn/full"):
            a = attend_full(cut_tile(qc_f, i), cut_tile(qr_f, i), kv_sel, sel,
                            jnp.zeros((tile, k_sel.H), jnp.int32),
                            jnp.broadcast_to(ps[:, None], (tile, k_sel.H)), scale=scale)
        return jax.lax.dynamic_update_slice_in_dim(out, a, i * tile, 0)

    def full_attention(li, qc_f, qr_f, qi_f, wi_f, kvp, ip, out, chosen=None):
        """The full layers' attention over (P, ...) queries in ``idx``'s
        order, the real positions first, into ``out`` (P, H, C), tile by tile.
        ``chosen`` (a model whose shared layers take this layer's selection):
        the buffers its tiles leave their selection in, as ``attend_chosen`` takes
        it — gathered, the chosen keys' sequence positions and pool blocks, (P, K)
        each; walked, ``top_k``'s members, (P, nb * bs) -> (out, chosen)."""

        def one_tile(i, carry):
            out = carry if chosen is None else carry[0]
            cut = lambda a: cut_tile(a, i)
            ps, tb = cut(pos_of), cut(tbl_of)
            with jax.named_scope("layer/attn/index"):
                with jax.named_scope("scores"):
                    scores = index_fn(cut(qi_f), cut(wi_f), ip, li)  # (tile, N * bs)
                    mine = jnp.take_along_axis(scores.reshape(tile, N, bs), tb[:, :, None],
                                               axis=1).reshape(tile, nb * bs)
                    seq = jnp.arange(nb * bs, dtype=jnp.int32)[None, :]
                    if fault == "first_keys":  # the first K keys, whatever their score
                        mine = -seq.astype(F32) + jnp.zeros_like(mine)
                    mine = jnp.where(seq <= ps[:, None], mine, -jnp.inf)
                with jax.named_scope("top_k"):
                    if walk:
                        # ``top_k``'s set as a membership mask and NOTHING sorted: the mask wants the
                        # k-th score and its last tie, two scalars a row (ISSUE 63)
                        picked = (members_of(mine, K),)
                    else:
                        sel = jax.lax.top_k(mine, K)[1]  # (tile, K) sequence positions
            if not walk:
                with jax.named_scope("layer/attn/select"):
                    # the block of each chosen key out of the slot's table: a compare
                    # and a sum over its few columns (a gather of scalars costs more
                    # than the rows it names)
                    col = (sel // bs)[:, :, None] == jnp.arange(nb, dtype=jnp.int32)
                    picked = (sel, jnp.sum(jnp.where(col, tb[:, None, :], 0), axis=-1))
            out = attend_chosen(i, li, ps, qc_f, qr_f, kvp, picked, out)
            if chosen is None:
                return out
            with jax.named_scope("layer/attn/carry"):  # handed on to the shared layers behind
                return out, tuple(jax.lax.dynamic_update_slice_in_dim(buf, v, i * tile, 0)
                                  for buf, v in zip(carry[1], picked))

        return jax.lax.fori_loop(0, n_tiles, one_tile, out if chosen is None else (out, chosen))

    def shared_attention(li, qc_f, qr_f, plane, chosen, out):
        """A shared layer's attention: the same tiles over the set the nearest
        full layer before it left in ``chosen``, fetched out of ITS OWN plane."""
        if fault == "other_row":  # a position reads its neighbour's selection
            chosen = tuple(jnp.roll(buf, 1, axis=0) for buf in chosen)

        def one_tile(i, out):
            with jax.named_scope("layer/attn/carry"):
                picked = tuple(cut_tile(buf, i) for buf in chosen)
            return attend_chosen(i, li, cut_tile(pos_of, i), qc_f, qr_f, plane, picked, out)

        return jax.lax.fori_loop(0, n_tiles, one_tile, out)

    def dense_attention(li, q_c, q_r, kvp):
        """The planted fault ``no_selection``: a full layer over every key."""
        k = kd["full"]
        g = lambda a: a.reshape(B, T * k.H, a.shape[-1])
        kv_all = kvp[li][block_tables[:, :nb]].reshape(B, nb * bs, k.C + k.dr)
        hi = jnp.repeat(positions, k.H, axis=1)
        a = sl.sparse_latent_attention_reference(
            g(q_c), g(q_r), kv_all,
            jnp.broadcast_to(jnp.arange(nb * bs, dtype=jnp.int32), (B, nb * bs)),
            jnp.zeros_like(hi), hi, scale=(k.dn + k.dr) ** -0.5)
        return a.reshape(B, T, k.H, k.C)

    def window_attention(li, q_c, q_r, cps, rps):
        k = kd["sliding"]
        g = lambda a: a.reshape(G, Tq * k.H, a.shape[-1])
        with jax.named_scope("layer/attn/window"):
            with jax.named_scope("gather"):
                # (behind the walk's ``while`` the window's blocks come straight out of the pool:
                # the layer's plane no longer fits the fast memory, and ``pool[li]`` is a 69 MB copy)
                blocks = (lambda pool: pool[li, wblk]) if rows is not None else (lambda pool: pool[li][wblk])
                c_w = blocks(cps).reshape(G, WB * bs, k.C)
                r_w = blocks(rps).reshape(G, WB * bs, k.dr)
            # (with the window planted away a row's whole context is one tile: XLA)
            a = (attend_window if window else twin)(
                g(q_c), g(q_r), c_w, r_w, kpos_w, lo_w, hi_w, scale=(k.dn + k.dr) ** -0.5)
        return a.reshape(B, T, k.H, k.C)

    scanned, whole = _scan_and_whole(params["layers"], cfg)

    def leaves(L, k: Kind, ki: int, scope=None):
        """(layer L's parameters, its MLP), every stacked leaf sliced by the
        layer's STATIC index — called inside whichever loop body reads them:
        a slice made before a loop is its operand, written out and read back.
        ``scope`` (the walk): the name a slice that is an op of its own runs
        under — the attention's planes under it, the MLP's under
        ``layer/ffn/pack`` — so that a reader of the region counts it."""
        named = jax.named_scope if scope else (lambda name: nullcontext())
        with named(scope):
            attn_p = jax.tree.map(lambda a: a[ki], params[k.stack])
        with named("layer/ffn/pack"):
            if L < n_dense:
                ffn_p = jax.tree.map(lambda a: a[L], params["dense_layers"])
                return {**attn_p, **ffn_p}, partial(_dense_ffn, cfg=cfg)
            j = L - n_dense
            ffn_p = {**jax.tree.map(lambda a: a[j], scanned), **whole, "layer": jnp.int32(j)}
            return {**attn_p, **ffn_p}, partial(_ffn, cfg=cfg)

    def front(p, k: Kind, li, x, cos, sin, blk, off, planes):
        """A layer up to its attention, position-wise over (b, t, d): the
        queries (q_c, q_r, the gate, the indexer's qI and w) and the kind's
        ``planes`` with these positions' rows written."""
        q_c, q_r, c, r, gate, index = latent_qkv(p, x, cfg, k, cos, sin, hold=rows is not None)
        if fault == "no_gate":
            gate = None
        qi, ki, wi = index or (None, None, None)
        if fault == "no_index_rope" and k.indexed:  # the index key cached as it was before its rotation
            ki = _rope_head(ki[:, :, None, :], cos, -sin, k.dr)[:, :, 0]
        with jax.named_scope("layer/kv_write"):
            # a full layer's position writes ONE row [c | r] and its index key, a shared one's that row
            # alone, a sliding one's c and r
            written = (sl.key_row(c, r), ki)[:len(planes)] if k.selected else (c, r)
            planes = tuple(pl.at[li, blk, off].set(v.astype(pl.dtype))
                           for pl, v in zip(planes, written))
        return {"c": q_c, "r": q_r, "gate": gate, "i": qi, "w": wi}, planes

    def attend_block(k: Kind, li, q_c, q_r, planes):
        """What takes a row's (b, t) block of queries: a sliding layer's
        window, and the planted fault ``no_selection``."""
        if not k.selected:
            return window_attention(li, q_c, q_r, *planes)
        return dense_attention(li, q_c, q_r, planes[0])

    def back(p, ffn, k: Kind, x, a, gate):
        """A layer behind its attention, position-wise over (b, t, d) ->
        (the new residual, the MLP's routed counts or None)."""
        attn = latent_out(p, a, k, gate, x.dtype)
        if fault == "short_value":  # a value head as wide as its key: the columns past dn dropped
            attn = attn.reshape(*attn.shape[:2], k.H, k.dv).at[..., k.dn:].set(0).reshape(attn.shape)
        with jax.named_scope("layer/attn_out"):
            x = x + _qe("bth,hd->btd", attn, p["wo"]).astype(x.dtype)
        with jax.named_scope("layer/ffn"):
            h = rms_norm(x, p["mlp_norm"], cfg.norm_eps)
        y, st = ffn(p, h)
        with jax.named_scope("layer/ffn"):
            return x + y, st

    pools = {"full": (kvp, ip), "sliding": (cps, rps), "shared": (skv,)}
    carries = "shared" in kd  # a full layer's selection is an input of the shared layers behind it
    # a selected layer attends every key: the fault planted in all of them, or in the shared ones
    every_key = lambda k: fault == "no_selection" or (fault == "shared_all_keys" and not k.indexed)

    def selected_attention(k: Kind, li, q, planes, out, chosen):
        """A selected layer's attention over queries in ``idx``'s order ->
        (out, the selection it made or took: what the next shared layer takes)."""
        if not k.indexed:
            return shared_attention(li, q["c"], q["r"], planes[0], chosen, out), chosen
        if not carries:
            return full_attention(li, *(q[n] for n in "criw"), *planes, out), None
        made = chosen if chosen is not None else (
            (jnp.zeros((P, nb * bs), bool),) if walk else tuple(jnp.zeros((P, K), jnp.int32) for _ in range(2)))
        out, made = full_attention(li, *(q[n] for n in "criw"), *planes, out, made)
        # (planted: every shared layer takes the FIRST full layer's set, not the nearest's)
        return out, chosen if fault == "first_selection" and chosen is not None else made

    def block_layer(L, kind, ki, x, held):
        """A layer over the (B, T) block as it stands. ``held``: the selection
        a full layer hands the shared layers behind it, where the model has any.
        -> (x, the MLP's routed counts or None, ``held``)."""
        k, li = kd[kind], jnp.int32(ki)
        p, ffn = leaves(L, k, ki)
        q, pools[kind] = front(p, k, li, x, *rope[kind], blk, off, pools[kind])
        with jax.named_scope("layer/attn"):
            if k.selected and not every_key(k):
                order_of = lambda a: a if a is None else a.reshape(P, *a.shape[2:])[idx]
                a, chosen = selected_attention(
                    k, li, {n: order_of(q[n]) for n in "criw"}, pools[kind],
                    jnp.zeros((P, k.H, k.C), x.dtype), held.get("chosen"))
                held = {"chosen": chosen}
                a = a[inv].reshape(B, T, k.H, k.C)
            else:
                a = attend_block(k, li, q["c"], q["r"], pools[kind])
        return (*back(p, ffn, k, x, a, q["gate"]), held)

    def walked_layer(L, kind, ki, x, held):
        """A layer over the PACKED residual x (P, d): ONE copy of its
        position-wise code in two walks over tiles of ``ffn_pack`` packed rows
        (one tile in ~99 % of a flood's forwards) — no predicate, no
        whole-width twin. Every stacked leaf is sliced inside the walk that
        reads it (``leaves``), and every move of a walk — a tile cut out of
        the packed arrays, a plane sliced, rows put back — runs under the
        region's own name (``layer/attn_qkv/pack`` and ``/unpack``,
        ``layer/attn_out/pack``, ``layer/ffn/pack`` and ``/unpack``).
        ``held``: the walks' buffers by slot, a kind's queries, the selected
        layers' output and the selection a full layer made, handed on from layer
        to layer (none zeroed a layer).
        -> (x, the MLP's routed counts or None, ``held``)."""
        k, li = kd[kind], jnp.int32(ki)
        blockwise = not k.selected or every_key(k)  # attention takes a row's (t, head) group

        def front_rows(i, planes):
            p = leaves(L, k, ki, "layer/attn_qkv/pack")[0]
            with jax.named_scope("layer/attn_qkv/pack"):
                cut = lambda a: rows.cut(a, i)[None]
                of_tile = (cut(x), *map(cut, rope[kind]), cut(blk), cut(off))
            q, planes = front(p, k, li, *of_tile, planes)
            with jax.named_scope("layer/attn_qkv/unpack"):
                return jax.tree.map(lambda v: v[0], q), planes

        def front_tile(i, carry):
            q, planes = front_rows(i, carry[1])
            # a sliding layer's window wants its queries by POSITION: its tile's q_c / q_r rows
            # land in the block at once (a padded position keeps what an earlier layer left
            # there: its output is never read); everything else stays by slot
            place = lambda n, buf, v: (buf.at[rows.cut(rows.idx, i)].set(v) if blockwise and n in "cr"
                                       else rows.put(buf, v, i))
            with jax.named_scope("layer/attn_qkv/unpack"):
                return {n: v if v is None else place(n, carry[0][n], v) for n, v in q.items()}, planes

        if kind not in held:
            like = jax.eval_shape(lambda: front_rows(0, pools[kind])[0])
            held = {**held, kind: jax.tree.map(lambda v: jnp.zeros((P, *v.shape[1:]), v.dtype), like)}
            if k.selected and "out" not in held:
                held["out"] = jnp.zeros((P, k.H, k.C), x.dtype)
        with jax.named_scope("layer/front"):  # (the walk's ``while`` itself: a name no reader matches)
            q, pools[kind] = jax.lax.fori_loop(0, rows.n_tiles, front_tile, (held[kind], pools[kind]))
        held = {**held, kind: q}
        with jax.named_scope("layer/attn"):
            if blockwise:
                by_row = lambda a: a.reshape(B, T, *a.shape[1:])
                a = attend_block(k, li, by_row(q["c"]), by_row(q["r"]), pools[kind]).reshape(P, k.H, k.C)
                a_rows = lambda i: a[rows.cut(rows.idx, i)]
            else:
                # the packed queries as they are, the output handed on packed. Its tiles (of 16) end
                # before a walk's tile (of ``ffn_pack``) does: a slot behind the last real one reads
                # THAT one's output, or its residual — and from the next layer on the latent it
                # writes to that position's cache index — would be another's
                a, chosen = selected_attention(k, li, q, pools[kind], held["out"], held.get("chosen"))
                held = {**held, "out": a, "chosen": chosen}
                a_rows = lambda i: a[rows.slots(i)]

        def back_tile(i, carry):
            out, st = carry
            p, ffn = leaves(L, k, ki, "layer/attn_out/pack")
            with jax.named_scope("layer/attn_out/pack"):
                a_i = a_rows(i)[None]
                gate = None if q["gate"] is None else rows.cut(q["gate"], i)[None]
                x_i = rows.cut(x, i)[None]
            x_i, s = back(p, ffn, k, x_i, a_i, gate)
            with jax.named_scope("layer/ffn/unpack"):
                return rows.put(out, x_i[0], i), (None if s is None else st + s)

        routed = L >= n_dense and cfg.n_experts > 0
        st0 = jnp.zeros((len(moe_stat_names(cfg)),), jnp.int32) if routed else None
        with jax.named_scope("layer/out"):  # (as ``layer/front``)
            x, st = jax.lax.fori_loop(0, rows.n_tiles, back_tile, (x, st0))
        return x, st, held

    if rows is not None:
        # once a forward: the residual, its angles and its cache indices by
        # packed slot. The residual STAYS packed from layer to layer
        with jax.named_scope("layer/attn_qkv/pack"):
            slots = lambda a: a.reshape(P, *a.shape[2:])[rows.idx]
            x, blk, off = slots(x), slots(blk), slots(off)
            rope = {t: tuple(slots(a) for a in cs) for t, cs in rope.items()}
    if "shared" in kd:  # a full layer's sizes: its angles
        rope["shared"] = rope["full"]
    stats, held = [], {}
    a_layer = block_layer if rows is None else walked_layer
    for L, (kind, ki) in enumerate(layer_plan(cfg)):
        x, st, held = a_layer(L, kind, ki, x, held)
        if st is not None:
            stats.append(st)

    kvp, ip = pools["full"]
    cps, rps = pools["sliding"]
    skv, = pools["shared"]
    if rows is not None:
        with jax.named_scope("layer/out/unpack"):  # once a forward: the positions the head reads
            x = x[rows.inv if logit_pos is None else
                  jnp.take_along_axis(rows.inv, logit_pos[:, None], axis=1)]
            logit_pos = None
    with jax.named_scope("final_norm"):
        if logit_pos is not None:  # the head on the one position a row reads
            x = jnp.take_along_axis(x, logit_pos[:, None, None], axis=1)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    with jax.named_scope("lm_head"):
        logits = _qe("btd,dv->btv", x, params["lm_head"])
    k_pool = {**k_pool, "kv": kvp, "idx": ip, **({} if cps is None else {"swa": cps}),
              **({} if skv is None else {"shared": skv})}
    v_pool = {**v_pool, **({} if rps is None else {"swa": rps})}
    extra = (sum(stats),) if moe_stats else ()
    if attn_stats or latent_stats:
        if attn_stats:
            extra += (_attn_stats(None, False, None, block_tables, positions, write_mask, bs),)
        if latent_stats:
            n_full, n_swa, n_shared = (layer_kinds(cfg).count(t) for t in ("full", "sliding", "shared"))
            n_sel = n_full + n_shared  # layers that gather and attend a selection
            n_alive = jnp.sum(alive).astype(jnp.int32)
            scored = n_tiles * tile
            seen = jnp.sum(jnp.where(real, pos_of + 1, 0))
            chosen = jnp.sum(jnp.where(real, jnp.minimum(pos_of + 1, K), 0))
            win = n_alive * (T // Tq) * WB * bs
            heads_swa = kd["sliding"].H if n_swa else 0
            extra += (jnp.stack([
                n_sel * scored * K + n_swa * win,
                n_alive * T * (n_sel * kd["full"].H + n_swa * heads_swa),
                n_full * scored * (N * bs), n_sel * seen, n_sel * chosen,
                n_swa * win, n_sel * n_tiles, n_sel * n_tiles * walk,
                n_full * n_tiles * (walk and pallas and K < nb * bs),
                *((n_full * n_pos, n_shared * n_pos) if cfg.indexer_types else ())
            ]).astype(jnp.int32),)
    if rows is not None:
        extra += (rows.stats,)
    return (logits, k_pool, v_pool, None, None, *extra)


# faults of this block's own mechanisms, planted in the served program for
# the comparison's limit to be set against (``benchmark/tools``): dense
# attention over every key, the first ``index_topk`` keys instead of the
# best, no window, no gate, no rescale
FAULTS = ("no_selection", "first_keys", "no_window", "no_gate", "no_rescale")
# of a selection carried across layers: a shared layer over every key, every shared layer on the
# FIRST full layer's set, a position on its neighbour's set, the index key cached unrotated, a
# value head cut to its key's width (dv > dn there)
CARRY_FAULTS = ("shared_all_keys", "first_selection", "other_row", "no_index_rope", "short_value")
