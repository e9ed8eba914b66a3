"""A ``deepseek_v3`` block (Moonlight-16B-A3B), the served forward: LATENT
attention, leading dense layers, and a router that selects by score + bias.
A ``LlamaConfig`` with ``kv_lora_rank`` > 0; ``llama.forward_paged`` hands
its arguments on to ``forward_paged`` here on that, as it does a
``SambaYConfig``'s to ``models.sambay``.

With d the hidden size, H heads, h = RMSNorm(x) (eps ``norm_eps``):

    q = h W_q                         H heads of [q_n (dn) | q_r (dr)]
    [c' (C) | r' (dr)] = h W_kva      C = kv_lora_rank
    c = RMSNorm(c'; g_kv, eps ``latent_norm_eps``)
    r = RoPE(r'),  q_r = RoPE(q_r)    interleaved pairs (x[2i], x[2i+1]),
                                      ONE r for all heads
    [k_n | v]_head = c W_kvb          C -> H x (dn + dv)
    score = (q_n . k_n + q_r . r) (dn + dr)^-0.5,  causal softmax
    o_head = sum p v;  x += concat(o) W_o

SERVED, the cache holds [c | r] alone — C + dr values a token a layer, where
decompressed K and V would be H (dn + dr + dv) — and every path attends by
the ABSORBED identity: q_c = q_n W_UK^T (dn -> C a head; W_UK the k_n columns
of W_kvb), score = (q_c . c + q_r . r) (dn + dr)^-0.5, o_head = (sum p c)
W_UV. An identity in exact arithmetic; no program materialises K or V of a
cached position.

Layers 0 .. ``first_dense_layers`` - 1: x += W_down (silu(h W_gate) * h W_up)
at ``dense_ffn_dim``. The rest: s = sigmoid(h W_g) (E wide, float32); the
``top_k`` experts with the largest s + b (``router_bias``); gates g = s of
the chosen WITHOUT b, g /= sum g, g *= ``router_scale``;
x += sum g_e E_e(h) + S(h), S ONE SwiGLU of ``n_shared_experts`` x
``ffn_dim`` columns (the shared experts summed: ``shared_sum``) —
``llama._ffn``, the routed block every routed model here runs.

Parameters: ``dense_layers`` stacks the leading layers, ``layers`` the routed
ones (a scan; the grouped kernel takes its planes stacked and the layer's
index). The pool's planes are indexed by the layer's index in the MODEL.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .llama import (MAX_BLOCK_DECODE_T, LlamaConfig, _attn_stats, _ffn, _qe, _scan_and_whole,
                    _swiglu, _EXPERT_LEAVES, apply_rope_interleaved, cache_planes, ffn_pack_index,
                    gather_row_blocks, packed_ffn, rms_norm, rope_tables, rows_written, write_rows,
                    write_walk)

F32 = jnp.float32

# what a latent forward counts beside ``ops.ATTN_STATS`` (summed over layers
# by the forward, over forwards by the chunk loop; ``scheduler`` publishes
# them as ``attn.<name>``): cached positions attention READ — whole blocks,
# one the live rows hold in common ONCE — and the query rows (positions x
# heads of live rows) it served
LATENT_STATS = ("latent_keys_read", "latent_query_rows")


class LatentCacheOnly(ValueError):
    """A serving feature that reads, moves, shares, re-stores or shards K and
    V planes by head was asked of a model whose cache is a latent and a shared
    rotated key (and, behind an indexer, an index key): it has no such planes."""


def cache_spec(cfg: LlamaConfig) -> dict:
    """What a token holds in the pool a layer: a latent in the k pool's one
    plane, ONE rotated key in the v pool's, each of its own width (no heads
    axis: of one, it would pad every position sixteenfold)."""
    return cache_planes({"kv": (cfg.n_layers, cfg.kv_lora_rank)}, {"kv": (cfg.n_layers, cfg.qk_rope_dim)})


# ---------------------------------------------------------------- params


def attn_shapes(cfg: LlamaConfig) -> dict:
    """The attention matrices of one layer, (fan_in, fan_out)."""
    d, H = cfg.dim, cfg.n_heads
    return {"wq": (d, H * cfg.head_dim), "w_kva": (d, cfg.kv_lora_rank + cfg.qk_rope_dim),
            "w_kvb": (cfg.kv_lora_rank, H * (cfg.qk_nope_dim + cfg.v_head_dim)),
            "wo": (H * cfg.v_head_dim, d)}


def init_params(cfg: LlamaConfig, key: jax.Array, dtype=jnp.bfloat16) -> dict:
    """Random init in ``llama.init_params``' recipe (normal, fan_in^-0.5;
    gains 1), the router's bias drawn NONZERO so that a test can tell
    selection from gates."""
    k_embed, k_dense, k_routed, k_head = jax.random.split(key, 4)
    d, f, E = cfg.dim, cfg.ffn_dim, cfg.n_experts
    n_dense, n_routed = cfg.first_dense_layers, cfg.n_layers - cfg.first_dense_layers

    def w(key, *shape, fan=None):
        return (jax.random.normal(key, shape, F32) * (fan or shape[-2]) ** -0.5).astype(dtype)

    def stack(key, L, ffn: dict) -> dict:
        names = {**attn_shapes(cfg), **ffn}
        ks = jax.random.split(key, len(names))
        out = {n: w(k, L, *s) for (n, s), k in zip(names.items(), ks)}
        return {**out, "attn_norm": jnp.ones((L, d), dtype), "mlp_norm": jnp.ones((L, d), dtype),
                "kv_norm": jnp.ones((L, cfg.kv_lora_rank), dtype)}

    fd, sf = cfg.dense_ffn_dim, cfg.n_shared_experts * f
    routed = stack(k_routed, n_routed, {
        "router": (d, E), "moe_gate": (cfg.n_held, d, f), "moe_up": (cfg.n_held, d, f),
        "moe_down": (cfg.n_held, f, d),
        **({"shared_gate": (d, sf), "shared_up": (d, sf), "shared_down": (sf, d)} if sf else {})})
    if cfg.router_bias:
        routed["router_bias"] = 0.1 * jax.random.normal(
            jax.random.fold_in(k_routed, 1), (n_routed, E), F32)
    params = {"embed": w(k_embed, cfg.vocab_size, d, fan=d), "layers": routed,
              "final_norm": jnp.ones((d,), dtype), "lm_head": w(k_head, d, cfg.vocab_size)}
    if n_dense:
        params["dense_layers"] = stack(k_dense, n_dense, {
            "w_gate": (d, fd), "w_up": (d, fd), "w_down": (fd, d)})
    return params


# ---------------------------------------------------------------- layers


def _split_kvb(leaf, cfg: LlamaConfig):
    """W_kvb (C, H x (dn + dv)), int8 {"q", "s"} or plain -> (W_UK (C, H, dn),
    its per-column scale (H, dn) or None, W_UV (C, H, dv), its scale)."""
    H, dn = cfg.n_heads, cfg.qk_nope_dim
    heads = lambda a: a.reshape(*a.shape[:-1], H, dn + cfg.v_head_dim)
    if isinstance(leaf, dict):
        q, s = heads(leaf["q"]), heads(leaf["s"])[0]
        return q[..., :dn], s[:, :dn], q[..., dn:], s[:, dn:]
    w = heads(leaf)
    return w[..., :dn], None, w[..., dn:], None


def latent_qkv(p, x, cfg: LlamaConfig, cos, sin):
    """The front half of a layer: -> (q_c (B, T, H, C) with W_UK absorbed,
    q_r (B, T, H, dr) rotated, c (B, T, C) normed, r (B, T, dr) rotated)."""
    B, T = x.shape[:2]
    H, dn, C = cfg.n_heads, cfg.qk_nope_dim, cfg.kv_lora_rank
    with jax.named_scope("layer/attn_qkv"):
        h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
        q = _qe("btd,dh->bth", h, p["wq"]).astype(x.dtype).reshape(B, T, H, cfg.head_dim)
        with jax.named_scope("kv_a"):
            cr = _qe("btd,dh->bth", h, p["w_kva"]).astype(x.dtype)
            c = rms_norm(cr[..., :C], p["kv_norm"], cfg.latent_norm_eps)
            r = apply_rope_interleaved(cr[..., None, C:], cos, sin)[:, :, 0]
        q_r = apply_rope_interleaved(q[..., dn:], cos, sin)
        with jax.named_scope("q_absorb"):
            w_uk, s_k, _, _ = _split_kvb(p["w_kvb"], cfg)
            q_n = q[..., :dn]
            if s_k is not None:  # a scale a column of W_UK: on the query, which contracts it
                q_n = (q_n.astype(F32) * s_k).astype(x.dtype)
            q_c = jnp.einsum("bthn,chn->bthc", q_n, w_uk.astype(x.dtype),
                             preferred_element_type=F32).astype(x.dtype)
    return q_c, q_r, c, r


def latent_out(p, a, cfg: LlamaConfig, dtype):
    """(B, T, H, C) attended latents -> (B, T, H * dv): W_UV a head."""
    with jax.named_scope("layer/attn_out"), jax.named_scope("v_up"):
        _, _, w_uv, s_v = _split_kvb(p["w_kvb"], cfg)
        o = jnp.einsum("bthc,chv->bthv", a.astype(dtype), w_uv.astype(dtype),
                       preferred_element_type=F32)
        if s_v is not None:
            o = o * s_v
        return o.astype(dtype).reshape(*a.shape[:2], -1)


def _dense_ffn(p, h, cfg: LlamaConfig, n_rows=None):
    """A leading layer's SwiGLU at ``dense_ffn_dim`` -> (y, no routed stats);
    every row it is handed, real or filler (``n_rows`` is the experts')."""
    with jax.named_scope("layer/ffn"), jax.named_scope("dense"):
        return _swiglu(p, h, ("w_gate", "w_up", "w_down")).astype(h.dtype), None


# ---------------------------------------------------------------- forward


def forward_paged(params, cfg: LlamaConfig, tokens, positions, c_pool, r_pool, block_tables, *,
                  attn_impl: str = "pallas", write_mask=None, trash_idx=None,
                  fresh_block: bool = False, gather_blocks: int | None = None, n_real=None,
                  logit_pos=None, moe_stats: bool = False, attn_stats: bool = False,
                  latent_stats: bool = False, kv_stats: bool = False, ffn_pack: int = 0):
    """``llama.forward_paged`` for a latent model: ``c_pool`` (L, N, bs, C)
    and ``r_pool`` (L, N, bs, dr) in the places of ``k_pool`` / ``v_pool``.
    -> (logits, c_pool, r_pool, None, None), then with ``moe_stats`` the
    routed layers' ``llama.MOE_STATS``, with ``attn_stats`` ``ops.ATTN_STATS``
    (a LAYER's read, as every ``LlamaConfig``'s), with ``latent_stats``
    ``LATENT_STATS`` over all layers, with ``kv_stats`` ``llama.KV_STATS`` (the latent and
    the rotated key are its two pools), with a packed MLP ``llama.FFN_STATS``.

    Attention: T <= ``MAX_BLOCK_DECODE_T`` under "pallas" — a decode step, a
    fast-forward block — goes through ``ops.paged_latent_attention`` (T = 1
    too; told ``n_real`` it multiplies the real positions' query rows alone,
    and a position behind them returns its row's last real one's); a fresh
    block attends its own latents; everything else (a suffix behind the
    cached prefix) gathers the row's covered blocks of BOTH planes and
    attends in XLA, absorbed like the rest."""
    from ..ops.latent_attention import (latent_attention_reference, latent_row_splits,
                                        paged_latent_attention)

    B, T = tokens.shape
    N, bs = c_pool.shape[1], c_pool.shape[2]
    H, C, dr = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_rope_dim
    scale = cfg.head_dim ** -0.5
    nb = gather_blocks if gather_blocks is not None else block_tables.shape[1]
    n_dense = cfg.first_dense_layers

    with jax.named_scope("embed"):
        x = params["embed"][tokens]
        cos, sin = rope_tables(positions, dr, cfg.rope_theta)
    # where each position's latent lands, as (block, offset): the pool is
    # indexed as it is shaped (a flat view is relaid out around a scatter)
    blk = jnp.take_along_axis(block_tables, positions // bs, axis=1)
    off = positions % bs
    if write_mask is not None:
        park = jnp.zeros((B,), jnp.int32) if trash_idx is None else trash_idx.astype(jnp.int32)
        blk = jnp.where(write_mask[:, None], blk, park[:, None] // bs)
        off = jnp.where(write_mask[:, None], off, park[:, None] % bs)
    # told its rows' real positions, the write walks tiles of them (``llama.write_rows``)
    live = None
    if n_real is not None:
        live = n_real if write_mask is None else jnp.where(write_mask, n_real, 0)
    with jax.named_scope("layer/kv_write"):
        write_tiles, write_at = write_walk(live, T, (blk, off))

    block_decode = attn_impl == "pallas" and not fresh_block and T <= MAX_BLOCK_DECODE_T
    split = None
    if block_decode:
        with jax.named_scope("layer/attn/split"):
            # ``n_real``: the kernel multiplies the real positions' query rows
            # alone (a row outside ``write_mask`` has no item, whatever it names)
            split = latent_row_splits((B, T, H, C, dr), block_tables, positions, write_mask, bs,
                                      params["embed"].dtype.itemsize, n_real)

    pack = None
    if ffn_pack and live is not None and B * T > ffn_pack:
        with jax.named_scope("layer/ffn/pack"):
            pack = ffn_pack_index(live, T, ffn_pack)

    def layer(carry, p, li, ffn):
        x, cp, rp = carry
        q_c, q_r, c, r = latent_qkv(p, x, cfg, cos, sin)
        with jax.named_scope("layer/kv_write"):
            cp, rp = write_rows(cp, rp, li, c.astype(cp.dtype), r.astype(rp.dtype),
                                write_at, write_tiles)
        with jax.named_scope("layer/attn"):
            if block_decode:
                with jax.named_scope("latent"):
                    a = paged_latent_attention(q_c, q_r, cp, rp, block_tables, positions, li,
                                               write_mask, split, scale=scale)
            elif fresh_block:  # a sequence from position 0: the block's own latents
                a = latent_attention_reference(q_c, q_r, c.astype(cp.dtype), r.astype(rp.dtype),
                                               positions, scale=scale)
            else:
                with jax.named_scope("kv_gather"):
                    tbl = block_tables[:, :nb]
                    cl = gather_row_blocks(cp, li, tbl).reshape(B, nb * bs, C)
                    rl = gather_row_blocks(rp, li, tbl).reshape(B, nb * bs, dr)
                a = latent_attention_reference(q_c, q_r, cl, rl, positions, scale=scale)
        attn = latent_out(p, a, cfg, x.dtype)
        with jax.named_scope("layer/attn_out"):
            x = x + _qe("bth,hd->btd", attn, p["wo"]).astype(x.dtype)
        with jax.named_scope("layer/ffn"):
            h = rms_norm(x, p["mlp_norm"], cfg.norm_eps)
        y, stats = packed_ffn(partial(ffn, p), h, pack)
        with jax.named_scope("layer/ffn"):
            x = x + y
        return (x, cp, rp), stats

    carry = (x, c_pool, r_pool)
    with jax.named_scope("dense_layers"):
        for i in range(n_dense):  # static: a slice of the stacked leaves is a view
            p = jax.tree.map(lambda a: a[i], params["dense_layers"])
            carry, _ = layer(carry, p, jnp.int32(i), partial(_dense_ffn, cfg=cfg))

    # the routed layers, a scan: the grouped kernel takes its expert planes
    # stacked and the layer's index (``llama._scan_and_whole``), and where
    # the MLP runs packed every leaf it reads is sliced inside its branch
    scanned, whole = _scan_and_whole(params["layers"], cfg, packed=pack is not None)
    stacked = () if pack is None else tuple(
        k for k in whole if not (cfg.moe_impl == "grouped" and k in _EXPERT_LEAVES))

    def routed(carry, layer_in):
        p, j = layer_in
        if whole:
            p = {**p, **whole, "layer": j, **({"stacked": stacked} if stacked else {})}
        return layer(carry, p, j + n_dense, partial(_ffn, cfg=cfg))

    with jax.named_scope("layers"):
        (x, c_pool, r_pool), stats = jax.lax.scan(
            routed, carry, (scanned, jnp.arange(cfg.n_layers - n_dense, dtype=jnp.int32)))

    with jax.named_scope("final_norm"):
        if logit_pos is not None:  # the head on the one position a row reads
            x = jnp.take_along_axis(x, logit_pos[:, None, None], axis=1)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    with jax.named_scope("lm_head"):
        logits = _qe("btd,dv->btv", x, params["lm_head"])
    extra = (jnp.sum(stats, axis=0),) if moe_stats else ()
    if attn_stats or latent_stats:
        counts = _attn_stats(split, False, None, block_tables, positions, write_mask, bs,
                             reads=cfg.n_layers)
        if attn_stats:
            extra += (counts,)
        if latent_stats:
            alive = jnp.ones((B,), bool) if write_mask is None else write_mask
            # blocks read a layer: the common ones once, not once a rider
            n_read = (sum(s.n_items for s in split) if split is not None else counts[1])
            extra += (jnp.stack([cfg.n_layers * n_read * bs,
                                 cfg.n_layers * jnp.sum(alive) * T * H]).astype(jnp.int32),)
    if kv_stats:
        extra += (cfg.n_layers * rows_written(write_tiles, positions)[None],)
    if pack is not None:
        extra += (pack.stats,)
    return (logits, c_pool, r_pool, None, None, *extra)

