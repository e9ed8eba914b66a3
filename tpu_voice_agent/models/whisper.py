"""Whisper-family speech encoder-decoder, TPU-first functional JAX.

This is the in-tree replacement for the reference's Deepgram cloud STT
(apps/voice/src/deepgram.ts:21-67). Same design language as models/llama.py:
stacked layer params under ``lax.scan``, static shapes, bf16 matmuls with f32
accumulation, sharding injected via ShardingRules. Architecture follows the
Whisper family: conv1d x2 (stride 1, 2) + GELU frontend, sinusoidal encoder
positions, pre-LN transformer; decoder with learned positions, causal
self-attention (KV cache) and cross-attention over the encoder output (keys/
values precomputed once per utterance); logits tied to the token embedding.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.compilewatch import watch_compiles


@dataclass(frozen=True)
class WhisperConfig:
    vocab_size: int = 4096
    n_mels: int = 80
    d_model: int = 384
    n_heads: int = 6
    enc_layers: int = 4
    dec_layers: int = 4
    max_audio_frames: int = 3000  # mel frames (30 s); encoder halves this
    max_text_len: int = 448
    norm_eps: float = 1e-5

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def ffn_dim(self) -> int:
        return 4 * self.d_model

    @property
    def enc_positions(self) -> int:
        return self.max_audio_frames // 2


PRESETS: dict[str, WhisperConfig] = {
    "whisper-test": WhisperConfig(d_model=64, n_heads=4, enc_layers=2, dec_layers=2,
                                  max_audio_frames=200, max_text_len=64),
    "whisper-tiny": WhisperConfig(d_model=384, n_heads=6, enc_layers=4, dec_layers=4),
    "whisper-base": WhisperConfig(d_model=512, n_heads=8, enc_layers=6, dec_layers=6),
    "whisper-small": WhisperConfig(d_model=768, n_heads=12, enc_layers=12, dec_layers=12),
    "whisper-large-v3": WhisperConfig(d_model=1280, n_heads=20, enc_layers=32, dec_layers=32,
                                      n_mels=128),
}


# ---------------------------------------------------------------- params


def init_params(cfg: WhisperConfig, key: jax.Array, dtype=jnp.bfloat16) -> dict:
    ks = jax.random.split(key, 16)
    d, f, hd, nh = cfg.d_model, cfg.ffn_dim, cfg.head_dim, cfg.n_heads

    def w(key, *shape, scale=None):
        scale = scale if scale is not None else (shape[-2] ** -0.5 if len(shape) >= 2 else 0.02)
        return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(dtype)

    def ln(*shape):
        return {"g": jnp.ones(shape, dtype=dtype), "b": jnp.zeros(shape, dtype=dtype)}

    def attn_block(key, L, kv_dim=None):
        kv_dim = kv_dim or d
        k1, k2, k3, k4 = jax.random.split(key, 4)
        return {
            "wq": w(k1, L, d, nh * hd),
            "wk": w(k2, L, kv_dim, nh * hd),
            "wv": w(k3, L, kv_dim, nh * hd),
            "wo": w(k4, L, nh * hd, d),
            "bq": jnp.zeros((L, nh * hd), dtype=dtype),
            "bv": jnp.zeros((L, nh * hd), dtype=dtype),
            "bo": jnp.zeros((L, d), dtype=dtype),
        }

    Le, Ld = cfg.enc_layers, cfg.dec_layers
    return {
        "encoder": {
            "conv1": {"w": w(ks[0], 3, cfg.n_mels, d), "b": jnp.zeros((d,), dtype=dtype)},
            "conv2": {"w": w(ks[1], 3, d, d), "b": jnp.zeros((d,), dtype=dtype)},
            "layers": {
                "ln1": ln(Le, d),
                "attn": attn_block(ks[2], Le),
                "ln2": ln(Le, d),
                "w1": w(ks[3], Le, d, f),
                "b1": jnp.zeros((Le, f), dtype=dtype),
                "w2": w(ks[4], Le, f, d),
                "b2": jnp.zeros((Le, d), dtype=dtype),
            },
            "ln_post": ln(d),
        },
        "decoder": {
            "tok_emb": w(ks[5], cfg.vocab_size, d, scale=0.02),
            "pos_emb": w(ks[6], cfg.max_text_len, d, scale=0.02),
            "layers": {
                "ln1": ln(Ld, d),
                "self_attn": attn_block(ks[7], Ld),
                "ln2": ln(Ld, d),
                "cross_attn": attn_block(ks[8], Ld),
                "ln3": ln(Ld, d),
                "w1": w(ks[9], Ld, d, f),
                "b1": jnp.zeros((Ld, f), dtype=dtype),
                "w2": w(ks[10], Ld, f, d),
                "b2": jnp.zeros((Ld, d), dtype=dtype),
            },
            "ln_final": ln(d),
        },
    }


def layer_norm(x: jax.Array, p: dict, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * p["g"] + p["b"]


def _sinusoid_pos(n_pos: int, d: int) -> np.ndarray:
    """Whisper-style sinusoidal position table (n_pos, d)."""
    log_timescale = np.log(10_000.0) / (d // 2 - 1)
    inv = np.exp(-log_timescale * np.arange(d // 2))
    scaled = np.arange(n_pos)[:, None] * inv[None, :]
    return np.concatenate([np.sin(scaled), np.cos(scaled)], axis=1).astype(np.float32)


def _mha(q, k, v, mask, nh, hd):
    """q (B,Tq,D), k/v (B,Tk,D) -> (B,Tq,D); mask (B,Tq,Tk) bool or None."""
    B, Tq, _ = q.shape
    Tk = k.shape[1]
    qh = q.reshape(B, Tq, nh, hd)
    kh = k.reshape(B, Tk, nh, hd)
    vh = v.reshape(B, Tk, nh, hd)
    scores = jnp.einsum("bqnh,bknh->bnqk", qh, kh, preferred_element_type=jnp.float32)
    scores = scores * (hd**-0.5)
    if mask is not None:
        scores = jnp.where(mask[:, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bnqk,bknh->bqnh", probs.astype(vh.dtype), vh,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Tq, nh * hd).astype(q.dtype)


def _proj(x, w, b=None):
    y = jnp.einsum("btd,dh->bth", x, w, preferred_element_type=jnp.float32).astype(x.dtype)
    return y + b if b is not None else y


# ---------------------------------------------------------------- encoder


@watch_compiles("whisper.encoder_forward")
@partial(jax.jit, static_argnames=("cfg", "rules", "attn_impl"))
def encoder_forward(
    params: dict, cfg: WhisperConfig, mel: jax.Array, rules=None, attn_impl: str = "xla",
    pos_offset: jax.Array | None = None,
) -> jax.Array:
    """mel (B, T, n_mels) -> (B, T//2, d_model). T must equal max_audio_frames
    for the bucket being compiled (pad with the mel floor).

    ``attn_impl="pallas"`` routes self-attention through ops.flash_attention
    (non-causal) — the encoder's (T/2)^2 attention is the dominant cost at
    whisper-large's 1500 frames.

    ``pos_offset`` (scalar, encoder-frame units) places this block's
    sinusoidal positions at its true offset inside the utterance — the
    incremental streaming path (serve.stt.SpeechEngine.incremental_feed)
    encodes ~0.5 s blocks with block-local attention instead of
    re-encoding the whole window per partial."""
    p = params["encoder"]
    cs = lambda x, name: rules.constrain(x, name) if rules is not None else x
    dn = ("NWC", "WIO", "NWC")
    with jax.named_scope("encoder/conv"):
        x = jax.lax.conv_general_dilated(
            mel.astype(p["conv1"]["w"].dtype), p["conv1"]["w"], (1,), "SAME", dimension_numbers=dn
        ) + p["conv1"]["b"]
        x = jax.nn.gelu(x)
        x = jax.lax.conv_general_dilated(
            x, p["conv2"]["w"], (2,), "SAME", dimension_numbers=dn
        ) + p["conv2"]["b"]
        x = jax.nn.gelu(x)  # (B, T//2, d)
        T2 = x.shape[1]
        table = jnp.asarray(_sinusoid_pos(cfg.enc_positions, cfg.d_model))
        if pos_offset is None:
            pos = table[:T2]
        else:
            pos = jax.lax.dynamic_slice_in_dim(table, pos_offset, T2, axis=0)
        x = (x + pos.astype(x.dtype)[None])
        x = cs(x, "act")

    nh, hd = cfg.n_heads, cfg.head_dim

    def layer(x, lp):
        with jax.named_scope("encoder/layer"):
            h = layer_norm(x, {"g": lp["ln1"]["g"], "b": lp["ln1"]["b"]}, cfg.norm_eps)
            a = lp["attn"]
            q = _proj(h, a["wq"], a["bq"])
            k = _proj(h, a["wk"])
            v = _proj(h, a["wv"], a["bv"])
            if attn_impl == "pallas":
                from ..ops import sharded_flash_attention

                B, T2l, _ = q.shape
                mesh = rules.mesh if rules is not None else None
                attn = sharded_flash_attention(
                    mesh, q.reshape(B, T2l, nh, hd), k.reshape(B, T2l, nh, hd),
                    v.reshape(B, T2l, nh, hd), causal=False,
                ).reshape(B, T2l, nh * hd)
            else:
                attn = _mha(q, k, v, None, nh, hd)
            x = x + cs(_proj(attn, a["wo"], a["bo"]), "act")
            h = layer_norm(x, {"g": lp["ln2"]["g"], "b": lp["ln2"]["b"]}, cfg.norm_eps)
            h = jax.nn.gelu(_proj(h, lp["w1"], lp["b1"]))
            x = x + cs(_proj(h, lp["w2"], lp["b2"]), "act")
        return x, None

    x, _ = jax.lax.scan(lambda carry, lp: layer(carry, lp), x, p["layers"])
    return layer_norm(x, p["ln_post"], cfg.norm_eps)


# ---------------------------------------------------------------- decoder


def init_self_cache(cfg: WhisperConfig, batch: int, dtype=jnp.bfloat16) -> dict:
    shape = (cfg.dec_layers, batch, cfg.max_text_len, cfg.n_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype=dtype), "v": jnp.zeros(shape, dtype=dtype)}


def init_cross_kv_pool(cfg: WhisperConfig, slots: int, dtype=jnp.bfloat16) -> dict:
    """S-slot cross-attention KV pool for multi-stream batched STT serving:
    one shared (L, S, enc_positions, nh, hd) buffer whose slot axis doubles
    as the batch axis of the batched decode. Each live utterance owns one
    slot; per-slot validity is a host-side ``enc_len`` the decode turns into
    an encoder mask (stale positions beyond a slot's enc_len are masked, so
    slot reuse never needs a zeroing pass)."""
    shape = (cfg.dec_layers, slots, cfg.enc_positions, cfg.n_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype=dtype), "v": jnp.zeros(shape, dtype=dtype)}


def pad_cross_kv(cross_kv: dict, total: int) -> dict:
    """Zero-pad cross-KV along the encoder-position axis to ``total`` so the
    batched STT plane can mix ragged buckets in ONE fixed-shape decode
    dispatch (padded positions are masked by enc_mask; a masked score of
    -1e30 underflows exp() to exactly 0.0, so padding is numerically inert,
    not approximate). The B=1 plane decodes at each bucket's own length —
    a short utterance must not read the full window's KV per step."""
    T = cross_kv["k"].shape[2]
    if T == total:
        return cross_kv
    if T > total:
        raise ValueError(f"cross-KV length {T} exceeds pad target {total}")
    pad = [(0, 0), (0, 0), (0, total - T), (0, 0), (0, 0)]
    return {"k": jnp.pad(cross_kv["k"], pad), "v": jnp.pad(cross_kv["v"], pad)}


@watch_compiles("whisper.compute_cross_kv")
@partial(jax.jit, static_argnames=("cfg", "rules"))
def compute_cross_kv(params: dict, cfg: WhisperConfig, enc_out: jax.Array, rules=None) -> dict:
    """Precompute per-layer cross-attention K/V from encoder output (one
    matmul pair per layer per utterance, reused for every decode step)."""
    a = params["decoder"]["layers"]["cross_attn"]
    B, T, _ = enc_out.shape
    nh, hd = cfg.n_heads, cfg.head_dim

    def one(carry, wkv):
        wk, wv, bv = wkv
        k = jnp.einsum("btd,dh->bth", enc_out, wk, preferred_element_type=jnp.float32)
        v = jnp.einsum("btd,dh->bth", enc_out, wv, preferred_element_type=jnp.float32) + bv
        return carry, (k.astype(enc_out.dtype).reshape(B, T, nh, hd),
                       v.astype(enc_out.dtype).reshape(B, T, nh, hd))

    _, (ks, vs) = jax.lax.scan(one, None, (a["wk"], a["wv"], a["bv"]))
    return {"k": ks, "v": vs}  # (L, B, T_enc, nh, hd)


# analyze: ok[jit-sentinel] -- traced inline by the watched stt._stt_decode_loop; host-dispatched only in offline distill training
@partial(jax.jit, static_argnames=("cfg", "rules", "attn_impl"))
def decoder_forward(
    params: dict,
    cfg: WhisperConfig,
    tokens: jax.Array,  # (B, T)
    positions: jax.Array,  # (B, T)
    self_cache: dict,
    cross_kv: dict,
    enc_mask: jax.Array,  # (B, T_enc) bool — valid encoder frames (prefix)
    rules=None,
    attn_impl: str = "xla",  # "pallas": T==1 steps use ops.decode_attention
) -> tuple[jax.Array, dict]:
    p = params["decoder"]
    cs = lambda x, name: rules.constrain(x, name) if rules is not None else x
    B, T = tokens.shape
    S = self_cache["k"].shape[2]
    nh, hd = cfg.n_heads, cfg.head_dim

    x = p["tok_emb"][tokens] + p["pos_emb"][jnp.clip(positions, 0, cfg.max_text_len - 1)]
    x = cs(x, "act")

    frontier = jnp.max(positions, axis=1)
    kv_valid = jnp.arange(S)[None, :] <= frontier[:, None]  # (B, S)
    slot_pos = jnp.arange(S)[None, None, :]
    causal = slot_pos <= positions[:, :, None]  # (B, T, S)
    self_mask = causal & kv_valid[:, None, :]
    cross_mask = jnp.broadcast_to(enc_mask[:, None, :], (B, T, enc_mask.shape[1]))
    # enc_mask is prefix-shaped (valid frames 0..n-1), so the pallas decode
    # kernel can treat cross attention as cache attention with kv_len = n
    enc_len = jnp.sum(enc_mask.astype(jnp.int32), axis=-1)
    batch_idx = jnp.arange(B)[:, None]
    use_pallas_step = attn_impl == "pallas" and T == 1

    def layer(x, inp):
        lp, k_cache, v_cache, ck, cv = inp
        with jax.named_scope("decoder/self_attn"):
            # self attention with cache
            h = layer_norm(x, lp["ln1"], cfg.norm_eps)
            a = lp["self_attn"]
            q = _proj(h, a["wq"], a["bq"]).reshape(B, T, nh, hd)
            k = _proj(h, a["wk"]).reshape(B, T, nh, hd)
            v = _proj(h, a["wv"], a["bv"]).reshape(B, T, nh, hd)
            k_cache = k_cache.at[batch_idx, positions].set(k)
            v_cache = v_cache.at[batch_idx, positions].set(v)
            if use_pallas_step:
                from ..ops import sharded_decode_attention

                mesh = rules.mesh if rules is not None else None
                attn = sharded_decode_attention(mesh, q[:, 0], k_cache, v_cache, frontier + 1)
                attn = attn.reshape(B, T, nh * hd).astype(x.dtype)
            else:
                scores = jnp.einsum("btnh,bsnh->bnts", q, k_cache, preferred_element_type=jnp.float32)
                scores = scores * (hd**-0.5)
                scores = jnp.where(self_mask[:, None, :, :], scores, -1e30)
                probs = jax.nn.softmax(scores, axis=-1)
                attn = jnp.einsum("bnts,bsnh->btnh", probs.astype(x.dtype), v_cache,
                                  preferred_element_type=jnp.float32)
                attn = attn.reshape(B, T, nh * hd).astype(x.dtype)
            x = x + cs(_proj(attn, a["wo"], a["bo"]), "act")

        with jax.named_scope("decoder/cross_attn"):
            # cross attention over precomputed encoder K/V
            h = layer_norm(x, lp["ln2"], cfg.norm_eps)
            ca = lp["cross_attn"]
            qc = _proj(h, ca["wq"], ca["bq"]).reshape(B, T, nh, hd)
            if use_pallas_step:
                from ..ops import sharded_decode_attention

                mesh = rules.mesh if rules is not None else None
                attn = sharded_decode_attention(mesh, qc[:, 0], ck, cv, enc_len)
                attn = attn.reshape(B, T, nh * hd).astype(x.dtype)
            else:
                scores = jnp.einsum("btnh,bsnh->bnts", qc, ck, preferred_element_type=jnp.float32)
                scores = scores * (hd**-0.5)
                scores = jnp.where(cross_mask[:, None, :, :], scores, -1e30)
                probs = jax.nn.softmax(scores, axis=-1)
                attn = jnp.einsum("bnts,bsnh->btnh", probs.astype(x.dtype), cv,
                                  preferred_element_type=jnp.float32)
                attn = attn.reshape(B, T, nh * hd).astype(x.dtype)
            x = x + cs(_proj(attn, ca["wo"], ca["bo"]), "act")

        with jax.named_scope("decoder/ffn"):
            h = layer_norm(x, lp["ln3"], cfg.norm_eps)
            h = jax.nn.gelu(_proj(h, lp["w1"], lp["b1"]))
            x = x + cs(_proj(h, lp["w2"], lp["b2"]), "act")
        return x, (k_cache, v_cache)

    x, (new_k, new_v) = jax.lax.scan(
        lambda carry, inp: layer(carry, inp),
        x,
        (p["layers"], self_cache["k"], self_cache["v"], cross_kv["k"], cross_kv["v"]),
    )
    with jax.named_scope("lm_head"):
        x = layer_norm(x, p["ln_final"], cfg.norm_eps)
        logits = jnp.einsum("btd,vd->btv", x, p["tok_emb"], preferred_element_type=jnp.float32)
    return logits, {"k": new_k, "v": new_v}


def param_count(cfg: WhisperConfig) -> int:
    import math

    d, f = cfg.d_model, cfg.ffn_dim
    enc = 3 * cfg.n_mels * d + 3 * d * d + cfg.enc_layers * (4 * d * d + 2 * d * f)
    dec = cfg.vocab_size * d + cfg.max_text_len * d + cfg.dec_layers * (8 * d * d + 2 * d * f)
    return enc + dec
