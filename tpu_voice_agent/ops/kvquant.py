"""Quantized paged-KV value layout: the ONE copy of the scale/pack math.

ISSUE 12 / ROADMAP "Quantized KV + fused Pallas decode pass". The decode
stage sits at ~101% of the int8 HBM roofline (docs/PERF.md), so the next
decode factor is moving fewer bytes per step: the paged pool's KV blocks
store ``int8`` (or opt-in packed ``int4``) values with scales that travel
with the block, halving (quartering) per-block HBM bytes — which at a
fixed pool budget also doubles (quadruples) ``paged.kv_blocks_total``.

Layout contract (every reader/writer goes through these helpers):

- values: symmetric signed integers, ``int8`` storage. The int4 tier packs
  two 4-bit values per byte along head_dim — low nibble holds dims
  ``[0, hd/2)``, high nibble dims ``[hd/2, hd)`` — so a fused kernel can
  dot the two halves separately and never materialize the unpacked tensor.
- scales: one bf16 scale per (position, kv_head), stored block-major in a
  ``(L, N, bs, nkv)`` plane indexed exactly like the pool. Scales are
  pool-indexed by block id, so radix chains and the warm-restart
  ``reserve`` path share/adopt them with zero extra bookkeeping —
  "scales travel with the block". Per-position granularity
  (finer than one scale per whole block) is what makes quantize-on-write
  exact and deterministic under the incremental decode write pattern: a
  token's row is quantized once, at write time, independent of every
  other row in the block — a per-block running max would have to
  re-quantize already-written rows with a different scale, destroying the
  differential token-identity contracts the paged plane is tested by.
- quantization is DETERMINISTIC elementwise: ``s = amax(|x|, head_dim)/Q``
  cast to bf16 (the stored dtype IS the dtype used to quantize, so encode
  and decode agree bit-for-bit), ``q = clip(round(x / s), -Q, Q)``.

Byte accounting (``kv_block_bytes`` below is the single source for the
HBM ledger plan, the ``paged.kv_bytes_per_block`` gauge, and the bench
capacity rows): per block = ``2 * L * bs * nkv * (hd * vbytes + 2)`` with
``vbytes`` 2 (off) / 1 (int8) / 0.5 (int4) and 2 bytes of bf16 scale per
(position, head) per tensor. At serving head dims (64/128) that is
~1.94x / ~3.8x fewer bytes per block than bf16.
"""

from __future__ import annotations

import jax.numpy as jnp

# value grids per tier: int8 uses the full signed byte, int4 the symmetric
# nibble range (-8 is unreachable on purpose: symmetric grids keep
# quantization sign-stable and the packed arithmetic shift decode exact)
KV_QUANT_Q = {"int8": 127, "int4": 7}
# stored value bytes per head_dim element
KV_QUANT_VBYTES = {None: 2.0, "int8": 1.0, "int4": 0.5}
# bf16 scale bytes per (position, kv_head) per tensor (0 when off)
KV_SCALE_BYTES = {None: 0, "int8": 2, "int4": 2}


def kv_quant_bits(kv_quant: str | None) -> int:
    """Stored bits per KV value element (16 = unquantized bf16)."""
    return {None: 16, "int8": 8, "int4": 4}[kv_quant]


def kv_store_dim(head_dim: int, kv_quant: str | None) -> int:
    """Last-axis width of the stored pool: hd, or hd/2 packed for int4."""
    if kv_quant == "int4":
        if head_dim % 2:
            raise ValueError(f"int4 KV packing needs an even head_dim, got {head_dim}")
        return head_dim // 2
    return head_dim


def kv_store_dtype(kv_quant: str | None):
    return jnp.bfloat16 if kv_quant is None else jnp.int8


def kv_block_bytes(n_layers: int, block_size: int, n_kv_heads: int,
                   head_dim: int, kv_quant: str | None) -> int:
    """HBM bytes ONE pool block occupies (k + v + their scale planes)."""
    per_pos_head = head_dim * KV_QUANT_VBYTES[kv_quant] + KV_SCALE_BYTES[kv_quant]
    return int(2 * n_layers * block_size * n_kv_heads * per_pos_head)


def pack_int4(q: jnp.ndarray) -> jnp.ndarray:
    """(..., hd) int8 values in [-7, 7] -> (..., hd/2) packed bytes: low
    nibble = dims [0, hd/2), high nibble = dims [hd/2, hd)."""
    hd = q.shape[-1]
    lo = q[..., : hd // 2]
    hi = q[..., hd // 2:]
    return jnp.bitwise_or(jnp.bitwise_and(lo, 15), jnp.left_shift(hi, 4))


def unpack_int4(p: jnp.ndarray) -> jnp.ndarray:
    """Inverse of ``pack_int4`` (arithmetic shifts sign-extend the nibbles)."""
    lo = jnp.right_shift(jnp.left_shift(p, 4), 4)
    hi = jnp.right_shift(p, 4)
    return jnp.concatenate([lo, hi], axis=-1)


def quantize_kv(x: jnp.ndarray, kv_quant: str):
    """(..., hd) float -> (stored int8 values (..., hd or hd/2),
    bf16 scales (...,)). One scale per trailing row — the engine calls this
    with (..., nkv, hd) so scales land per (position, kv_head)."""
    Q = KV_QUANT_Q[kv_quant]
    xf = x.astype(jnp.float32)
    s = (jnp.max(jnp.abs(xf), axis=-1) / Q).astype(jnp.bfloat16)
    # guard AFTER the bf16 cast: a subnormal amax that rounds to zero must
    # still produce a usable (identity-ish) scale
    s = jnp.where(s == 0, jnp.bfloat16(1.0), s)
    q = jnp.clip(jnp.round(xf / s.astype(jnp.float32)[..., None]), -Q, Q)
    q = q.astype(jnp.int8)
    if kv_quant == "int4":
        q = pack_int4(q)
    return q, s


def dequantize_kv(q: jnp.ndarray, s: jnp.ndarray, kv_quant: str,
                  dtype=jnp.bfloat16) -> jnp.ndarray:
    """Stored values + scales -> (..., hd) in ``dtype``. The XLA read paths
    (prefill gather, fresh-block attention) use this; the Pallas decode
    kernels never materialize it — they fold the per-position scale into
    the score/probability tiles instead (see ops.paged_attention)."""
    if kv_quant == "int4":
        q = unpack_int4(q)
    return (q.astype(jnp.float32) * s.astype(jnp.float32)[..., None]).astype(dtype)
