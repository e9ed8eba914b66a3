"""Paged decode attention: block-table indirection into a global KV pool.

SURVEY.md §7 step 2 names a paged KV cache; this is its attention kernel.
Sequences own non-contiguous fixed-size blocks of one pool, so HBM holds
only the context each sequence actually has (a dense per-slot cache burns
max_len capacity per slot regardless), and the shared prompt prefix can be
ONE set of blocks referenced by every sequence's table (serve.paged).

Kernel shape: one query token per row attends over its blocks. The block
table rides in scalar-prefetch SMEM and the *BlockSpec index map* does the
indirection — grid cell (b, j) streams pool block table[b, j] — so the
gather never materializes a contiguous per-sequence cache in HBM (the same
index-map trick as grammar_mask's state-indexed tiles and
decode_attention_layer's stacked-cache plane).

The pool is layer-stacked (L, N, bs, nkv, hd) with the layer index in the
scalars, so the decode loop's scan body never slices a per-layer pool.

The (B, T > 1) BLOCK kernel (grammar fast-forward) does
not let each row walk its own table: the leading blocks that live rows hold
in common — the shared prompt prefix — are read ONCE, against every row's
queries, and only a row's own blocks are read per row; see "block decode"
below. The T = 1 kernel and the ``*_quant`` twins walk row by row.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import on_cpu

_NEG_INF = -1e30


def _paged_kernel(
    scalars_ref,  # SMEM: [kv_len (B,) | layer (1,) | table (B*max_blocks,)]
    q_ref,  # (1, nkv, group, hd)
    k_ref,  # (1, 1, bs, nkv, hd) — pool block picked by the index map
    v_ref,  # like k_ref
    o_ref,  # (1, nkv, group, hd)
    acc_ref,  # VMEM (nkv, group, hd) f32
    m_ref,  # VMEM (nkv, group, 128) f32
    l_ref,  # VMEM (nkv, group, 128) f32
    *,
    scale: float,
    nkv: int,
    group: int,
    bs: int,
):
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    kv_len = scalars_ref[b]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j * bs < kv_len)
    def _tile():
        k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (group, bs), 1)
        valid = k_pos < kv_len
        for h in range(nkv):  # static unroll; nkv is small (GQA)
            q = q_ref[0, h].astype(jnp.float32)  # (group, hd)
            k = k_ref[0, 0, :, h].astype(jnp.float32)  # (bs, hd)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale
            s = jnp.where(valid, s, _NEG_INF)

            m_prev = m_ref[h, :, :1]
            l_prev = l_ref[h, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            pv = jax.lax.dot_general(
                p, v_ref[0, 0, :, h].astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc_ref[h] = acc_ref[h] * alpha + pv
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(j == nj - 1)
    def _finish():
        l = l_ref[:, :, :1]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


# analyze: ok[jit-sentinel] -- kernel wrapper traced inline by the watched engine/stt loops, never a serving dispatch entry point
@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_attention(
    q: jax.Array,  # (B, nq, hd) — one query token per row
    k_pool: jax.Array,  # (L, N, bs, nkv, hd) — global block pool
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, max_blocks) int32 pool-block ids
    kv_len: jax.Array,  # (B,) int32 valid keys per row
    layer: jax.Array,  # scalar int32 — which pool layer plane
    *,
    scale: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns (B, nq, hd) in q.dtype. Unused table entries must hold a
    valid block id (0 is fine) — tiles beyond kv_len are skipped."""
    B, nq, hd = q.shape
    bs, nkv = k_pool.shape[2], k_pool.shape[3]
    max_blocks = block_tables.shape[1]
    assert nq % nkv == 0
    group = nq // nkv
    scale = scale if scale is not None else hd**-0.5
    interpret = interpret if interpret is not None else on_cpu()
    qg = q.reshape(B, nkv, group, hd)

    scalars = jnp.concatenate([
        kv_len.astype(jnp.int32),
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        block_tables.astype(jnp.int32).reshape(-1),
    ])
    kernel = functools.partial(
        _paged_kernel, scale=scale, nkv=nkv, group=group, bs=bs
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, max_blocks),
        in_specs=[
            pl.BlockSpec((1, nkv, group, hd), lambda b, j, sc: (b, 0, 0, 0)),
            pl.BlockSpec(
                (1, 1, bs, nkv, hd),
                lambda b, j, sc, M=max_blocks: (sc[B], sc[B + 1 + b * M + j], 0, 0, 0),
            ),
            pl.BlockSpec(
                (1, 1, bs, nkv, hd),
                lambda b, j, sc, M=max_blocks: (sc[B], sc[B + 1 + b * M + j], 0, 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec((1, nkv, group, hd), lambda b, j, sc: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nkv, group, hd), jnp.float32),
            pltpu.VMEM((nkv, group, 128), jnp.float32),
            pltpu.VMEM((nkv, group, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nkv, group, hd), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(scalars, qg, k_pool, v_pool)
    return out.reshape(B, nq, hd)


# ------------------------------------------------------------ quantized pool
#
# KV_QUANT (ISSUE 12): the pool stores per-(position, head)-scaled int8 (or
# packed int4) values, so decode moves half (a quarter) of the KV bytes per
# step. Dequantization is FUSED: the per-position scale is constant along
# head_dim, so it factors OUT of both attention dots — scores multiply by
# the k-scale row after the q·k dot, probabilities multiply by the v-scale
# row before the p·v dot — and fp KV never exists in HBM or VMEM. The int4
# tier never unpacks either: low/high nibbles hold head dims [0, hd/2) and
# [hd/2, hd) (ops.kvquant pack contract), so the dots run per half.


def _qk_dot(qh, k2, bits: int, hd: int):
    """Score tile (rows, kv_rows) of fp queries against one head's stored
    values ``k2`` (kv_rows, hdp) — int4 dots its halves against the
    sign-extended nibbles. THE one copy of the packed-dot arithmetic
    (ops.kvquant pack contract: low nibble = dims [0, hd/2)), shared by
    the paged kernels here and the dense decode kernel
    (ops.decode_attention._decode_kernel_quant)."""
    if bits == 8:
        return jax.lax.dot_general(
            qh, k2.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    p32 = k2.astype(jnp.int32)  # (kv_rows, hd/2) packed
    lo = jnp.right_shift(jnp.left_shift(p32, 28), 28).astype(jnp.float32)
    hi = jnp.right_shift(p32, 4).astype(jnp.float32)
    s_lo = jax.lax.dot_general(
        qh[:, : hd // 2], lo, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    s_hi = jax.lax.dot_general(
        qh[:, hd // 2:], hi, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return s_lo + s_hi


def _pv_dot(p_scaled, v2, bits: int):
    """(rows, kv_rows) v-scaled probabilities times one head's stored
    values ``v2`` (kv_rows, hdp): (rows, hd) f32. int4 concatenates its
    two half-dim products back in the pack order (low nibble = first
    half). Shared like ``_qk_dot``."""
    if bits == 8:
        return jax.lax.dot_general(
            p_scaled, v2.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    p32 = v2.astype(jnp.int32)
    lo = jnp.right_shift(jnp.left_shift(p32, 28), 28).astype(jnp.float32)
    hi = jnp.right_shift(p32, 4).astype(jnp.float32)
    pv_lo = jax.lax.dot_general(
        p_scaled, lo, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    pv_hi = jax.lax.dot_general(
        p_scaled, hi, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    return jnp.concatenate([pv_lo, pv_hi], axis=1)


def _paged_kernel_quant(
    scalars_ref,  # SMEM: [kv_len (B,) | layer (1,) | table (B*max_blocks,)]
    q_ref,  # (1, nkv, group, hd)
    k_ref,  # (1, 1, bs, nkv, hdp) int8 — pool block picked by the index map
    v_ref,
    ks_ref,  # (1, 1, bs, nkv) bf16 per-(position, head) k scales
    vs_ref,
    o_ref,  # (1, nkv, group, hd)
    acc_ref,  # VMEM (nkv, group, hd) f32
    m_ref,  # VMEM (nkv, group, 128) f32
    l_ref,  # VMEM (nkv, group, 128) f32
    *,
    scale: float,
    nkv: int,
    group: int,
    bs: int,
    hd: int,
    bits: int,
):
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    kv_len = scalars_ref[b]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j * bs < kv_len)
    def _tile():
        k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (group, bs), 1)
        valid = k_pos < kv_len
        for h in range(nkv):  # static unroll; nkv is small (GQA)
            q = q_ref[0, h].astype(jnp.float32)  # (group, hd)
            ks = ks_ref[0, 0, :, h].astype(jnp.float32)  # (bs,)
            vs = vs_ref[0, 0, :, h].astype(jnp.float32)
            # fused dequant: the per-position scale is constant along hd,
            # so (q · (k_int * ks)) == (q · k_int) * ks — one row multiply
            # on the score tile instead of materializing fp K
            s = _qk_dot(q, k_ref[0, 0, :, h], bits, hd) * ks[None, :] * scale
            s = jnp.where(valid, s, _NEG_INF)

            m_prev = m_ref[h, :, :1]
            l_prev = l_ref[h, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            # same trick on V: (p · (v_int * vs)) == ((p * vs) · v_int)
            pv = _pv_dot(p * vs[None, :], v_ref[0, 0, :, h], bits)
            acc_ref[h] = acc_ref[h] * alpha + pv
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(j == nj - 1)
    def _finish():
        l = l_ref[:, :, :1]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


# analyze: ok[jit-sentinel] -- kernel wrapper traced inline by the watched engine/stt loops, never a serving dispatch entry point
@functools.partial(jax.jit, static_argnames=("bits", "scale", "interpret"))
def paged_attention_quant(
    q: jax.Array,  # (B, nq, hd) — one query token per row
    k_pool: jax.Array,  # (L, N, bs, nkv, hdp) int8 stored values
    v_pool: jax.Array,
    k_scale: jax.Array,  # (L, N, bs, nkv) bf16 per-(position, head) scales
    v_scale: jax.Array,
    block_tables: jax.Array,  # (B, max_blocks) int32 pool-block ids
    kv_len: jax.Array,  # (B,) int32 valid keys per row
    layer: jax.Array,  # scalar int32
    *,
    bits: int = 8,  # 8 | 4 (ops.kvquant storage contract)
    scale: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """``paged_attention`` over the quantized pool: same block-table
    indirection, dequant fused into the score/probability tiles. Returns
    (B, nq, hd) in q.dtype."""
    B, nq, hd = q.shape
    bs, nkv = k_pool.shape[2], k_pool.shape[3]
    max_blocks = block_tables.shape[1]
    assert nq % nkv == 0
    assert bits in (8, 4)
    group = nq // nkv
    scale = scale if scale is not None else hd**-0.5
    interpret = interpret if interpret is not None else on_cpu()
    qg = q.reshape(B, nkv, group, hd)

    scalars = jnp.concatenate([
        kv_len.astype(jnp.int32),
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        block_tables.astype(jnp.int32).reshape(-1),
    ])
    kernel = functools.partial(
        _paged_kernel_quant, scale=scale, nkv=nkv, group=group, bs=bs, hd=hd,
        bits=bits,
    )
    hdp = k_pool.shape[4]
    pool_spec = pl.BlockSpec(
        (1, 1, bs, nkv, hdp),
        lambda b, j, sc, M=max_blocks: (sc[B], sc[B + 1 + b * M + j], 0, 0, 0),
    )
    scale_spec = pl.BlockSpec(
        (1, 1, bs, nkv),
        lambda b, j, sc, M=max_blocks: (sc[B], sc[B + 1 + b * M + j], 0, 0),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, max_blocks),
        in_specs=[
            pl.BlockSpec((1, nkv, group, hd), lambda b, j, sc: (b, 0, 0, 0)),
            pool_spec, pool_spec, scale_spec, scale_spec,
        ],
        out_specs=pl.BlockSpec((1, nkv, group, hd), lambda b, j, sc: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nkv, group, hd), jnp.float32),
            pltpu.VMEM((nkv, group, 128), jnp.float32),
            pltpu.VMEM((nkv, group, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nkv, group, hd), q.dtype),
        interpret=interpret,
        name="paged_attention_quant",
    )(scalars, qg, k_pool, v_pool, k_scale, v_scale)
    return out.reshape(B, nq, hd)


def sharded_paged_attention_quant(
    mesh,
    q: jax.Array,  # (B, nq, hd)
    k_pool: jax.Array,  # (L, N, bs, nkv, hdp) int8
    v_pool: jax.Array,
    k_scale: jax.Array,  # (L, N, bs, nkv)
    v_scale: jax.Array,
    block_tables: jax.Array,  # (B, max_blocks) GLOBAL block ids
    kv_len: jax.Array,
    layer: jax.Array,
    **kw,
) -> jax.Array:
    """``paged_attention_quant`` over a (dp, tp) mesh — the scale planes
    shard exactly like the pool minus the head_dim axis
    (parallel.mesh.paged_scale_shardings), so each dp shard's rows read
    only local values AND local scales. Same divisibility contract as
    ``sharded_paged_attention``."""
    if mesh is None:
        return paged_attention_quant(q, k_pool, v_pool, k_scale, v_scale,
                                     block_tables, kv_len, layer, **kw)
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape.get("tp", 1)
    dp = mesh.shape.get("dp", 1)
    B, nq = q.shape[0], q.shape[1]
    N, nkv = k_pool.shape[1], k_pool.shape[3]
    tp_ax = "tp" if (tp > 1 and nq % tp == 0 and nkv % tp == 0) else None
    if dp > 1 and (B % dp != 0 or N % dp != 0):
        raise ValueError(
            f"sharded_paged_attention_quant: batch B={B} and pool blocks "
            f"N={N} must both be divisible by dp={dp}")
    dp_ax = "dp" if dp > 1 else None
    local_blocks = N // dp if dp_ax else N

    def local(q, kp, vp, ks, vs, bt, kl, layer):
        if dp_ax is not None:
            bt = bt - jax.lax.axis_index("dp") * local_blocks
        return paged_attention_quant(q, kp, vp, ks, vs, bt, kl, layer, **kw)

    qs = P(dp_ax, tp_ax, None)
    ps = P(None, dp_ax, None, tp_ax, None)
    ss = P(None, dp_ax, None, tp_ax)
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(qs, ps, ps, ss, ss, P(dp_ax, None), P(dp_ax), P()),
        out_specs=qs,
        check_vma=False,
    )
    return fn(q, k_pool, v_pool, k_scale, v_scale,
              block_tables.astype(jnp.int32), kv_len.astype(jnp.int32), layer)


def paged_attention_quant_reference(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    k_scale: jax.Array,
    v_scale: jax.Array,
    block_tables: jax.Array,
    kv_len: jax.Array,
    layer,
    *,
    bits: int = 8,
    scale: float | None = None,
) -> jax.Array:
    """Pure-jnp twin: dequantize the gathered blocks and run the plain
    reference."""
    from .kvquant import dequantize_kv

    kv_quant = "int8" if bits == 8 else "int4"
    kq = dequantize_kv(k_pool[layer], k_scale[layer], kv_quant, jnp.float32)
    vq = dequantize_kv(v_pool[layer], v_scale[layer], kv_quant, jnp.float32)
    return paged_attention_reference(
        q, kq[None], vq[None], block_tables, kv_len, 0, scale=scale)


def sharded_paged_attention(
    mesh,
    q: jax.Array,  # (B, nq, hd)
    k_pool: jax.Array,  # (L, N, bs, nkv, hd)
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, max_blocks) int32 GLOBAL block ids
    kv_len: jax.Array,  # (B,)
    layer: jax.Array,
    **kw,
) -> jax.Array:
    """paged_attention over a (dp, tp) mesh (mesh=None -> plain kernel).

    Layout mirrors parallel.mesh.paged_pool_shardings: pool blocks shard
    over dp, kv heads over tp, batch rows over dp. The allocator only hands
    a slot blocks from its own dp group's range, so each dp shard's rows
    attend entirely within the local pool shard — zero collectives, like
    the dense sharded_decode_attention. Block-table ids are global; the
    local body subtracts the shard's block offset before the kernel's
    index-map indirection."""
    if mesh is None:
        return paged_attention(q, k_pool, v_pool, block_tables, kv_len, layer, **kw)
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape.get("tp", 1)
    dp = mesh.shape.get("dp", 1)
    B, nq = q.shape[0], q.shape[1]
    N, nkv = k_pool.shape[1], k_pool.shape[3]
    tp_ax = "tp" if (tp > 1 and nq % tp == 0 and nkv % tp == 0) else None
    if dp > 1 and (B % dp != 0 or N % dp != 0):
        # never degrade to replicated in_specs here: with the pool
        # physically sharded over dp, GSPMD would all-gather the whole KV
        # pool per layer — a severe layout bug this public op must surface,
        # not hide (PagedDecodeEngine already enforces the invariants).
        raise ValueError(
            f"sharded_paged_attention: batch B={B} and pool blocks N={N} "
            f"must both be divisible by dp={dp}")
    dp_ax = "dp" if dp > 1 else None
    local_blocks = N // dp if dp_ax else N

    def local(q, kp, vp, bt, kl, layer):
        if dp_ax is not None:
            bt = bt - jax.lax.axis_index("dp") * local_blocks
        return paged_attention(q, kp, vp, bt, kl, layer, **kw)

    qs = P(dp_ax, tp_ax, None)
    ps = P(None, dp_ax, None, tp_ax, None)
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(qs, ps, ps, P(dp_ax, None), P(dp_ax), P()),
        out_specs=qs,
        check_vma=False,
    )
    return fn(q, k_pool, v_pool, block_tables.astype(jnp.int32),
              kv_len.astype(jnp.int32), layer)


def paged_attention_reference(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    kv_len: jax.Array,
    layer,
    *,
    scale: float | None = None,
) -> jax.Array:
    """Pure-jnp twin: gather each row's blocks into a contiguous cache and
    run dense masked attention."""
    B, nq, hd = q.shape
    bs, nkv = k_pool.shape[2], k_pool.shape[3]
    scale = scale if scale is not None else hd**-0.5
    kl = k_pool[layer][block_tables]  # (B, max_blocks, bs, nkv, hd)
    vl = v_pool[layer][block_tables]
    S = kl.shape[1] * bs
    k = kl.reshape(B, S, nkv, hd)
    v = vl.reshape(B, S, nkv, hd)
    group = nq // nkv
    qg = q.reshape(B, nkv, group, hd)
    scores = jnp.einsum("bkgh,bskh->bkgs", qg, k, preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(S)[None, :] < kv_len[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, nq, hd).astype(q.dtype)


# --------------------------------------------------------------- block decode
#
# Paged twin of ops.decode_attention's block kernel: grammar fast-forward
# under the batcher takes (B, 1+W) steps, and the paged pool must serve them
# without gathering each row's whole table to a contiguous cache (the T>1
# XLA fallback's cost). T queries fold into the row dimension; per-query
# write positions give intra-block causality.
#
# Two passes in one kernel (ISSUE 31). Every row of this system sits behind
# the same prompt prefix, held ONCE in the pool and named by the first
# columns of every row's table, so a kernel in which each row walks its own
# table fetches those blocks once per row. ``common_block_split`` reads, from
# the tables, the positions and which rows are live, how many leading blocks
# S the live rows hold in common and see whole, and writes ONE list of work
# items, walked by a dynamic grid (a dead tile costs nothing):
#   * the first S items are the COMMON pass: one pool block each, multiplied
#     against the queries of the rows that ride, as one (rows, hd) matrix per
#     kv head — a K block is fetched, and its heads picked apart, once for all
#     of them;
#   * the rest are the OWN pass: (row, tile) pairs, each live row's tiles
#     from its first own block (S if it rides, 0 if not) to its last query's.
#
# The two passes hold their queries in two layouts (ISSUE 48). The common pass
# has no mask and no table of a row's, so nothing in it needs a row's queries
# to be an aligned slice: it takes the riders' REAL positions (row b's are
# ``t < n_real[b]``; of a fast-forward block's 9 about 1.4), packed — riders
# in row order, ``group`` query rows a position — and walks the sub-chunks of
# that list that hold one. Its time goes with the query rows it is handed (a
# VPU-bound ~2.5 ns a (query row, kv head, block), PR 31), so it is handed
# those whose output somebody reads. The own pass keeps a row's T * group query
# rows (padded to whole sublane tiles) as ONE aligned block of the layout the
# kernel always had — (nkv, rows * Rp, hd), riders first: its mask is per
# position and its tiles the row's table's. That layout is the kernel's ONE
# query operand, resident for the walk; the packed copy is the kernel's own
# work (its first step moves each rider's block, cast up, to the row's packed
# place in VMEM). So what XLA does around the call is what it was: a gather of
# the packed rows outside cost 25-55 us a call (more than the pass it fed), and
# a second reader of the queries changed how their producer was fused — the
# hybrid's seeded plans turn on such a rounding (§6 of PERF.md, PR 48).
#
# The statistics (m, l, acc: float32) are CARRIED from one pass to the other,
# not merged: when a rider's own pass starts, its real positions' rows of state
# move from their packed place (a dynamic offset, any sublane) to the row's
# block, and its own tiles go on from what the common pass left instead of
# from (-inf, 0, 0) — every other query row of the block starts from nothing.
# That IS the log-sum-exp merge (m = max(m1, m2),
# l = e^(m1-m) l1 + e^(m2-m) l2, acc alike) with nothing written out between
# them, in the order and arithmetic of the one-pass walk: block by block,
# ascending. The division is a row's last act. A query row's dot products do
# not depend on which rows share its tile, so a real position's output is the
# same bits whatever ``n_real`` packs beside it. S = 0 (under half of the live
# rows agree, or a query inside the first block) is the old walk. A row that
# is not live has no item and its output is zero; a position that is not real
# returns its row's last real position's output (``llama.FfnPack.inv``'s rule,
# a row's last act before it is written back): where a padded position is a
# copy of that one and writes the same K/V index (``llama.forward_paged``) it
# attended to exactly that before, and the next layer's two scatters there
# must hold one value. A live row without a real position returns zeros.
#
# Behind a WINDOW (a sliding layer: ``window``, the kernel's static
# ``windowed``) the blocks most rows hold in common lie partly BEFORE the rows'
# windows, and a row's boundary block is cut by its own: what the riders can
# read once is a RANGE of columns [S0, S1) — from the first block that every
# query of every rider sees whole (``j * bs > max(qpos) - window``, and past every
# rider's boundary block) to the unwindowed pass's end (ISSUE 51). The item list
# then has three runs and the kernel three phases, each row's blocks still
# visited in ASCENDING order with the one walk's arithmetic: the riders' LOW
# walks, (row, column) from a row's boundary block to S0, masked by the window
# per query position, state from nothing — at a rider's last low item its block
# of state goes WHOLE to its packed place, as its queries went (rows in order,
# the next rider's overwrites the tail); the range's blocks, the common pass's
# body over the packed real positions, going on from that state; then every live
# row's other blocks — a rider's from S1, carried as above, another row's whole
# window in one walk. Without a range (``S1 <= S0``, too few riders) that last
# run is all there is: one walk a live row, from the block that holds the first
# position any of its queries sees. 32 rows at ~8300 behind one 8192-token head
# and a window of 4096 walk ~33 blocks a row of which 30 are the range: 1056
# items become 127, 1.33 ms a call 0.33 (my chip run, PR 51); a range of ONE
# block still pays at 32 rows and at 8 (``tools/block_attn_check.py --window``).
#
# Operands: q, k, v and p go to both dots cast up to float32, as the one-pass
# walk cast them: on the chip a float32 dot at the default precision is one
# bf16 pass of the MXU, so that is what the pool's bf16 values cost, and bf16
# operands measured 5-12 % SLOWER here (my chip runs, PR 31). With the order
# unchanged the outputs equal the one-pass kernel's bit for bit on the chip
# (``tools/block_attn_check.py``). In interpret mode they do where ``scale`` is
# a power of two: XLA's CPU compiler contracts ``dot * scale - m`` into one fused
# multiply-add in the pass that has no mask between the two (PR 51).


class BlockSplit(NamedTuple):
    """What ``common_block_split`` derives for one forward (every layer of
    it: tables do not move inside a forward, positions do between them)."""

    n_common: jax.Array  # () int32 — S, leading table columns of the common pass
    # (behind a window: the columns of its range, ``S1 - S0``)
    n_items: jax.Array  # () int32 — S + the own pass's (row, tile) pairs
    n_riders: jax.Array  # () int32
    item_block: jax.Array  # (max_blocks + B*max_blocks,) int32 — pool block
    item_row: jax.Array  # ... row (own items; rows in order)
    item_tile: jax.Array  # ... and table column of each item
    slot: jax.Array  # (B,) int32 — the row's place with the riders first, so
    # a row rides — starts from the common pass — iff slot < n_riders (the
    # latent kernel lays its queries out by it)
    order: jax.Array  # (B,) int32 — its inverse: the row at each place
    attended: jax.Array  # (B,) bool — the row has an own tile (it is live)
    counts: jax.Array  # (3,) int32 — ATTN_STATS
    pack_start: jax.Array  # (B,) int32 — the riders' real positions packed, rows
    # in order: a row's first slot ...
    pack_n: jax.Array  # (B,) int32 — ... and how many it holds: a rider's real
    # positions, 0 for a row that does not ride (``counts[2]`` in all)
    n_real: jax.Array  # (B,) int32 — every row's real positions (T where the
    # caller names none): a position behind them returns the last one's output
    n_low: jax.Array | None = None  # () int32 — behind a window: the riders' LOW
    # items, before the common blocks (which are then a RANGE of columns,
    # ``n_common`` of them from ``item_tile[n_low]``); None without a window


# what a block forward counts (``forward_paged(attn_stats=True)``; the chunk
# loops sum them, ``scheduler`` publishes them as ``attn.<name>``): row-blocks
# the common pass took, row-blocks live rows attend in all, and the query
# positions the common pass was handed (each summed over a forward's reads)
ATTN_STATS = ("common_row_blocks", "row_blocks", "common_query_rows")


def _riders(bt: jax.Array, live: jax.Array):
    """-> (the leader's row of the tables, the live rows that hold its first
    block, the columns on which every one of them holds the leader's id): the
    leader is the lowest row of the largest set of live rows that agree on
    their first block."""
    agree = (bt[:, :1] == bt[:, 0][None, :]) & live[:, None] & live[None, :]
    leader = jnp.argmax(jnp.sum(agree, axis=1))
    lead = bt[leader]
    cand = agree[leader]  # none when nothing is live
    same = jnp.all((bt == lead[None, :]) | ~cand[:, None], axis=0)  # (M,)
    return lead, cand, same


def _riders_places(rides: jax.Array, n_real: jax.Array | None, T: int):
    """-> (``order``, ``slot``: the rows with the riders first, and its inverse;
    every row's real positions; the riders' — 0 for a row that does not ride —
    and where they end, packed in row order: a row's slots start where the rows
    before it end)."""
    B = rides.shape[0]
    order = jnp.argsort(~rides, stable=True).astype(jnp.int32)
    slot = jnp.zeros((B,), jnp.int32).at[order].set(jnp.arange(B, dtype=jnp.int32))
    n_real = jnp.full((B,), T, jnp.int32) if n_real is None else jnp.clip(n_real.astype(jnp.int32), 0, T)
    packed = jnp.where(rides, n_real, 0)
    return order, slot, n_real, packed, jnp.cumsum(packed)


def common_block_split(
    block_tables: jax.Array,  # (B, max_blocks) int32
    q_positions: jax.Array,  # (B, T) int32
    live: jax.Array | None,  # (B,) bool — None: every row
    bs: int,
    window: int | None = None,  # a windowed layer: a query sees its last
    # ``window`` positions, its own among them
    n_real: jax.Array | None = None,  # (B,) int32 — None: all T of every row
) -> BlockSplit:
    """The split, from what the kernel is handed and nothing else.

    The rows that RIDE the common pass are the largest set of live rows
    that hold the same first block (ties: the set of the lowest row), so a
    row with another first block walks its whole table as before and
    switches nothing off for the others; fewer riders than half the live
    rows take no common pass at all (it pays only for blocks most rows
    read). Column j is common when every rider holds the leader's id there
    and (j+1)*bs <= the smallest query position of any rider: every query
    of every rider sees the whole block (no mask), and it is never a block
    this forward writes (a row writes at its query positions, all of them at
    or past S*bs).

    Under a ``window`` a row's walk starts at the block that holds the first
    position any of its queries sees, and what the riders read once is a RANGE
    of columns (``_window_block_split``).

    ``n_real`` moves no item: it says which of the riders' positions the
    common pass is handed (``pack_start`` / ``pack_n``; every one has a slot,
    so nothing has to fit)."""
    if window is not None:
        return _window_block_split(block_tables, q_positions, live, bs, window, n_real)
    bt = block_tables.astype(jnp.int32)
    B, M = bt.shape
    qp = q_positions.astype(jnp.int32)
    T = qp.shape[1]
    live = jnp.ones((B,), bool) if live is None else live.astype(bool)
    lead, cand, same = _riders(bt, live)
    s_agree = jnp.sum(jnp.cumprod(same.astype(jnp.int32)))
    s_pos = jnp.min(jnp.where(cand, jnp.min(qp, axis=1) // bs, M))
    S = jnp.where(2 * jnp.sum(cand) >= jnp.maximum(jnp.sum(live), 1),
                  jnp.minimum(s_agree, s_pos), 0)
    rides = cand & (S > 0)
    first = jnp.where(rides, S, 0)
    last = jnp.minimum(jnp.max(qp, axis=1) // bs, M - 1)
    n = jnp.where(live, last - first + 1, 0)
    ends = jnp.cumsum(n)
    # items: S common blocks, then each row's (row, tile) pairs, rows in order
    w = jnp.arange(M + B * M, dtype=jnp.int32)
    own = w - S
    rows = jnp.clip(jnp.sum(own[:, None] >= ends[None, :], axis=1), 0, B - 1)
    tiles = jnp.clip(first[rows] + own - (ends - n)[rows], 0, M - 1)
    blocks = jnp.where(own < 0, lead[jnp.minimum(w, M - 1)], bt[rows, tiles])
    order, slot, n_real, packed, p_ends = _riders_places(rides, n_real, T)
    counts = jnp.stack([S * jnp.sum(rides), jnp.sum(jnp.where(live, last + 1, 0)), p_ends[-1]])
    i32 = lambda x: x.astype(jnp.int32)
    return BlockSplit(i32(S), i32(S + ends[-1]), i32(jnp.sum(rides)), i32(blocks),
                      i32(rows), i32(tiles), slot, order, n > 0, i32(counts),
                      i32(p_ends - packed), i32(packed), n_real)


def _window_block_split(block_tables, q_positions, live, bs: int, window: int, n_real) -> BlockSplit:
    """``common_block_split`` behind a ``window``: what the riders read once is
    a RANGE ``[S0, S1)`` of table columns.

    The riders are the unwindowed split's, and so is ``S1``: the leading
    columns on which every rider holds the leader's id and that end at or
    before the smallest query position of any rider. ``S0`` is the first
    column whose block every query of every rider sees WHOLE —
    ``j * bs > max(qpos) - window`` over the riders — and that lies past every
    rider's boundary block (the one that holds the first position any of its
    queries sees), so a rider's state always starts in a walk of its own.
    Where ``S1 <= S0`` nobody rides. Items: the riders' LOW walks, (row,
    column) for the columns from a row's boundary block to ``S0``, rows in
    order; the range's blocks, once; then every live row's other blocks, rows
    in order — a rider's from ``S1`` to its last query's, another row's whole
    window. ``n_common`` is ``S1 - S0``, ``n_low`` the items before the
    range, ``item_tile[n_low]`` is ``S0``, and ``counts[0]`` the row-blocks
    the range took off the riders' walks (no full layer's: a caller publishes
    them under another name than ``ATTN_STATS[0]``)."""
    bt = block_tables.astype(jnp.int32)
    B, M = bt.shape
    qp = q_positions.astype(jnp.int32)
    T = qp.shape[1]
    live = jnp.ones((B,), bool) if live is None else live.astype(bool)
    lead, cand, same = _riders(bt, live)
    qmin, qmax = jnp.min(qp, axis=1), jnp.max(qp, axis=1)
    first = jnp.maximum(qmin - (window - 1), 0) // bs  # a row's boundary block
    last = jnp.minimum(qmax // bs, M - 1)
    S1 = jnp.minimum(jnp.sum(jnp.cumprod(same.astype(jnp.int32))),
                     jnp.min(jnp.where(cand, qmin // bs, M)))
    whole = -(-jnp.maximum(jnp.max(jnp.where(cand, qmax, 0)) - window + 1, 0) // bs)
    S0 = jnp.maximum(whole, jnp.max(jnp.where(cand, first + 1, 0)))
    taken = (2 * jnp.sum(cand) >= jnp.maximum(jnp.sum(live), 1)) & (S1 > S0)
    R = jnp.where(taken, S1 - S0, 0)
    rides = cand & taken
    n_low = jnp.where(rides, S0 - first, 0)
    high = jnp.where(rides, S1, first)
    n_high = jnp.where(live, last - high + 1, 0)
    low_ends, high_ends = jnp.cumsum(n_low), jnp.cumsum(n_high)
    L = low_ends[-1]
    w = jnp.arange(M + B * M, dtype=jnp.int32)

    def run(at, ends, n, start):  # the (row, column) of item ``at`` of a run of walks, rows in order
        rows = jnp.clip(jnp.sum(at[:, None] >= ends[None, :], axis=1), 0, B - 1)
        return rows, jnp.clip(start[rows] + at - (ends - n)[rows], 0, M - 1)

    low_rows, low_tiles = run(w, low_ends, n_low, first)
    # a range item names the row of the first item behind the range: its output
    # block is the one the pipeline holds next
    high_rows, high_tiles = run(jnp.maximum(w - L - R, 0), high_ends, n_high, high)
    in_low, in_range = w < L, (w >= L) & (w < L + R)
    rows = jnp.where(in_low, low_rows, high_rows)
    tiles = jnp.where(in_low, low_tiles,
                      jnp.where(in_range, jnp.minimum(S0 + w - L, M - 1), high_tiles))
    blocks = jnp.where(in_range, lead[tiles], bt[rows, tiles])
    order, slot, n_real, packed, p_ends = _riders_places(rides, n_real, T)
    counts = jnp.stack([R * jnp.sum(rides), jnp.sum(jnp.where(live, last + 1, 0)), p_ends[-1]])
    i32 = lambda x: x.astype(jnp.int32)
    return BlockSplit(i32(R), i32(L + R + high_ends[-1]), i32(jnp.sum(rides)), i32(blocks),
                      i32(rows), i32(tiles), slot, order, n_high > 0, i32(counts),
                      i32(p_ends - packed), i32(packed), n_real, i32(L))


def _softmax_tile(q, k, v, valid, m_prev, l_prev, acc_prev, scale: float):
    """One online-softmax step of (rows, hd) queries over one block's (bs, hd)
    float32 k and v: new m, l, acc. ``valid`` None: every key is seen."""
    s = jax.lax.dot_general(
        q.astype(jnp.float32), k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if valid is not None:
        s = jnp.where(valid, s, _NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return m_new, l_new, acc_prev * alpha + pv


def _paged_block_kernel(
    qpos_ref,  # SMEM (B*T,)
    meta_ref,  # SMEM (4,): [layer, S, items, packed sub-chunks that hold a query row]
    # (``windowed``: (6,), then the window — a query sees that many positions — and
    # the riders' low items, which stand before the S common blocks)
    block_ref,  # SMEM (max_blocks + B*max_blocks,): each item's pool block ...
    row_ref,  # ... row ...
    tile_ref,  # ... and table column
    slot_ref,  # SMEM (B,): the row's place in the own pass's queries, riders first
    pack_ref,  # SMEM (B,): the row's first packed query row ...
    real_ref,  # ... and how many it holds there: a rider's real ones, 0 for a row that does not ride
    nreal_ref,  # SMEM (B,): the row's real POSITIONS (t < it), a rider's or not
    q_ref,  # (nkv, B*Rp, hd) — every row's queries, riders first (row b's at slot[b] * Rp)
    k_ref,  # (1, 1, bs, nkv, hd) — pool block block[w]
    v_ref,
    o_ref,  # (nkv, Rp, hd) — row row[w]'s, rows in their own order
    qc_ref,  # VMEM (nkv, Pq + Rp, hd) f32 — the riders' real positions' queries, packed
    cacc_ref,  # VMEM (nkv, Pq + Rp, hd) f32 — the common pass's state, packed alike
    cm_ref,  # VMEM (nkv, Pq + Rp, 128) f32, a value across its lanes
    cl_ref,
    acc_ref,  # VMEM (nkv, Rp, hd) f32 — the own pass's: one row's at a time
    m_ref,  # VMEM (nkv, Rp, 128) f32
    l_ref,
    kf_ref,  # VMEM (bs, nkv, hd) f32 — a common block, cast up once for all its heads
    vf_ref,
    *,
    scale: float,
    nkv: int,
    group: int,
    T: int,
    bs: int,
    Rp: int,  # query rows a batch row holds in the own pass (T*group, padded)
    sub: int,  # query rows a sub-chunk of the common pass
    windowed: bool = False,
):
    w = pl.program_id(0)
    S, n, n_sub = meta_ref[1], meta_ref[2], meta_ref[3]
    hd = acc_ref.shape[2]
    if windowed:
        # the common blocks are a RANGE of columns, items [lo, hi): the riders'
        # low walks stand before it, every live row's other blocks behind it
        lo = meta_ref[5]
        hi = lo + S

    def advance(q, k, v, valid, refs, h, at):  # one head's rows ``at`` over one block
        acc, m, l = refs
        size = q.shape[0]
        m_new, l_new, acc_new = _softmax_tile(q, k, v, valid, m[h, at, :1], l[h, at, :1],
                                              acc[h, at, :], scale)
        acc[h, at, :] = acc_new
        m[h, at, :] = jnp.broadcast_to(m_new, (size, 128))
        l[h, at, :] = jnp.broadcast_to(l_new, (size, 128))

    packed, own = (cacc_ref, cm_ref, cl_ref), (acc_ref, m_ref, l_ref)
    chunk = lambda i: pl.ds(pl.multiple_of(i * sub, sub), sub)
    queries = lambda b: pl.ds(pl.multiple_of(slot_ref[b] * Rp, Rp), Rp)  # row b's, aligned

    @pl.when(w == 0)
    def _riders_start():
        def start(i, c):  # state from nothing, and no query a chunk's last rows may lack
            for h in range(nkv):
                qc_ref[h, chunk(i), :] = jnp.zeros((sub, hd), jnp.float32)
                cacc_ref[h, chunk(i), :] = jnp.zeros((sub, hd), jnp.float32)
                cm_ref[h, chunk(i), :] = jnp.full((sub, 128), _NEG_INF, jnp.float32)
                cl_ref[h, chunk(i), :] = jnp.zeros((sub, 128), jnp.float32)
            return c

        jax.lax.fori_loop(0, n_sub, start, 0)

        def pack(b, c):
            # a rider's block goes to its packed place WHOLE (a dynamic offset, any
            # sublane): the rows behind its real ones are the next rider's place,
            # written after it — rows in order — or behind the last, never read
            @pl.when(real_ref[b] > 0)
            def _rider():
                for h in range(nkv):
                    qc_ref[h, pl.ds(pack_ref[b], Rp), :] = q_ref[h, queries(b), :].astype(jnp.float32)

            return c

        jax.lax.fori_loop(0, slot_ref.shape[0], pack, 0)

    @pl.when(jnp.logical_and(w >= lo, w < hi) if windowed else w < S)
    def _common():  # every rider sees the whole block: no mask
        # the block to float32 WHOLE, once: a head is then a strided read of
        # 32-bit sublanes, where picking it out of the packed bf16 pairs costs
        # ~0.2 us a (block, head) — most of this pass now that its rows are few
        kf_ref[...] = k_ref[0, 0].astype(jnp.float32)
        vf_ref[...] = v_ref[0, 0].astype(jnp.float32)

        def riders(i, c):  # the heads in line, so that one's dots hide under another's softmax
            for h in range(nkv):
                advance(qc_ref[h, chunk(i), :], kf_ref[:, h, :], vf_ref[:, h, :], None, packed, h,
                        chunk(i))
            return c

        jax.lax.fori_loop(0, n_sub, riders, 0)

    @pl.when(jnp.logical_and(jnp.logical_or(w < lo, w >= hi), w < n) if windowed
             else jnp.logical_and(w >= S, w < n))
    def _own():
        b, j = row_ref[w], tile_ref[w]
        row_was = lambda: row_ref[jnp.maximum(w - 1, 0)] != b
        row_next = lambda: row_ref[jnp.minimum(w + 1, row_ref.shape[0] - 1)] != b
        if windowed:  # a rider walks twice: up to the range, and on from behind it
            first = (w == 0) | (w == hi) | row_was()
            last = (w == lo - 1) | (w == n - 1) | row_next()
        else:
            first = jnp.logical_or(w == S, row_was())
            last = jnp.logical_or(w == n - 1, row_next())

        @pl.when(first)
        def _row_start():
            # a rider's REAL positions go on from what the common pass left them,
            # every other query row from nothing: its own tiles alone (a value
            # nobody reads, but one that is a number)
            carried = jax.lax.broadcasted_iota(jnp.int32, (Rp, 1), 0) < real_ref[b]
            if windowed:  # a low walk starts from nothing, as the row's one walk did
                carried = jnp.logical_and(carried, w >= hi)
            was = pl.ds(pack_ref[b], Rp)
            for h in range(nkv):
                acc_ref[h] = jnp.where(carried, cacc_ref[h, was, :], 0.0)
                m_ref[h] = jnp.where(carried, cm_ref[h, was, :], _NEG_INF)
                l_ref[h] = jnp.where(carried, cl_ref[h, was, :], 0.0)

        k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (Rp, bs), 1)
        qpos_rows = jnp.zeros((Rp, 1), jnp.int32)  # padding rows stay at 0
        for i in range(T):
            qpos_rows = jnp.where(
                (jax.lax.broadcasted_iota(jnp.int32, (Rp, 1), 0) // group) == i,
                qpos_ref[b * T + i], qpos_rows)
        valid = k_pos <= qpos_rows  # causal + frontier in one mask
        if windowed:  # the boundary block, masked per query position
            valid = jnp.logical_and(valid, k_pos > qpos_rows - meta_ref[4])
        for h in range(nkv):
            advance(q_ref[h, queries(b), :], k_ref[0, 0, :, h].astype(jnp.float32),
                    v_ref[0, 0, :, h].astype(jnp.float32), valid, own, h, slice(None))

        if windowed:
            @pl.when(last & (w < lo) & (real_ref[b] > 0))
            def _to_the_range():
                # a rider's low walk ends: its state goes to its packed place WHOLE,
                # as its queries went (``_riders_start``): the rows behind its real
                # ones are the next rider's place, whose low walk ends after this one
                was = pl.ds(pack_ref[b], Rp)
                for h in range(nkv):
                    cacc_ref[h, was, :] = acc_ref[h]
                    cm_ref[h, was, :] = m_ref[h]
                    cl_ref[h, was, :] = l_ref[h]

        @pl.when(jnp.logical_and(last, w >= lo) if windowed else last)
        def _row_finish():
            n_pos = nreal_ref[b]
            for h in range(nkv):
                l = l_ref[h, :, :1]
                acc_ref[h] = acc_ref[h] / jnp.where(l == 0.0, 1.0, l)

            @pl.when(n_pos < T)
            def _behind_the_real_ones():
                # a position behind its row's real ones returns the last real
                # one's output (a copy of it, it attended to just that before
                # the common pass left it out); ascending, so a source is never
                # a row this loop wrote
                for t in range(1, T):
                    src = pl.ds(jnp.maximum(jnp.minimum(t, n_pos - 1), 0) * group, group)
                    for h in range(nkv):
                        acc_ref[h, t * group:(t + 1) * group, :] = acc_ref[h, src, :]

            for h in range(nkv):  # a live row without a real position: zeros
                o_ref[h] = jnp.where(n_pos > 0, acc_ref[h], 0.0).astype(o_ref.dtype)


# the kernel keeps every row's queries (the pipeline's two buffers), the packed
# float32 copy of the riders' (every position has a slot) and the packed float32
# statistics in VMEM for the whole walk: at (32 rows, 9 positions, 4 query rows a
# position, 8 kv heads of 128) 1152 query rows a head, 23 MB — more than the
# 16 MB a kernel gets unasked, a sixth of a v5e's. A row's own block (output,
# statistics) is a row's: 0.5 MB. Wider batches than fit ``_STATE_BYTES`` go
# through the kernel in groups of rows, each with a split of its own (16 query
# rows a position: 4608 a head, 94 MB; two groups of 16 rows, 47 MB each).
_VMEM_LIMIT = 64 << 20
_STATE_BYTES = 48 << 20


def _rows_that_fit(B: int, R: int, nkv: int, hd: int, itemsize: int) -> int:
    """The largest divisor of B whose rows' resident state — R = T * group
    query rows a row: the queries, double-buffered; their packed float32 copy;
    acc, m, l — stays inside ``_STATE_BYTES``."""
    per_row = nkv * R * (2 * hd * itemsize + 2 * 4 * hd + 2 * 4 * 128)
    return max([c for c in range(1, B + 1) if B % c == 0 and c * per_row <= _STATE_BYTES],
               default=1)


def _sub_rows(B: int) -> int:
    """Batch rows' worth of queries a sub-chunk of the common pass holds: a
    quarter of the rows' (the largest divisor of B at or under it). Its dots
    stream that many query rows past one head's K block, and only sub-chunks
    that hold one run: larger is faster when every position is real (at 32
    rows a quarter is within 3 % of the whole), smaller when one of eight is
    (my chip runs, PR 31). The block kernel counts them in PACKED rows, T *
    group each (``_sub_query_rows``); the latent kernel's sub-chunks are a
    fixed count of packed query rows (``latent_attention._PACK_SUB``)."""
    return max(c for c in range(1, max(B // 4, 1) + 1) if B % c == 0)


def _sub_query_rows(B: int, R: int) -> int:
    """Query rows a sub-chunk of the block kernel's common pass: ``_sub_rows``
    rows' R = T * group, to whole tiles of the queries' packed sublanes."""
    return -(-_sub_rows(B) * R // 16) * 16


def _padded_query_rows(T: int, group: int) -> int:
    """Query rows a batch row holds in the own pass's layout: T * group, padded
    to whole sublane tiles so that a row is one aligned block."""
    return -(-T * group // 16) * 16


def row_group_splits(shape: tuple[int, int, int, int, int], block_tables, q_positions, live,
                     bs: int, window: int | None = None, itemsize: int = 2,
                     n_real: jax.Array | None = None) -> tuple[BlockSplit, ...]:
    """``common_block_split`` of each group of rows ``paged_block_attention``
    walks for queries of ``shape`` (B, T, nq, nkv, hd): one split where the
    rows' resident state fits the kernel whole, else one for each group — made
    by the caller once a forward, for all its layers (and, with ``window``,
    for the layers behind that window)."""
    B, T, nq, nkv, hd = shape
    Bg = _rows_that_fit(B, T * (nq // nkv), nkv, hd, itemsize)
    cut = lambda x, g: None if x is None else x[g:g + Bg]
    return tuple(common_block_split(block_tables[g:g + Bg], q_positions[g:g + Bg], cut(live, g),
                                    bs, window, cut(n_real, g))
                 for g in range(0, B, Bg))


# analyze: ok[jit-sentinel] -- kernel wrapper traced inline by the watched engine/stt loops, never a serving dispatch entry point
@functools.partial(jax.jit, static_argnames=("scale", "interpret", "out_dtype"))
def paged_block_attention(
    q: jax.Array,  # (B, T, nq, hd) — a small block of queries per row
    k_pool: jax.Array,  # (L, N, bs, nkv, hd)
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, max_blocks) int32
    q_positions: jax.Array,  # (B, T) int32 — each query's sequence position
    layer: jax.Array,  # scalar int32
    live: jax.Array | None = None,  # (B,) bool — rows whose output is read
    split: BlockSplit | tuple | None = None,  # common_block_split of the
    # three above and ``n_real``, when the caller has it already (one forward,
    # many layers); or ``row_group_splits``' tuple, one for each group of rows
    window: jax.Array | None = None,  # scalar int32: query i attends its last
    # ``window`` positions alone (a traced value, so that layers of one scan
    # may differ; ``split`` is then the caller's, made with that window)
    n_real: jax.Array | None = None,  # (B,) int32: row b's real positions are
    # t < n_real[b] (None: all T). The common pass multiplies those alone; a
    # position behind them returns the row's last real one's output, a row
    # without one zeros. Read where the wrapper makes the split; a caller's
    # ``split`` was made with it
    *,
    scale: float | None = None,
    interpret: bool | None = None,
    out_dtype=None,  # None: q's. float32 where what follows takes a
    # DIFFERENCE of outputs (models.sambay): the accumulator is float32 anyway
) -> jax.Array:
    """Returns (B, T, nq, hd). Query i attends positions [0, q_positions
    [b, i]] of its row's paged sequence (the caller has already scattered
    the block's k/v at those positions). Unused table entries must hold a
    valid block id. A row that is not ``live`` is not attended: zeros."""
    B, T, nq, hd = q.shape
    bs, nkv = k_pool.shape[2], k_pool.shape[3]
    assert nq % nkv == 0
    group = nq // nkv
    scale = scale if scale is not None else hd**-0.5
    interpret = interpret if interpret is not None else on_cpu()
    R = T * group
    Rp = _padded_query_rows(T, group)
    out_dtype = q.dtype if out_dtype is None else jnp.dtype(out_dtype)
    Bg = _rows_that_fit(B, R, nkv, hd, q.dtype.itemsize)
    # ``row_group_splits``' tuple, or nothing (one BlockSplit is a whole batch's)
    groups = None if split is None or isinstance(split, BlockSplit) else split
    if Bg < B:
        # groups of rows, each with a split of its own: ``row_group_splits``'
        # where the caller made them once a forward, else made here
        if groups is None and window is not None:
            raise NotImplementedError(
                "a windowed layer's split is its caller's, made for one group of rows")
        cut = lambda x, g: None if x is None else x[g:g + Bg]
        return jnp.concatenate([
            paged_block_attention(
                q[g:g + Bg], k_pool, v_pool, block_tables[g:g + Bg], q_positions[g:g + Bg],
                layer, cut(live, g), None if groups is None else groups[g // Bg], window,
                cut(n_real, g), scale=scale, interpret=interpret, out_dtype=out_dtype)
            for g in range(0, B, Bg)])
    if groups is not None:
        (split,) = groups  # rows that fit whole: one group
    if split is None:
        split = common_block_split(block_tables, q_positions, live, bs, n_real=n_real)
    # the queries' layout is what it always was — (nkv, B * Rp, hd), riders
    # first, a row's T * group query rows padded to whole sublane tiles so that a
    # row is one aligned block — now resident for the whole walk: the kernel packs
    # the riders' real positions out of it itself, into whole sub-chunks
    sub = _sub_query_rows(B, R)
    Pq = -(-B * R // sub) * sub
    qg = q.reshape(B, T, nkv, group, hd).transpose(2, 0, 1, 3, 4).reshape(nkv, B, R, hd)
    qg = jnp.pad(qg[:, split.order], ((0, 0), (0, 0), (0, Rp - R), (0, 0)))
    qg = qg.reshape(nkv, B * Rp, hd)
    pool_spec = pl.BlockSpec(
        (1, 1, bs, nkv, hd), lambda w, qpos, meta, block, *_: (meta[0], block[w], 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_paged_block_kernel, scale=scale, nkv=nkv, group=group, T=T,
                          bs=bs, Rp=Rp, sub=sub,
                          **({} if window is None else {"windowed": True})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=9,
            grid=(jnp.maximum(split.n_items, 1),),
            in_specs=[pl.BlockSpec((nkv, B * Rp, hd), lambda w, *_: (0, 0, 0)),
                      pool_spec, pool_spec],
            out_specs=pl.BlockSpec((nkv, Rp, hd),
                                   lambda w, qpos, meta, block, row, *_: (0, row[w], 0)),
            scratch_shapes=[
                # a row's queries are written to, and its state read from, its
                # packed place as a whole block, T * group rows padded: that
                # much room behind the last slot
                pltpu.VMEM((nkv, Pq + Rp, hd), jnp.float32),
                pltpu.VMEM((nkv, Pq + Rp, hd), jnp.float32),
                pltpu.VMEM((nkv, Pq + Rp, 128), jnp.float32),
                pltpu.VMEM((nkv, Pq + Rp, 128), jnp.float32),
                pltpu.VMEM((nkv, Rp, hd), jnp.float32),
                pltpu.VMEM((nkv, Rp, 128), jnp.float32),
                pltpu.VMEM((nkv, Rp, 128), jnp.float32),
                pltpu.VMEM((bs, nkv, hd), jnp.float32),
                pltpu.VMEM((bs, nkv, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((nkv, B * Rp, hd), out_dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="paged_block_attention",
    )(q_positions.astype(jnp.int32).reshape(-1),
      jnp.stack([jnp.reshape(layer, ()).astype(jnp.int32), split.n_common, split.n_items,
                 -(-split.counts[2] * group // sub),
                 *(() if window is None else (jnp.reshape(window, ()).astype(jnp.int32),
                                              split.n_low))]),
      split.item_block, split.item_row, split.item_tile, split.slot,
      split.pack_start * group, split.pack_n * group, split.n_real,
      qg, k_pool, v_pool)
    # a row without an item was never written: zeros, not what the buffer held
    out = jnp.where(split.attended[None, :, None, None],
                    out.reshape(nkv, B, Rp, hd)[:, :, :R], 0)
    return (out.reshape(nkv, B, T, group, hd)
               .transpose(1, 2, 0, 3, 4)
               .reshape(B, T, nq, hd))


def paged_block_attention_reference(
    q: jax.Array,  # (B, T, nq, hd)
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    q_positions: jax.Array,  # (B, T)
    layer,
    *,
    scale: float | None = None,
) -> jax.Array:
    """Pure-jnp twin: ``paged_attention_reference``'s gather, T queries a row
    under the dense block reference's mask."""
    from .decode_attention import decode_block_attention_reference

    B = q.shape[0]
    bs, nkv, hd = k_pool.shape[2], k_pool.shape[3], k_pool.shape[4]
    S = block_tables.shape[1] * bs
    kc = k_pool[layer][block_tables].reshape(B, S, nkv, hd)
    vc = v_pool[layer][block_tables].reshape(B, S, nkv, hd)
    return decode_block_attention_reference(q, kc, vc, q_positions, scale=scale)


def sharded_paged_block_attention(
    mesh,
    q: jax.Array,  # (B, T, nq, hd)
    k_pool: jax.Array,  # (L, N, bs, nkv, hd)
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, max_blocks) GLOBAL block ids
    q_positions: jax.Array,  # (B, T)
    layer: jax.Array,
    live: jax.Array | None = None,  # (B,) bool
    split: BlockSplit | None = None,  # mesh=None only
    window: jax.Array | None = None,  # ...
    n_real: jax.Array | None = None,  # ... these two as well
    **kw,
) -> jax.Array:
    """paged_block_attention over a (dp, tp) mesh — same layout contract as
    sharded_paged_attention (pool blocks over dp, kv heads over tp, each dp
    group's rows reference only its own block range). Each dp group pins its
    own prefix blocks, so under a mesh the common-block split is derived
    shard-locally, from the shard's rows of the tables, positions and
    ``live``; ``split`` is the unmeshed caller's."""
    if mesh is None:
        return paged_block_attention(q, k_pool, v_pool, block_tables,
                                     q_positions, layer, live, split, window, n_real, **kw)
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape.get("tp", 1)
    dp = mesh.shape.get("dp", 1)
    B, T, nq = q.shape[0], q.shape[1], q.shape[2]
    N, nkv = k_pool.shape[1], k_pool.shape[3]
    tp_ax = "tp" if (tp > 1 and nq % tp == 0 and nkv % tp == 0) else None
    if dp > 1 and (B % dp != 0 or N % dp != 0):
        raise ValueError(
            f"sharded_paged_block_attention: batch B={B} and pool blocks "
            f"N={N} must both be divisible by dp={dp}")
    dp_ax = "dp" if dp > 1 else None
    local_blocks = N // dp if dp_ax else N

    def local(q, kp, vp, bt, qp, live, layer):
        if dp_ax is not None:
            bt = bt - jax.lax.axis_index("dp") * local_blocks
        return paged_block_attention(q, kp, vp, bt, qp, layer, live, **kw)

    qs = P(dp_ax, None, tp_ax, None)
    ps = P(None, dp_ax, None, tp_ax, None)
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(qs, ps, ps, P(dp_ax, None), P(dp_ax, None), P(dp_ax), P()),
        out_specs=qs,
        check_vma=False,
    )
    return fn(q, k_pool, v_pool, block_tables.astype(jnp.int32),
              q_positions.astype(jnp.int32),
              jnp.ones((B,), bool) if live is None else live.astype(bool), layer)


def _paged_block_kernel_quant(
    scalars_ref,  # SMEM: [q_pos (B*T,) | layer (1,) | table (B*max_blocks,)]
    q_ref,  # (1, nkv, T*group, hd)
    k_ref,  # (1, 1, bs, nkv, hdp) int8 — pool block picked by the index map
    v_ref,
    ks_ref,  # (1, 1, bs, nkv) bf16
    vs_ref,
    o_ref,  # (1, nkv, T*group, hd)
    acc_ref,  # VMEM (nkv, T*group, hd) f32
    m_ref,  # VMEM (nkv, T*group, 128) f32
    l_ref,
    *,
    scale: float,
    nkv: int,
    group: int,
    T: int,
    bs: int,
    hd: int,
    bits: int,
):
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    rows = T * group

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    max_pos = scalars_ref[b * T]
    for _i in range(1, T):
        max_pos = jnp.maximum(max_pos, scalars_ref[b * T + _i])

    @pl.when(j * bs <= max_pos)
    def _tile():
        k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 1)
        qpos_rows = jnp.zeros((rows, 1), jnp.int32)
        for i in range(T):
            qpos_rows = jnp.where(
                (jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // group) == i,
                scalars_ref[b * T + i], qpos_rows)
        valid = k_pos <= qpos_rows  # causal + frontier in one mask
        for h in range(nkv):
            q = q_ref[0, h].astype(jnp.float32)  # (rows, hd)
            ks = ks_ref[0, 0, :, h].astype(jnp.float32)  # (bs,)
            vs = vs_ref[0, 0, :, h].astype(jnp.float32)
            s = _qk_dot(q, k_ref[0, 0, :, h], bits, hd) * ks[None, :] * scale
            s = jnp.where(valid, s, _NEG_INF)

            m_prev = m_ref[h, :, :1]
            l_prev = l_ref[h, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            pv = _pv_dot(p * vs[None, :], v_ref[0, 0, :, h], bits)
            acc_ref[h] = acc_ref[h] * alpha + pv
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(j == nj - 1)
    def _finish():
        l = l_ref[:, :, :1]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


# analyze: ok[jit-sentinel] -- kernel wrapper traced inline by the watched engine/stt loops, never a serving dispatch entry point
@functools.partial(jax.jit, static_argnames=("bits", "scale", "interpret"))
def paged_block_attention_quant(
    q: jax.Array,  # (B, T, nq, hd)
    k_pool: jax.Array,  # (L, N, bs, nkv, hdp) int8
    v_pool: jax.Array,
    k_scale: jax.Array,  # (L, N, bs, nkv) bf16
    v_scale: jax.Array,
    block_tables: jax.Array,  # (B, max_blocks) int32
    q_positions: jax.Array,  # (B, T) int32
    layer: jax.Array,  # scalar int32
    *,
    bits: int = 8,
    scale: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """``paged_block_attention`` over the quantized pool (grammar ff chain
    steps): per-query frontiers, fused dequant."""
    B, T, nq, hd = q.shape
    bs, nkv = k_pool.shape[2], k_pool.shape[3]
    max_blocks = block_tables.shape[1]
    assert nq % nkv == 0
    assert bits in (8, 4)
    group = nq // nkv
    scale = scale if scale is not None else hd**-0.5
    interpret = interpret if interpret is not None else on_cpu()
    qg = q.reshape(B, T, nkv, group, hd).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(B, nkv, T * group, hd)

    scalars = jnp.concatenate([
        q_positions.astype(jnp.int32).reshape(-1),
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        block_tables.astype(jnp.int32).reshape(-1),
    ])
    kernel = functools.partial(
        _paged_block_kernel_quant, scale=scale, nkv=nkv, group=group, T=T,
        bs=bs, hd=hd, bits=bits,
    )
    BT = B * T
    hdp = k_pool.shape[4]
    pool_spec = pl.BlockSpec(
        (1, 1, bs, nkv, hdp),
        lambda b, j, sc, M=max_blocks: (sc[BT], sc[BT + 1 + b * M + j], 0, 0, 0),
    )
    scale_spec = pl.BlockSpec(
        (1, 1, bs, nkv),
        lambda b, j, sc, M=max_blocks: (sc[BT], sc[BT + 1 + b * M + j], 0, 0),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, max_blocks),
        in_specs=[
            pl.BlockSpec((1, nkv, T * group, hd), lambda b, j, sc: (b, 0, 0, 0)),
            pool_spec, pool_spec, scale_spec, scale_spec,
        ],
        out_specs=pl.BlockSpec((1, nkv, T * group, hd),
                               lambda b, j, sc: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nkv, T * group, hd), jnp.float32),
            pltpu.VMEM((nkv, T * group, 128), jnp.float32),
            pltpu.VMEM((nkv, T * group, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nkv, T * group, hd), q.dtype),
        interpret=interpret,
        name="paged_block_attention_quant",
    )(scalars, qg, k_pool, v_pool, k_scale, v_scale)
    return (out.reshape(B, nkv, T, group, hd)
               .transpose(0, 2, 1, 3, 4)
               .reshape(B, T, nq, hd))


def sharded_paged_block_attention_quant(
    mesh,
    q: jax.Array,  # (B, T, nq, hd)
    k_pool: jax.Array,  # (L, N, bs, nkv, hdp) int8
    v_pool: jax.Array,
    k_scale: jax.Array,
    v_scale: jax.Array,
    block_tables: jax.Array,  # (B, max_blocks) GLOBAL block ids
    q_positions: jax.Array,  # (B, T)
    layer: jax.Array,
    **kw,
) -> jax.Array:
    """``paged_block_attention_quant`` over a (dp, tp) mesh — same layout
    contract as ``sharded_paged_attention_quant``."""
    if mesh is None:
        return paged_block_attention_quant(
            q, k_pool, v_pool, k_scale, v_scale, block_tables, q_positions,
            layer, **kw)
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape.get("tp", 1)
    dp = mesh.shape.get("dp", 1)
    B, T, nq = q.shape[0], q.shape[1], q.shape[2]
    N, nkv = k_pool.shape[1], k_pool.shape[3]
    tp_ax = "tp" if (tp > 1 and nq % tp == 0 and nkv % tp == 0) else None
    if dp > 1 and (B % dp != 0 or N % dp != 0):
        raise ValueError(
            f"sharded_paged_block_attention_quant: batch B={B} and pool "
            f"blocks N={N} must both be divisible by dp={dp}")
    dp_ax = "dp" if dp > 1 else None
    local_blocks = N // dp if dp_ax else N

    def local(q, kp, vp, ks, vs, bt, qp, layer):
        if dp_ax is not None:
            bt = bt - jax.lax.axis_index("dp") * local_blocks
        return paged_block_attention_quant(q, kp, vp, ks, vs, bt, qp, layer, **kw)

    qs = P(dp_ax, None, tp_ax, None)
    ps = P(None, dp_ax, None, tp_ax, None)
    ss = P(None, dp_ax, None, tp_ax)
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(qs, ps, ps, ss, ss, P(dp_ax, None), P(dp_ax, None), P()),
        out_specs=qs,
        check_vma=False,
    )
    return fn(q, k_pool, v_pool, k_scale, v_scale,
              block_tables.astype(jnp.int32), q_positions.astype(jnp.int32),
              layer)


def paged_block_attention_quant_reference(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    k_scale: jax.Array,
    v_scale: jax.Array,
    block_tables: jax.Array,
    q_positions: jax.Array,
    layer,
    *,
    bits: int = 8,
    scale: float | None = None,
) -> jax.Array:
    """Pure-jnp twin: dequantize the pool plane, gather, dense block twin."""
    from .decode_attention import decode_block_attention_reference
    from .kvquant import dequantize_kv

    B = q.shape[0]
    bs, nkv = k_pool.shape[2], k_pool.shape[3]
    hd = q.shape[-1]
    kv_quant = "int8" if bits == 8 else "int4"
    kq = dequantize_kv(k_pool[layer], k_scale[layer], kv_quant, jnp.float32)
    vq = dequantize_kv(v_pool[layer], v_scale[layer], kv_quant, jnp.float32)
    S = block_tables.shape[1] * bs
    kc = kq[block_tables].reshape(B, S, nkv, hd)
    vc = vq[block_tables].reshape(B, S, nkv, hd)
    return decode_block_attention_reference(q, kc, vc, q_positions, scale=scale)
