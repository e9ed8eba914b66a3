"""Single-token decode attention against the dense KV cache (Pallas, TPU).

The per-step hot op of the decode loop: one query token per sequence attends
over that sequence's full cache. Per-row valid lengths are dynamic (rows in a
continuous batch are at different positions), so ``kv_len`` rides in SMEM and
gates tiles at run time — tiles entirely beyond a row's frontier are skipped,
which makes step cost proportional to the row's actual context, not the
cache capacity.

Layout: q heads are grouped by their kv head (GQA), so each grid cell
computes a (group, block_k) score tile on the MXU with the kv block loaded
once for the whole group.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import on_cpu

_NEG_INF = -1e30


def _decode_kernel(
    kv_len_ref,  # SMEM (B,) int32 — all rows' valid key counts
    q_ref,  # (1, nkv, group, hd)
    k_ref,  # (1, block_k, nkv, hd) — or (1, 1, bk, nkv, hd) stacked-cache view
    v_ref,  # like k_ref
    o_ref,  # (1, nkv, group, hd)
    acc_ref,  # VMEM (nkv, group, hd) f32
    m_ref,  # VMEM (nkv, group, 128) f32
    l_ref,  # VMEM (nkv, group, 128) f32
    *,
    scale: float,
    nkv: int,
    group: int,
    block_k: int,
    stacked: bool = False,  # kv blocks carry a leading layer dim of 1
):
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    kv_len = kv_len_ref[b]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j * block_k < kv_len)
    def _tile():
        k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (group, block_k), 1)
        valid = k_pos < kv_len
        for h in range(nkv):  # static unroll; nkv is small (GQA)
            q = q_ref[0, h].astype(jnp.float32)  # (group, hd)
            k = (k_ref[0, 0, :, h] if stacked else k_ref[0, :, h]).astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale  # (group, bk)
            s = jnp.where(valid, s, _NEG_INF)

            m_prev = m_ref[h, :, :1]
            l_prev = l_ref[h, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            vblk = (v_ref[0, 0, :, h] if stacked else v_ref[0, :, h]).astype(jnp.float32)
            pv = jax.lax.dot_general(
                p, vblk, (((1,), (0,)), ((), ())),
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
@functools.partial(jax.jit, static_argnames=("scale", "block_k", "interpret"))
def decode_attention(
    q: jax.Array,  # (B, nq, hd) — one query token per row
    k_cache: jax.Array,  # (B, S, nkv, hd)
    v_cache: jax.Array,  # (B, S, nkv, hd)
    kv_len: jax.Array,  # (B,) int32 — valid keys per row (frontier + 1)
    *,
    scale: float | None = None,
    block_k: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns (B, nq, hd) in q.dtype."""
    B, nq, hd = q.shape
    S, nkv = k_cache.shape[1], k_cache.shape[2]
    assert nq % nkv == 0
    group = nq // nkv
    scale = scale if scale is not None else hd**-0.5
    interpret = interpret if interpret is not None else on_cpu()

    # Blocks DMA straight out of the cache's native (B, S, nkv, hd) layout —
    # no moveaxis/pad relayout of the full cache per step (the step's HBM
    # traffic must stay proportional to the attended keys, not capacity).
    # All kv heads ride in each block (TPU tiling wants the second-minor
    # block dim equal to the array dim) and the small GQA head loop unrolls
    # in-kernel. block_k must divide S. Bucketed caches (multiples of 64/128)
    # hit the no-copy path; an odd S (e.g. prime) pads up to the next block
    # boundary rather than degenerating to block_k=1 — the pad region sits
    # beyond every row's kv_len, so the tile gate skips it entirely.
    block_k = min(block_k, S)
    if S % block_k:
        S_pad = -(-S // block_k) * block_k
        pad = [(0, 0), (0, S_pad - S), (0, 0), (0, 0)]
        k_cache = jnp.pad(k_cache, pad)
        v_cache = jnp.pad(v_cache, pad)
        S = S_pad
    qg = q.reshape(B, nkv, group, hd)  # reshape only — no copy

    grid = (B, S // block_k)
    kernel = functools.partial(
        _decode_kernel, scale=scale, nkv=nkv, group=group, block_k=block_k
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((B,), lambda b, j: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, nkv, group, hd), lambda b, j: (b, 0, 0, 0)),
            pl.BlockSpec((1, block_k, nkv, hd), lambda b, j: (b, j, 0, 0)),
            pl.BlockSpec((1, block_k, nkv, hd), lambda b, j: (b, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, nkv, group, hd), lambda b, j: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nkv, group, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((nkv, group, hd), jnp.float32),
            pltpu.VMEM((nkv, group, 128), jnp.float32),
            pltpu.VMEM((nkv, group, 128), jnp.float32),
        ],
        interpret=interpret,
        name="decode_attention",
    )(kv_len.astype(jnp.int32), qg, k_cache, v_cache)
    return out.reshape(B, nq, hd)


# analyze: ok[jit-sentinel] -- kernel wrapper traced inline by the watched engine/stt loops, never a serving dispatch entry point
@functools.partial(jax.jit, static_argnames=("scale", "block_k", "interpret"))
def decode_attention_layer(
    q: jax.Array,  # (B, nq, hd) — one query token per row
    k_cache: jax.Array,  # (L, B, S, nkv, hd) — the FULL stacked cache
    v_cache: jax.Array,
    kv_len: jax.Array,  # (B,) int32
    layer: jax.Array,  # scalar int32 — which cache plane to attend
    *,
    scale: float | None = None,
    block_k: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """decode_attention reading one layer's plane straight out of the
    stacked (L, B, S, nkv, hd) cache via a scalar-prefetched layer index in
    the BlockSpec index map. The per-layer ``cache[li]`` slice a scan body
    would otherwise materialize for the kernel is a full-plane HBM copy per
    layer per token — this kernel makes the decode loop's cache traffic the
    attended keys only.

    Cache-length contract: S must be divisible by some block >= 32 (16-wide
    k-tiles waste the TPU's (8,128) lane tiling, so the fallback chain
    stops at 32 and raises instead). The in-tree engines already bucket
    cache capacity to powers of two; external callers must size S
    accordingly — e.g. 96 works (block 32), 80 does not."""
    B, nq, hd = q.shape
    S, nkv = k_cache.shape[2], k_cache.shape[3]
    assert nq % nkv == 0
    group = nq // nkv
    scale = scale if scale is not None else hd**-0.5
    interpret = interpret if interpret is not None else on_cpu()
    # this kernel runs once per LAYER per step: padding the stacked cache
    # here would copy the ENTIRE cache L times per token — the exact
    # traffic it exists to eliminate. Take a smaller block instead; oddly
    # sized caches must be bucketed by the caller (engines already do).
    block_k = min(block_k, S)
    while S % block_k and block_k > 32:
        block_k //= 2
    if S % block_k:
        raise ValueError(
            f"stacked decode kernel needs cache length {S} divisible by a "
            f">=32 block; size the cache to a power-of-two bucket")
    qg = q.reshape(B, nkv, group, hd)

    # scalar prefetch carries (kv_len ++ layer) so the index map can place
    # each block at (layer, b, j) in the stacked cache — same trick as
    # grammar_mask's state-indexed mask tiles
    scalars = jnp.concatenate(
        [kv_len.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32)]
    )
    kernel = functools.partial(
        _decode_kernel, scale=scale, nkv=nkv, group=group, block_k=block_k,
        stacked=True,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, S // block_k),
        in_specs=[
            pl.BlockSpec((1, nkv, group, hd), lambda b, j, sc: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, block_k, nkv, hd), lambda b, j, sc: (sc[B], b, j, 0, 0)),
            pl.BlockSpec((1, 1, block_k, nkv, hd), lambda b, j, sc: (sc[B], b, j, 0, 0)),
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
        name="decode_attention_layer",
    )(scalars, qg, k_cache, v_cache)
    return out.reshape(B, nq, hd)


def sharded_decode_attention_layer(
    mesh,
    q: jax.Array,  # (B, nq, hd)
    k_cache: jax.Array,  # (L, B, S, nkv, hd)
    v_cache: jax.Array,
    kv_len: jax.Array,
    layer: jax.Array,
    **kw,
) -> jax.Array:
    """decode_attention_layer over a (dp, tp) mesh (mesh=None -> plain)."""
    if mesh is None:
        return decode_attention_layer(q, k_cache, v_cache, kv_len, layer, **kw)
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape.get("tp", 1)
    dp = mesh.shape.get("dp", 1)
    B, nq = q.shape[0], q.shape[1]
    nkv = k_cache.shape[3]
    tp_ax = "tp" if (tp > 1 and nq % tp == 0 and nkv % tp == 0) else None
    dp_ax = "dp" if (dp > 1 and B % dp == 0) else None
    qs = P(dp_ax, tp_ax, None)
    cs = P(None, dp_ax, None, tp_ax, None)
    fn = jax.shard_map(
        functools.partial(decode_attention_layer, **kw),
        mesh=mesh,
        in_specs=(qs, cs, cs, P(dp_ax), P()),
        out_specs=qs,
        check_vma=False,
    )
    return fn(q, k_cache, v_cache, kv_len.astype(jnp.int32), layer)


def sharded_decode_attention(
    mesh,
    q: jax.Array,  # (B, nq, hd)
    k_cache: jax.Array,  # (B, S, nkv, hd)
    v_cache: jax.Array,
    kv_len: jax.Array,  # (B,)
    **kw,
) -> jax.Array:
    """decode_attention over a (dp, tp) mesh via shard_map (``mesh=None``
    falls through to the plain kernel, so call sites need no branching).

    Decode attention is batch-local and head-local, so each device runs the
    kernel on its (B/dp, nq/tp) shard with zero collectives — the wrapper
    exists only because a bare pallas_call under GSPMD would replicate its
    operands (the round-1 blocker for kernels='pallas' on a mesh). Heads
    stay sharded only when tp divides both nq and nkv (matching
    parallel.mesh.default_rules' gating)."""
    if mesh is None:
        return decode_attention(q, k_cache, v_cache, kv_len, **kw)
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape.get("tp", 1)
    dp = mesh.shape.get("dp", 1)
    B, nq = q.shape[0], q.shape[1]
    nkv = k_cache.shape[2]
    tp_ax = "tp" if (tp > 1 and nq % tp == 0 and nkv % tp == 0) else None
    # single-row admission prefill/decode runs B=1 on a dp>1 mesh: batch
    # stays replicated there, heads still shard
    dp_ax = "dp" if (dp > 1 and B % dp == 0) else None
    qs = P(dp_ax, tp_ax, None)
    cs = P(dp_ax, None, tp_ax, None)
    fn = jax.shard_map(
        functools.partial(decode_attention, **kw),
        mesh=mesh,
        in_specs=(qs, cs, cs, P(dp_ax)),
        out_specs=qs,
        check_vma=False,
    )
    return fn(q, k_cache, v_cache, kv_len.astype(jnp.int32))


def decode_attention_reference(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    kv_len: jax.Array,
    *,
    scale: float | None = None,
) -> jax.Array:
    """Pure-jnp twin of ``decode_attention``."""
    B, nq, hd = q.shape
    S, nkv = k_cache.shape[1], k_cache.shape[2]
    group = nq // nkv
    scale = scale if scale is not None else hd**-0.5
    qg = q.reshape(B, nkv, group, hd)
    scores = jnp.einsum("bkgh,bskh->bkgs", qg, k_cache, preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(S)[None, :] < kv_len[:, None]  # (B, S)
    scores = jnp.where(valid[:, None, None, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bkgs,bskh->bkgh", probs.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, nq, hd).astype(q.dtype)


# --------------------------------------------------------------- block decode
#
# Grammar fast-forward under the BATCHER (round-3 VERDICT next #4): a forced-
# chain step is a (B, 1+W) forward. The XLA cache-attention fallback reads the
# cache at its full CAPACITY for every row, which is why ff was restricted to
# single-request generate(). This kernel is the lifted restriction: T queries
# per row attend the row's cache up to its own frontier — tile gating keeps
# the read proportional to actual context, exactly like the T=1 kernel, and
# intra-block causality comes from the queries' write positions (slot index
# == token position for contiguous caches).


def _decode_block_kernel(
    scalars_ref,  # SMEM (B*T [+1]) int32 — q positions row-major [+ layer]
    q_ref,  # (1, nkv, T*group, hd)
    k_ref,  # (1, block_k, nkv, hd) — or (1, 1, bk, nkv, hd) stacked view
    v_ref,
    o_ref,  # (1, nkv, T*group, hd)
    acc_ref,  # VMEM (nkv, T*group, hd) f32
    m_ref,  # VMEM (nkv, T*group, 128) f32
    l_ref,
    *,
    scale: float,
    nkv: int,
    group: int,
    T: int,
    block_k: int,
    stacked: bool = False,
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

    # per-query frontiers: row r of the folded (T*group) dim belongs to
    # query index r // group; its last visible slot is its own position.
    # Tile gating needs the true block max — computed over all T entries
    # (T is tiny, static unroll), NOT assumed to be the last query's, so
    # arbitrary q_positions orderings stay correct
    max_pos = scalars_ref[b * T]
    for _i in range(1, T):
        max_pos = jnp.maximum(max_pos, scalars_ref[b * T + _i])

    @pl.when(j * block_k <= max_pos)
    def _tile():
        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), 1)
        # gather each row's own position out of SMEM via a small static loop
        # (T is tiny); builds a (rows, 1) frontier column
        qpos_rows = jnp.zeros((rows, 1), jnp.int32)
        for i in range(T):
            qpos_rows = jnp.where(
                (jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // group) == i,
                scalars_ref[b * T + i], qpos_rows)
        valid = k_pos <= qpos_rows  # causal + frontier in one mask
        for h in range(nkv):
            q = q_ref[0, h].astype(jnp.float32)  # (rows, hd)
            k = (k_ref[0, 0, :, h] if stacked else k_ref[0, :, h]).astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale  # (rows, bk)
            s = jnp.where(valid, s, _NEG_INF)

            m_prev = m_ref[h, :, :1]
            l_prev = l_ref[h, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            vblk = (v_ref[0, 0, :, h] if stacked else v_ref[0, :, h]).astype(jnp.float32)
            pv = jax.lax.dot_general(
                p, vblk, (((1,), (0,)), ((), ())),
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
@functools.partial(jax.jit, static_argnames=("scale", "block_k", "interpret"))
def decode_block_attention(
    q: jax.Array,  # (B, T, nq, hd) — a small block of queries per row
    k_cache: jax.Array,  # (B, S, nkv, hd)
    v_cache: jax.Array,
    q_positions: jax.Array,  # (B, T) int32 — each query's cache position
    *,
    scale: float | None = None,
    block_k: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns (B, T, nq, hd) in q.dtype. Query i attends cache slots
    [0, q_positions[b, i]] — the caller has already written the block's k/v
    at those positions (forward's contract)."""
    B, T, nq, hd = q.shape
    S, nkv = k_cache.shape[1], k_cache.shape[2]
    assert nq % nkv == 0
    group = nq // nkv
    scale = scale if scale is not None else hd**-0.5
    interpret = interpret if interpret is not None else on_cpu()

    block_k = min(block_k, S)
    if S % block_k:
        S_pad = -(-S // block_k) * block_k
        pad = [(0, 0), (0, S_pad - S), (0, 0), (0, 0)]
        k_cache = jnp.pad(k_cache, pad)
        v_cache = jnp.pad(v_cache, pad)
        S = S_pad
    # (B, T, nkv, group, hd) -> (B, nkv, T, group, hd) -> fold (T, group)
    qg = q.reshape(B, T, nkv, group, hd).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(B, nkv, T * group, hd)

    grid = (B, S // block_k)
    kernel = functools.partial(
        _decode_block_kernel, scale=scale, nkv=nkv, group=group, T=T,
        block_k=block_k,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((B * T,), lambda b, j: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, nkv, T * group, hd), lambda b, j: (b, 0, 0, 0)),
            pl.BlockSpec((1, block_k, nkv, hd), lambda b, j: (b, j, 0, 0)),
            pl.BlockSpec((1, block_k, nkv, hd), lambda b, j: (b, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, nkv, T * group, hd), lambda b, j: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nkv, T * group, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((nkv, T * group, hd), jnp.float32),
            pltpu.VMEM((nkv, T * group, 128), jnp.float32),
            pltpu.VMEM((nkv, T * group, 128), jnp.float32),
        ],
        interpret=interpret,
        name="decode_block_attention",
    )(q_positions.reshape(-1).astype(jnp.int32), qg, k_cache, v_cache)
    return (out.reshape(B, nkv, T, group, hd)
               .transpose(0, 2, 1, 3, 4)
               .reshape(B, T, nq, hd))


# analyze: ok[jit-sentinel] -- kernel wrapper traced inline by the watched engine/stt loops, never a serving dispatch entry point
@functools.partial(jax.jit, static_argnames=("scale", "block_k", "interpret"))
def decode_block_attention_layer(
    q: jax.Array,  # (B, T, nq, hd)
    k_cache: jax.Array,  # (L, B, S, nkv, hd) — the FULL stacked cache
    v_cache: jax.Array,
    q_positions: jax.Array,  # (B, T) int32
    layer: jax.Array,  # scalar int32
    *,
    scale: float | None = None,
    block_k: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """decode_block_attention reading one layer's plane of the stacked cache
    via scalar prefetch (same rationale as decode_attention_layer: slicing
    cache[li] in the scan body materializes a full-plane copy per layer).

    Same cache-length contract as decode_attention_layer: S divisible by a
    block >= 32, or ValueError — size caches to power-of-two buckets."""
    B, T, nq, hd = q.shape
    S, nkv = k_cache.shape[2], k_cache.shape[3]
    assert nq % nkv == 0
    group = nq // nkv
    scale = scale if scale is not None else hd**-0.5
    interpret = interpret if interpret is not None else on_cpu()
    block_k = min(block_k, S)
    while S % block_k and block_k > 32:
        block_k //= 2
    if S % block_k:
        raise ValueError(
            f"stacked block-decode kernel needs cache length {S} divisible "
            f"by a >=32 block; size the cache to a power-of-two bucket")
    qg = q.reshape(B, T, nkv, group, hd).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(B, nkv, T * group, hd)

    scalars = jnp.concatenate([
        q_positions.reshape(-1).astype(jnp.int32),
        jnp.reshape(layer, (1,)).astype(jnp.int32),
    ])
    kernel = functools.partial(
        _decode_block_kernel, scale=scale, nkv=nkv, group=group, T=T,
        block_k=block_k, stacked=True,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, S // block_k),
        in_specs=[
            pl.BlockSpec((1, nkv, T * group, hd), lambda b, j, sc: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, block_k, nkv, hd),
                         lambda b, j, sc: (sc[B * T], b, j, 0, 0)),
            pl.BlockSpec((1, 1, block_k, nkv, hd),
                         lambda b, j, sc: (sc[B * T], b, j, 0, 0)),
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
        name="decode_block_attention_layer",
    )(scalars, qg, k_cache, v_cache)
    return (out.reshape(B, nkv, T, group, hd)
               .transpose(0, 2, 1, 3, 4)
               .reshape(B, T, nq, hd))


def sharded_decode_block_attention_layer(
    mesh,
    q: jax.Array,  # (B, T, nq, hd)
    k_cache: jax.Array,  # (L, B, S, nkv, hd)
    v_cache: jax.Array,
    q_positions: jax.Array,  # (B, T)
    layer: jax.Array,
    **kw,
) -> jax.Array:
    """decode_block_attention_layer over a (dp, tp) mesh (None -> plain)."""
    if mesh is None:
        return decode_block_attention_layer(q, k_cache, v_cache, q_positions,
                                            layer, **kw)
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape.get("tp", 1)
    dp = mesh.shape.get("dp", 1)
    B, T, nq = q.shape[0], q.shape[1], q.shape[2]
    nkv = k_cache.shape[3]
    tp_ax = "tp" if (tp > 1 and nq % tp == 0 and nkv % tp == 0) else None
    dp_ax = "dp" if (dp > 1 and B % dp == 0) else None
    qs = P(dp_ax, None, tp_ax, None)
    cs = P(None, dp_ax, None, tp_ax, None)
    fn = jax.shard_map(
        functools.partial(decode_block_attention_layer, **kw),
        mesh=mesh,
        in_specs=(qs, cs, cs, P(dp_ax, None), P()),
        out_specs=qs,
        check_vma=False,
    )
    return fn(q, k_cache, v_cache, q_positions.astype(jnp.int32), layer)


def decode_block_attention_reference(
    q: jax.Array,  # (B, T, nq, hd)
    k_cache: jax.Array,  # (B, S, nkv, hd)
    v_cache: jax.Array,
    q_positions: jax.Array,  # (B, T)
    *,
    scale: float | None = None,
) -> jax.Array:
    """Pure-jnp twin of ``decode_block_attention``."""
    B, T, nq, hd = q.shape
    S, nkv = k_cache.shape[1], k_cache.shape[2]
    group = nq // nkv
    scale = scale if scale is not None else hd**-0.5
    qg = q.reshape(B, T, nkv, group, hd)
    scores = jnp.einsum("btkgh,bskh->btkgs", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(S)[None, None, :] <= q_positions[:, :, None]  # (B, T, S)
    scores = jnp.where(valid[:, :, None, None, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "btkgs,bskh->btkgh", probs.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, T, nq, hd).astype(q.dtype)


# ------------------------------------------------------------ quantized cache
#
# KV_QUANT (ISSUE 12) building block for DENSE caches: single-token decode
# against an int8 (or packed int4) (B, S, nkv, hdp) cache with bf16
# per-(position, head) scales. The paged plane's fused-dequant kernels live
# in ops.paged_attention; this is the same score/probability scale-folding
# on the contiguous layout — the seam a future dense-engine KV tier plugs
# into, and the simplest kernel the quantization math is verified on.


def _decode_kernel_quant(
    kv_len_ref,  # SMEM (B,) int32
    q_ref,  # (1, nkv, group, hd)
    k_ref,  # (1, block_k, nkv, hdp) int8
    v_ref,
    ks_ref,  # (1, block_k, nkv) bf16
    vs_ref,
    o_ref,  # (1, nkv, group, hd)
    acc_ref,  # VMEM (nkv, group, hd) f32
    m_ref,  # VMEM (nkv, group, 128) f32
    l_ref,
    *,
    scale: float,
    nkv: int,
    group: int,
    block_k: int,
    hd: int,
    bits: int,
):
    # the packed-dot arithmetic has ONE copy (ops.kvquant pack contract):
    # the paged kernels' helpers, fed the pre-sliced (block_k, hdp) tile
    from .paged_attention import _NEG_INF as _NI
    from .paged_attention import _pv_dot, _qk_dot

    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    kv_len = kv_len_ref[b]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NI)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j * block_k < kv_len)
    def _tile():
        k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (group, block_k), 1)
        valid = k_pos < kv_len
        for h in range(nkv):
            q = q_ref[0, h].astype(jnp.float32)  # (group, hd)
            ks = ks_ref[0, :, h].astype(jnp.float32)  # (block_k,)
            vs = vs_ref[0, :, h].astype(jnp.float32)
            s = _qk_dot(q, k_ref[0, :, h], bits, hd) * ks[None, :] * scale
            s = jnp.where(valid, s, _NI)

            m_prev = m_ref[h, :, :1]
            l_prev = l_ref[h, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            pv = _pv_dot(p * vs[None, :], v_ref[0, :, h], bits)
            acc_ref[h] = acc_ref[h] * alpha + pv
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(j == nj - 1)
    def _finish():
        l = l_ref[:, :, :1]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


# analyze: ok[jit-sentinel] -- kernel wrapper traced inline by the watched engine/stt loops, never a serving dispatch entry point
@functools.partial(jax.jit, static_argnames=("bits", "scale", "block_k", "interpret"))
def decode_attention_quant(
    q: jax.Array,  # (B, nq, hd)
    k_cache: jax.Array,  # (B, S, nkv, hdp) int8 stored values
    v_cache: jax.Array,
    k_scale: jax.Array,  # (B, S, nkv) bf16
    v_scale: jax.Array,
    kv_len: jax.Array,  # (B,) int32
    *,
    bits: int = 8,
    scale: float | None = None,
    block_k: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """``decode_attention`` against a quantized dense cache. S must be a
    multiple of the chosen block (the engines bucket cache capacity)."""
    B, nq, hd = q.shape
    S, nkv = k_cache.shape[1], k_cache.shape[2]
    assert nq % nkv == 0
    assert bits in (8, 4)
    group = nq // nkv
    scale = scale if scale is not None else hd**-0.5
    interpret = interpret if interpret is not None else on_cpu()
    block_k = min(block_k, S)
    if S % block_k:
        raise ValueError(
            f"decode_attention_quant needs cache length {S} divisible by "
            f"block_k={block_k}; bucket the cache")
    qg = q.reshape(B, nkv, group, hd)
    hdp = k_cache.shape[3]

    grid = (B, S // block_k)
    kernel = functools.partial(
        _decode_kernel_quant, scale=scale, nkv=nkv, group=group,
        block_k=block_k, hd=hd, bits=bits,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((B,), lambda b, j: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, nkv, group, hd), lambda b, j: (b, 0, 0, 0)),
            pl.BlockSpec((1, block_k, nkv, hdp), lambda b, j: (b, j, 0, 0)),
            pl.BlockSpec((1, block_k, nkv, hdp), lambda b, j: (b, j, 0, 0)),
            pl.BlockSpec((1, block_k, nkv), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, nkv), lambda b, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, nkv, group, hd), lambda b, j: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nkv, group, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((nkv, group, hd), jnp.float32),
            pltpu.VMEM((nkv, group, 128), jnp.float32),
            pltpu.VMEM((nkv, group, 128), jnp.float32),
        ],
        interpret=interpret,
        name="decode_attention_quant",
    )(kv_len.astype(jnp.int32), qg, k_cache, v_cache, k_scale, v_scale)
    return out.reshape(B, nq, hd)


def decode_attention_quant_reference(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    k_scale: jax.Array,
    v_scale: jax.Array,
    kv_len: jax.Array,
    *,
    bits: int = 8,
    scale: float | None = None,
) -> jax.Array:
    """Pure-jnp twin of ``decode_attention_quant``."""
    from .kvquant import dequantize_kv

    kv_quant = "int8" if bits == 8 else "int4"
    kc = dequantize_kv(k_cache, k_scale, kv_quant, jnp.float32)
    vc = dequantize_kv(v_cache, v_scale, kv_quant, jnp.float32)
    return decode_attention_reference(q, kc, vc, kv_len, scale=scale)
