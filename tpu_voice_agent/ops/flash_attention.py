"""Blockwise flash attention (Pallas, TPU).

Online-softmax attention over (block_q, block_k) tiles: scores never hit HBM,
the running (max, sum, acc) state lives in VMEM scratch across the innermost
grid dimension. Grouped-query attention is handled in the index map (each q
head reads its kv head's blocks). Causal masking is done at tile granularity
— fully-masked tiles are skipped entirely, the diagonal tile gets an
element-wise iota mask.

Used for: Llama prefill + training (causal), Whisper encoder self-attention
(non-causal, padded frames masked via ``kv_len``).

The reference repo has no attention code of its own — its models are cloud
APIs (SURVEY.md §2 #6, #8); this kernel is part of their in-tree replacement.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import on_cpu

_NEG_INF = -1e30


def _flash_kernel(
    q_ref,  # (1, 1, block_q, hd)
    k_ref,  # (1, 1, block_k, hd)
    v_ref,  # (1, 1, block_k, hd)
    o_ref,  # (1, 1, block_q, hd)
    acc_ref,  # VMEM (block_q, hd) f32
    m_ref,  # VMEM (block_q, 128) f32 — running max (lane-replicated)
    l_ref,  # VMEM (block_q, 128) f32 — running sum
    *,
    scale: float,
    block_q: int,
    block_k: int,
    causal: bool,
    kv_len: int,
):
    i = pl.program_id(2)  # q block
    j = pl.program_id(3)  # kv block
    nj = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # tile-level skip: a kv tile strictly above the causal diagonal or fully
    # beyond kv_len contributes nothing
    run = j * block_k < kv_len
    if causal:
        run = jnp.logical_and(run, j * block_k <= (i + 1) * block_q - 1)

    @pl.when(run)
    def _tile():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)

        k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        valid = k_pos < kv_len
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            valid = jnp.logical_and(valid, q_pos >= k_pos)
        s = jnp.where(valid, s, _NEG_INF)

        m_prev = m_ref[:, :1]  # (bq, 1)
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)  # (bq, 1)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)

        pv = jax.lax.dot_general(
            p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bq, hd)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nj - 1)
    def _finish():
        l = l_ref[:, :1]
        o_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


# analyze: ok[jit-sentinel] -- kernel wrapper traced inline by the watched engine/stt loops, never a serving dispatch entry point
@functools.partial(
    jax.jit,
    static_argnames=("causal", "kv_len", "scale", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,  # (B, T, nq, hd)
    k: jax.Array,  # (B, S, nkv, hd)
    v: jax.Array,  # (B, S, nkv, hd)
    *,
    causal: bool = True,
    kv_len: int | None = None,  # static true key count (<= S); None => S
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Flash attention; returns (B, T, nq, hd) in q.dtype.

    ``kv_len`` masks padded keys at positions >= kv_len (static: pad lengths
    are bucketed by the caller, matching the engine's prefill buckets). With
    ``causal=True`` queries/keys are positioned at their array index.
    """
    B, T, nq, hd = q.shape
    S, nkv = k.shape[1], k.shape[2]
    assert nq % nkv == 0, f"GQA needs nq % nkv == 0, got {nq} % {nkv}"
    group = nq // nkv
    scale = scale if scale is not None else hd**-0.5
    kv_len = kv_len if kv_len is not None else S
    interpret = interpret if interpret is not None else on_cpu()

    block_q = min(block_q, T)
    block_k = min(block_k, S)

    # pad T/S to block multiples; padded keys are masked via kv_len, padded
    # queries produce garbage rows that are sliced off
    pad_t = (-T) % block_q
    pad_s = (-S) % block_k
    qt = jnp.moveaxis(q, 2, 1)  # (B, nq, T, hd)
    kt = jnp.moveaxis(k, 2, 1)  # (B, nkv, S, hd)
    vt = jnp.moveaxis(v, 2, 1)
    if pad_t:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_t), (0, 0)))
    if pad_s:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
    Tp, Sp = qt.shape[2], kt.shape[2]

    grid = (B, nq, Tp // block_q, Sp // block_k)
    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        causal=causal,
        kv_len=kv_len,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nq, Tp, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(qt, kt, vt)
    return jnp.moveaxis(out[:, :, :T, :], 1, 2)


def sharded_flash_attention(
    mesh,
    q: jax.Array,  # (B, T, nq, hd)
    k: jax.Array,  # (B, S, nkv, hd)
    v: jax.Array,
    **kw,
) -> jax.Array:
    """flash_attention over a (dp, tp) mesh via shard_map — batch over dp,
    heads over tp, zero collectives (attention is head-local). Exists
    because a bare pallas_call under GSPMD replicates its operands.
    ``mesh=None`` falls through to the plain kernel."""
    if mesh is None:
        return flash_attention(q, k, v, **kw)
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape.get("tp", 1)
    dp = mesh.shape.get("dp", 1)
    B, nq, nkv = q.shape[0], q.shape[2], k.shape[2]
    tp_ax = "tp" if (tp > 1 and nq % tp == 0 and nkv % tp == 0) else None
    dp_ax = "dp" if (dp > 1 and B % dp == 0) else None  # B=1 prefill: replicate batch
    spec = P(dp_ax, None, tp_ax, None)
    fn = jax.shard_map(
        functools.partial(flash_attention, **kw),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


def attention_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    kv_len: int | None = None,
    scale: float | None = None,
) -> jax.Array:
    """Pure-jnp twin of ``flash_attention`` (same signature semantics)."""
    B, T, nq, hd = q.shape
    S, nkv = k.shape[1], k.shape[2]
    group = nq // nkv
    scale = scale if scale is not None else hd**-0.5
    kv_len = kv_len if kv_len is not None else S

    qg = q.reshape(B, T, nkv, group, hd)
    scores = jnp.einsum("btkgh,bskh->bkgts", qg, k, preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(S)[None, :] < kv_len  # (1, S)
    mask = jnp.broadcast_to(valid[:, None, :], (1, T, S))
    if causal:
        mask = mask & (jnp.arange(T)[None, :, None] >= jnp.arange(S)[None, None, :])
    scores = jnp.where(mask[:, None, None, :, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bkgts,bskh->btkgh", probs.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    return out.reshape(B, T, nq, hd).astype(q.dtype)
