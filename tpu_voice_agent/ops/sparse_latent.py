"""Learned sparse attention over a latent cache (``models.dots3`` has the
equations): the INDEXER that scores cached positions, and latent attention
over a key set that was GATHERED for its queries.

``indexer_scores`` — a full layer caches ONE index key of ``di`` values a
token beside its latent. A query position holds ``Hi`` index heads and a
weight a head, and scores a cached key s as ``sum_j w[j] relu(q[j] . k[s])``.
The kernel scores a tile of positions against the WHOLE pool plane of the
layer, block by block as the plane is shaped: a block's keys are the same
whichever row's table names it, so a block rows hold in common (the shared
prompt prefix) is read and scored once for all of them, and a position reads
its own row's blocks out of the result by its table. The pool is sized to
what its rows hold, so the plane is little more than the union they see.

``sparse_latent_attention`` / ``window_latent_attention`` — ONE kernel under
two names (the device trace's): absorbed latent attention (``(q_c . c + q_r .
r) * scale``, softmax, ``sum p c``) of G groups of Q query rows, each group
over ITS OWN K gathered keys: one grid step a group, the whole key set one
tile (K is a few thousand at most: no online softmax). A key carries its
sequence position and a query row the bounds [lo, hi] it may see, so one
kernel serves the selected keys of a full layer (a group = a position's H
heads over its ``index_topk`` keys, valid while they last) and the window of
a sliding layer (a group = a row's T x H queries over the blocks that hold
its window, each query its own causal and window edge). The keys come in the
form their planes hold them — by the arguments' shapes, no flag: a full
layer's as ONE tile of rows [c | r] (``key_row``; one gather out of one
plane), scored by one dot against [q_c | q_r] and attended through the tile's
first C columns (a tile of any other width is refused); a sliding layer's as
(c, r) out of two planes, two dots summed. Dots take the pool's dtype and
accumulate in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import on_cpu
from .paged_attention import _NEG_INF, _VMEM_LIMIT

F32 = jnp.float32
# positions a step of the indexer's inner loop scores: their Hi heads are the
# rows of one dot against the key tile
_INDEX_SUB = 8


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())), preferred_element_type=F32)


def _indexer_kernel(layer_ref, q_ref, w_ref, k_ref, o_ref, *, Hi: int):
    """q (P * Hi, di), w (P * Hi, 1) f32, k (1, tkb, bs, di) -> o (P, tkb * bs)."""
    del layer_ref  # read by the index maps
    tk = o_ref.shape[1]
    k = k_ref[0].reshape(tk, k_ref.shape[-1])
    rows = _INDEX_SUB * Hi

    def body(i, carry):
        at = pl.ds(pl.multiple_of(i * rows, rows), rows)
        s = jnp.maximum(_dot(q_ref[at, :], k, ((1,), (1,))), 0.0) * w_ref[at, :]
        o_ref[pl.ds(pl.multiple_of(i * _INDEX_SUB, _INDEX_SUB), _INDEX_SUB), :] = jnp.sum(
            s.reshape(_INDEX_SUB, Hi, tk), axis=1)
        return carry

    jax.lax.fori_loop(0, o_ref.shape[0] // _INDEX_SUB, body, 0)


def _key_blocks(N: int, bs: int) -> int:
    """Pool blocks a grid step scores: as many as divide the pool, to ~1024 keys."""
    return max(t for t in (8, 4, 2, 1) if N % t == 0 and t * bs <= max(1024, bs))


# analyze: ok[jit-sentinel] -- kernel wrapper traced inline by the watched engine loops, never a serving dispatch entry point
@functools.partial(jax.jit, static_argnames=("interpret",))
def indexer_scores(q: jax.Array, w: jax.Array, k_plane: jax.Array, layer: jax.Array, *,
                   interpret: bool | None = None) -> jax.Array:
    """q (P, Hi, di) in the pool's dtype, w (P, Hi) float32, k_plane (L, N,
    bs, di), layer a scalar -> (P, N * bs) float32: every pool position's
    score for every query position, ``sum_j w[p, j] relu(q[p, j] . k[s])``."""
    P, Hi, di = q.shape
    _, N, bs, _ = k_plane.shape
    interpret = interpret if interpret is not None else on_cpu()
    Pp = -(-P // _INDEX_SUB) * _INDEX_SUB
    q2 = jnp.pad(q.astype(k_plane.dtype), ((0, Pp - P), (0, 0), (0, 0))).reshape(Pp * Hi, di)
    w2 = jnp.pad(w.astype(F32), ((0, Pp - P), (0, 0))).reshape(Pp * Hi, 1)
    tkb = _key_blocks(N, bs)
    call = pl.pallas_call(
        functools.partial(_indexer_kernel, Hi=Hi),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(N // tkb,),
            in_specs=[pl.BlockSpec((Pp * Hi, di), lambda j, l: (0, 0)),
                      pl.BlockSpec((Pp * Hi, 1), lambda j, l: (0, 0)),
                      pl.BlockSpec((1, tkb, bs, di), lambda j, l: (l[0], j, 0, 0))],
            out_specs=pl.BlockSpec((Pp, tkb * bs), lambda j, l: (0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((Pp, N * bs), F32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="indexer_scores",
    )
    with jax.named_scope("indexer_scores"):  # the kernel alone, by its name, in the device trace
        out = call(jnp.reshape(layer, (1,)).astype(jnp.int32), q2, w2, k_plane)
    return out[:P]


def indexer_scores_reference(q, w, k_plane, layer) -> jax.Array:
    """Pure-jnp twin of the kernel."""
    k = k_plane[layer].reshape(-1, k_plane.shape[-1])
    s = jnp.einsum("phd,sd->phs", q.astype(k.dtype), k, preferred_element_type=F32)
    return jnp.sum(jnp.maximum(s, 0.0) * w.astype(F32)[:, :, None], axis=1)


def key_row(c: jax.Array, r: jax.Array) -> jax.Array:
    """(..., C) latents and (..., R) rotated keys -> the (..., C + R) rows a
    full layer's plane holds, [c | r]; a query's [q_c | q_r] likewise."""
    return jnp.concatenate([c, r], axis=-1)


def _gathered_kernel(*refs, scale: float, n: int):
    """``n`` query operands, lo, hi, their ``n`` key operands, kpos -> o (Q,
    C). A score is the sum of the pairs' dots — (q_c, c) and (q_r, r) where
    the keys come as two planes' rows, ([q_c | q_r], [c | r]) where they come
    as one — and the values are the first key operand's first C columns."""
    qs, (lo_ref, hi_ref), ks, (kpos_ref, o_ref) = (
        refs[:n], refs[n:n + 2], refs[n + 2:2 * n + 2], refs[2 * n + 2:])
    s = functools.reduce(jnp.add, (_dot(q[0], k[0], ((1,), (1,))) for q, k in zip(qs, ks))) * scale  # (Q, K)
    kpos = kpos_ref[0]  # (1, K)
    s = jnp.where(jnp.logical_and(kpos >= lo_ref[0], kpos <= hi_ref[0]), s, _NEG_INF)
    p = jnp.exp(s - jnp.max(s, axis=1, keepdims=True))
    l = jnp.sum(p, axis=1, keepdims=True)
    c = ks[0][0, :, :o_ref.shape[-1]]
    o_ref[0] = (_dot(p.astype(c.dtype), c, ((1,), (0,))) / l).astype(o_ref.dtype)


def _gathered(q_c, q_r, keys, kpos, lo, hi, scale, interpret):
    """What both entry points hand ``pallas_call``: the kernel, the grid (one
    step a group, the whole key set one tile), the specs, the padded
    arguments. ``keys``: (c, r), or (kv,) whose rows are [c | r] — the queries
    then go in as ONE operand, [q_c | q_r]."""
    G, Q, C = q_c.shape
    K = keys[0].shape[1]
    qs = (q_c, q_r) if len(keys) == 2 else (key_row(q_c, q_r),)
    if qs[0].shape[-1] != keys[0].shape[-1]:
        raise ValueError(f"keys of {keys[0].shape[-1]} columns are no rows [c | r] of {C} + {q_r.shape[-1]}")
    Qp, Kp = -(-Q // 16) * 16, -(-K // 128) * 128
    padq = lambda a: jnp.pad(a, ((0, 0), (0, Qp - Q)) + ((0, 0),) * (a.ndim - 2))
    padk = lambda a, v=0: jnp.pad(a, ((0, 0), (0, Kp - K)) + ((0, 0),) * (a.ndim - 2),
                                  constant_values=v)
    group = lambda *tail: pl.BlockSpec((1, *tail), lambda g: (g,) + (0,) * len(tail))
    spec = dict(grid=(G,),
                in_specs=[*(group(Qp, q.shape[-1]) for q in qs), group(Qp, 1), group(Qp, 1),
                          *(group(Kp, k.shape[-1]) for k in keys), group(1, Kp)],
                out_specs=group(Qp, C),
                out_shape=jax.ShapeDtypeStruct((G, Qp, C), q_c.dtype),
                compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
                interpret=interpret if interpret is not None else on_cpu())
    args = (*map(padq, qs), padq(lo.astype(jnp.int32))[..., None], padq(hi.astype(jnp.int32))[..., None],
            *map(padk, keys), padk(kpos.astype(jnp.int32), -1)[:, None, :])
    return functools.partial(_gathered_kernel, scale=scale, n=len(keys)), spec, args


# The two entry points below are ONE kernel under the two names a reader of
# the device trace greps for. q_c (G, Q, C), q_r (G, Q, R) over G groups of K
# keys whose key k of group g sits at sequence position kpos[g, k]; query row
# q of the group sees the keys with lo[g, q] <= kpos <= hi[g, q] (lo >= 0: a
# key at a negative position is padding) -> (G, Q, C), softmax over those keys
# of ``(q_c . c + q_r . r) * scale`` times the latents.


# analyze: ok[jit-sentinel] -- kernel wrapper traced inline by the watched engine loops, never a serving dispatch entry point
@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def sparse_latent_attention(q_c: jax.Array, q_r: jax.Array, kv: jax.Array, kpos: jax.Array,
                            lo: jax.Array, hi: jax.Array, *, scale: float,
                            interpret: bool | None = None) -> jax.Array:
    """A full layer's: a group = a position's H heads over its selected keys,
    the rows ``kv`` (G, K, C + R) as its plane holds them, [c | r]."""
    kernel, spec, args = _gathered(q_c, q_r, (kv,), kpos, lo, hi, scale, interpret)
    with jax.named_scope("sparse_latent_attention"):  # the kernel alone, by its name, in the device trace
        out = pl.pallas_call(kernel, name="sparse_latent_attention", **spec)(*args)
    return out[:, :q_c.shape[1]]


# analyze: ok[jit-sentinel] -- kernel wrapper traced inline by the watched engine loops, never a serving dispatch entry point
@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def window_latent_attention(q_c: jax.Array, q_r: jax.Array, c: jax.Array, r: jax.Array,
                            kpos: jax.Array, lo: jax.Array, hi: jax.Array, *, scale: float,
                            interpret: bool | None = None) -> jax.Array:
    """A sliding layer's: a group = a row's T x H queries over the blocks
    that hold its window, c (G, K, C) and r (G, K, R) out of their two
    planes, each query its own causal and window edge."""
    kernel, spec, args = _gathered(q_c, q_r, (c, r), kpos, lo, hi, scale, interpret)
    with jax.named_scope("window_latent_attention"):  # the kernel alone, by its name, in the device trace
        out = pl.pallas_call(kernel, name="window_latent_attention", **spec)(*args)
    return out[:, :q_c.shape[1]]


def sparse_latent_attention_reference(q_c, q_r, kv, kpos, lo, hi, *, scale: float) -> jax.Array:
    """Pure-jnp twin of ``sparse_latent_attention``: the rows split again."""
    C, R = q_c.shape[-1], q_r.shape[-1]
    return gathered_latent_attention_reference(q_c, q_r, kv[..., :C], kv[..., C:C + R], kpos, lo, hi,
                                               scale=scale)


def gathered_latent_attention_reference(q_c, q_r, c, r, kpos, lo, hi, *, scale: float) -> jax.Array:
    """Pure-jnp twin of the kernel on keys that come as (c, r), float32 softmax."""
    s = (jnp.einsum("gqc,gkc->gqk", q_c, c, preferred_element_type=F32)
         + jnp.einsum("gqr,gkr->gqk", q_r, r, preferred_element_type=F32)) * scale
    seen = (kpos[:, None, :] >= lo[:, :, None]) & (kpos[:, None, :] <= hi[:, :, None])
    p = jax.nn.softmax(jnp.where(seen, s, _NEG_INF), axis=-1)
    return jnp.einsum("gqk,gkc->gqc", p.astype(c.dtype), c,
                      preferred_element_type=F32).astype(q_c.dtype)
