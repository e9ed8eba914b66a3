"""Paged attention over a LATENT cache (multi-head latent attention as
``deepseek_v3`` caches it; ``models.mla`` has the equations).

A cached token is, a layer, ONE latent ``c`` (C = ``kv_lora_rank`` wide,
normed) and ONE rotated key ``r`` (R = ``qk_rope_dim``) that every head
shares — no K plane and no V plane. With the up-projections absorbed into the
query and the output, head h of a query scores a position as
``(q_c[h] . c + q_r[h] . r) * scale`` and its output is ``sum p c``: the SAME
(bs, C) tile of a block serves the scores and the values, and all H heads of
all T positions of a row are query rows over one shared "head".

The pool is TWO planes, ``c_pool`` (L, N, bs, C) and ``r_pool`` (L, N, bs, R),
so a block is a (bs, C) tile of whole 128-lane columns beside a (bs, R) one;
C + R = 576 in one plane would be 4.5 lane tiles, every slice of it a relayout
(PERF.md section 6, PR 38). ``bs`` is the second-minor axis: a heads axis of
one there would pad every position to a whole sublane tile in HBM.

``paged_latent_attention`` is ``paged_block_attention``'s walk (its
``common_block_split`` as it is): the S blocks live rows hold in common are
read ONCE for all riders — sub-chunks of whole batch rows, (sub, C) queries
against one (bs, C) tile — then each row's own blocks under the causal mask,
the online softmax carried in VMEM (float32 m, l, acc), a row's division its
last act. T = 1 rides the same kernel (a row's H queries). Dots take the
pool's dtype as their operands (bf16 on the chip) and accumulate in float32;
the probabilities are cast to that dtype for the second dot.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import on_cpu
from .paged_attention import (_NEG_INF, _STATE_BYTES, _VMEM_LIMIT, BlockSplit, _padded_query_rows,
                              _sub_rows, common_block_split)


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())), preferred_element_type=jnp.float32)


def _latent_kernel(
    qpos_ref,  # SMEM (B*T,)
    meta_ref,  # SMEM (5,): [layer, S, items, riders, sub-chunks that hold one]
    block_ref,  # SMEM: each item's pool block ...
    row_ref,  # ... row ...
    tile_ref,  # ... and table column
    slot_ref,  # SMEM (B,): riders first
    qc_ref,  # (B*Rp, C) — every row's absorbed queries, riders first
    qr_ref,  # (B*Rp, R) — and their rotated halves
    c_ref,  # (1, 1, bs, C) — pool block block[w]
    r_ref,  # (1, 1, bs, R)
    o_ref,  # (B*Rp, C) — rows in their own order
    acc_ref,  # VMEM (B*Rp, C) f32
    m_ref,  # VMEM (B*Rp, 128) f32, a value across its lanes
    l_ref,
    *,
    scale: float,
    H: int,
    T: int,
    bs: int,
    Rp: int,  # query rows a batch row holds in the layout (T*H, padded)
    sub: int,  # query rows a sub-chunk of the common pass: whole batch rows
):
    w = pl.program_id(0)
    S, n, n_riders, n_sub = meta_ref[1], meta_ref[2], meta_ref[3], meta_ref[4]
    C = acc_ref.shape[1]

    def start(at, size):  # state from nothing
        acc_ref[at, :] = jnp.zeros((size, C), jnp.float32)
        m_ref[at, :] = jnp.full((size, 128), _NEG_INF, jnp.float32)
        l_ref[at, :] = jnp.zeros((size, 128), jnp.float32)

    def advance(at, size, valid):  # rows ``at`` over this item's block
        c, r = c_ref[0, 0], r_ref[0, 0]
        s = (_dot(qc_ref[at, :], c, ((1,), (1,))) + _dot(qr_ref[at, :], r, ((1,), (1,)))) * scale
        if valid is not None:
            s = jnp.where(valid, s, _NEG_INF)
        m_prev, l_prev = m_ref[at, :1], l_ref[at, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[at, :] = acc_ref[at, :] * alpha + _dot(p.astype(c.dtype), c, ((1,), (0,)))
        m_ref[at, :] = jnp.broadcast_to(m_new, (size, 128))
        l_ref[at, :] = jnp.broadcast_to(l_new, (size, 128))

    chunk = lambda i: pl.ds(pl.multiple_of(i * sub, sub), sub)

    @pl.when(w == 0)
    def _riders_start():
        jax.lax.fori_loop(0, n_sub, lambda i, c: (start(chunk(i), sub), c)[1], 0)

    @pl.when(w < S)
    def _common():  # every rider sees the whole block: no mask
        jax.lax.fori_loop(0, n_sub, lambda i, c: (advance(chunk(i), sub, None), c)[1], 0)

    @pl.when(jnp.logical_and(w >= S, w < n))
    def _own():
        b, j = row_ref[w], tile_ref[w]
        at = pl.ds(pl.multiple_of(slot_ref[b] * Rp, Rp), Rp)
        first = jnp.logical_or(w == S, row_ref[jnp.maximum(w - 1, 0)] != b)
        last = jnp.logical_or(w == n - 1, row_ref[jnp.minimum(w + 1, row_ref.shape[0] - 1)] != b)

        @pl.when(jnp.logical_and(first, slot_ref[b] >= n_riders))
        def _row_start():  # a rider goes on from the common pass: the merge
            start(at, Rp)

        k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (Rp, bs), 1)
        qpos_rows = jnp.zeros((Rp, 1), jnp.int32)  # padding rows stay at 0
        for i in range(T):
            qpos_rows = jnp.where(
                (jax.lax.broadcasted_iota(jnp.int32, (Rp, 1), 0) // H) == i,
                qpos_ref[b * T + i], qpos_rows)
        advance(at, Rp, k_pos <= qpos_rows)  # causal + frontier in one mask

        @pl.when(last)
        def _row_finish():
            to = pl.ds(pl.multiple_of(b * Rp, Rp), Rp)
            l = l_ref[at, :1]
            o_ref[to, :] = (acc_ref[at, :] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _rows_that_fit(B: int, Rp: int, C: int, R: int, itemsize: int) -> int:
    """The largest divisor of B whose rows' resident state (both query halves
    and the output, double-buffered, the rotated half padded to a lane tile;
    acc, m, l) stays inside the block kernel's ``_STATE_BYTES``."""
    per_row = Rp * (2 * itemsize * (2 * C + max(R, 128)) + 4 * C + 2 * 4 * 128)
    return max([c for c in range(1, B + 1) if B % c == 0 and c * per_row <= _STATE_BYTES],
               default=1)


def latent_row_splits(shape: tuple[int, int, int, int, int], block_tables, q_positions, live,
                      bs: int, itemsize: int = 2) -> tuple[BlockSplit, ...]:
    """``common_block_split`` of each group of rows the kernel walks for
    queries of ``shape`` (B, T, H, C, R): one split where the rows' resident
    state fits it whole (the cell's 32 rows of 144 query rows do: 35 MB), else
    one a group — made by the caller once a forward, for all its layers."""
    B, T, H, C, R = shape
    Bg = _rows_that_fit(B, _padded_query_rows(T, H), C, R, itemsize)
    return tuple(common_block_split(block_tables[g:g + Bg], q_positions[g:g + Bg],
                                    None if live is None else live[g:g + Bg], bs)
                 for g in range(0, B, Bg))


# analyze: ok[jit-sentinel] -- kernel wrapper traced inline by the watched engine loops, never a serving dispatch entry point
@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_latent_attention(
    q_c: jax.Array,  # (B, T, H, C) — queries with W_UK absorbed
    q_r: jax.Array,  # (B, T, H, R) — their rotated halves
    c_pool: jax.Array,  # (L, N, bs, C)
    r_pool: jax.Array,  # (L, N, bs, R)
    block_tables: jax.Array,  # (B, max_blocks) int32
    q_positions: jax.Array,  # (B, T) int32 — each query's sequence position
    layer: jax.Array,  # scalar int32
    live: jax.Array | None = None,  # (B,) bool — rows whose output is read
    split: tuple | None = None,  # ``latent_row_splits`` of the three above,
    # when the caller has them already (one forward, many layers)
    *,
    scale: float,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns (B, T, H, C): head h of query i of a row, softmax over
    positions [0, q_positions[b, i]] of the row's paged sequence of
    ``(q_c . c + q_r . r) * scale``, times the latents (the caller has
    already scattered the block's own c and r at those positions). Unused
    table entries must hold a valid block id. A row that is not ``live`` is
    not attended: zeros."""
    B, T, H, C = q_c.shape
    R, bs = q_r.shape[-1], c_pool.shape[2]
    interpret = interpret if interpret is not None else on_cpu()
    Rp = _padded_query_rows(T, H)
    Bg = _rows_that_fit(B, Rp, C, R, q_c.dtype.itemsize)
    if split is None:
        split = latent_row_splits((B, T, H, C, R), block_tables, q_positions, live, bs,
                                  q_c.dtype.itemsize)
    if Bg < B:  # groups of rows, each with a split of its own
        return jnp.concatenate([
            paged_latent_attention(
                q_c[g:g + Bg], q_r[g:g + Bg], c_pool, r_pool, block_tables[g:g + Bg],
                q_positions[g:g + Bg], layer, None if live is None else live[g:g + Bg],
                (split[g // Bg],), scale=scale, interpret=interpret)
            for g in range(0, B, Bg)])
    (split,) = split
    Bc = _sub_rows(B)

    def lay(q):  # (B, T, H, w) -> (B * Rp, w), riders first, a row padded to whole tiles
        q = q.reshape(B, T * H, q.shape[-1])[split.order]
        return jnp.pad(q, ((0, 0), (0, Rp - T * H), (0, 0))).reshape(B * Rp, q.shape[-1])

    whole = lambda width: pl.BlockSpec((B * Rp, width), lambda w, *_: (0, 0))
    pool = lambda width: pl.BlockSpec(
        (1, 1, bs, width), lambda w, qpos, meta, block, *_: (meta[0], block[w], 0, 0))
    out = pl.pallas_call(
        functools.partial(_latent_kernel, scale=scale, H=H, T=T, bs=bs, Rp=Rp, sub=Bc * Rp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(jnp.maximum(split.n_items, 1),),
            in_specs=[whole(C), whole(R), pool(C), pool(R)],
            out_specs=whole(C),
            scratch_shapes=[
                pltpu.VMEM((B * Rp, C), jnp.float32),
                pltpu.VMEM((B * Rp, 128), jnp.float32),
                pltpu.VMEM((B * Rp, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * Rp, C), q_c.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="paged_latent_attention",
    )(q_positions.astype(jnp.int32).reshape(-1),
      jnp.stack([jnp.reshape(layer, ()).astype(jnp.int32), split.n_common, split.n_items,
                 split.n_riders, -(-split.n_riders // Bc)]),
      split.item_block, split.item_row, split.item_tile, split.slot,
      lay(q_c), lay(q_r), c_pool, r_pool)
    # a row without an item was never written: zeros, not what the buffer held
    out = jnp.where(split.attended[:, None, None], out.reshape(B, Rp, C)[:, :T * H], 0)
    return out.reshape(B, T, H, C)


def latent_attention_reference(q_c, q_r, c, r, q_positions, *, scale: float) -> jax.Array:
    """Plain absorbed attention of (B, T, H, C) / (B, T, H, R) queries over
    (B, S, C) latents and (B, S, R) rotated keys whose slot IS their
    position: causal, float32 softmax -> (B, T, H, C)."""
    f32 = jnp.float32
    B, T, H, C = q_c.shape
    # every head of every position a query ROW over the one shared "head"
    rows = lambda q: q.reshape(B, T * H, q.shape[-1])
    s = (jnp.einsum("bqc,bsc->bqs", rows(q_c), c, preferred_element_type=f32)
         + jnp.einsum("bqr,bsr->bqs", rows(q_r), r, preferred_element_type=f32)) * scale
    seen = jnp.arange(c.shape[1])[None, None, :] <= q_positions[:, :, None]  # (B, T, S)
    p = jax.nn.softmax(jnp.where(jnp.repeat(seen, H, axis=1), s, _NEG_INF), axis=-1)
    return jnp.einsum("bqs,bsc->bqc", p.astype(c.dtype), c,
                      preferred_element_type=f32).astype(q_c.dtype).reshape(B, T, H, C)


def paged_latent_attention_reference(q_c, q_r, c_pool, r_pool, block_tables, q_positions, layer,
                                     *, scale: float) -> jax.Array:
    """Pure-jnp twin of the kernel: gather the rows' blocks, attend plainly."""
    B = q_c.shape[0]
    S = block_tables.shape[1] * c_pool.shape[2]
    c = c_pool[layer][block_tables].reshape(B, S, -1)
    r = r_pool[layer][block_tables].reshape(B, S, -1)
    return latent_attention_reference(q_c, q_r, c, r, q_positions, scale=scale)
