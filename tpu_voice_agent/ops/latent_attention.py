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
read ONCE for all riders — (sub, C) queries against one (bs, C) tile — then
each row's own blocks under the causal mask, the online softmax carried in
VMEM (float32 m, l, acc), a row's division its last act. T = 1 rides the same
kernel (a row's H queries). Dots take the pool's dtype as their operands (bf16
on the chip) and accumulate in float32; the probabilities are cast to that
dtype for the second dot.

Both passes multiply the query rows of the positions that are REAL (ISSUE 49;
row b's are ``t < n_real[b]``, of a fast-forward block's 9 about 1.4): an item
of either pass is two dots over its query rows and a softmax update of their
state, so its time goes with the rows. A row's query rows lie positions major,
heads minor, so its real positions' are its LEADING ``n_real[b] * H``. The
kernel's query operands are the layout it always had — (B * Rp, C) and
(B * Rp, R), riders first, a row padded to whole sublane tiles, resident — and
what XLA does around the call is what it was (an op added there parts seeded
plans: PERF.md section 6, PR 48). The kernel's first step copies each rider's
real positions, H rows each, to the row's packed place in VMEM (``BlockSplit.
pack_start`` / ``pack_n``: riders in row order); the common items walk the
sub-chunks of that list that hold one, their m / l / acc in packed place. When
a rider's own pass starts, its real rows' state moves from there to the row's
own block (every other row starts from nothing) and its own tiles go on from
what the common pass left, block by block, ascending — the one-pass walk's
order and arithmetic, nothing written out between the passes, and a query
row's dots do not depend on which rows share its tile: a real position's
output is the same bits whatever ``n_real`` packs beside it. An own item
advances the smallest of a few leading pieces of the row (``_own_pieces``)
that holds its real positions. A position that is not real returns its row's
last real position's output, as the row's last act (``ops/paged_attention.py``
has the rule and why); a live row without a real position zeros.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import on_cpu
from .paged_attention import (_NEG_INF, _STATE_BYTES, _VMEM_LIMIT, BlockSplit, _padded_query_rows,
                              common_block_split)


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())), preferred_element_type=jnp.float32)


def _latent_kernel(
    qpos_ref,  # SMEM (B*T,)
    meta_ref,  # SMEM (4,): [layer, S, items, packed sub-chunks that hold a query row]
    block_ref,  # SMEM: each item's pool block ...
    row_ref,  # ... row ...
    tile_ref,  # ... and table column
    slot_ref,  # SMEM (B,): riders first
    pack_ref,  # SMEM (B,): the row's first packed query row ...
    real_ref,  # ... and how many it holds there: a rider's real ones, 0 for a row that does not ride
    nreal_ref,  # SMEM (B,): the row's real POSITIONS (t < it), a rider's or not
    qc_ref,  # (B*Rp, C) — every row's absorbed queries, riders first
    qr_ref,  # (B*Rp, R) — and their rotated halves
    c_ref,  # (1, 1, bs, C) — pool block block[w]
    r_ref,  # (1, 1, bs, R)
    o_ref,  # (B*Rp, C) — rows in their own order
    pqc_ref,  # VMEM (P + Rp, C) — the riders' real positions' queries, packed
    pqr_ref,  # VMEM (P + Rp, R)
    pacc_ref,  # VMEM (P + Rp, C) f32 — the common pass's state, packed alike
    pm_ref,  # VMEM (P + Rp, 128) f32, a value across its lanes
    pl_ref,
    acc_ref,  # VMEM (Rp, C) f32 — the own pass's: one row's at a time
    m_ref,  # VMEM (Rp, 128) f32
    l_ref,
    *,
    scale: float,
    H: int,
    T: int,
    bs: int,
    Rp: int,  # query rows a batch row holds in the layout (T*H, padded)
    sub: int,  # query rows a sub-chunk of the common pass
    pieces: tuple[int, ...],  # the leading query rows an own item may advance, ascending to Rp
):
    w = pl.program_id(0)
    S, n, n_sub = meta_ref[1], meta_ref[2], meta_ref[3]
    C = acc_ref.shape[1]

    def advance(q_c, q_r, valid, refs, at):  # query rows over this item's block, their state at ``at``
        acc, m, l = refs
        size = q_c.shape[0]
        c, r = c_ref[0, 0], r_ref[0, 0]
        s = (_dot(q_c, c, ((1,), (1,))) + _dot(q_r, r, ((1,), (1,)))) * scale
        if valid is not None:
            s = jnp.where(valid, s, _NEG_INF)
        m_prev, l_prev = m[at, :1], l[at, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc[at, :] = acc[at, :] * alpha + _dot(p.astype(c.dtype), c, ((1,), (0,)))
        m[at, :] = jnp.broadcast_to(m_new, (size, 128))
        l[at, :] = jnp.broadcast_to(l_new, (size, 128))

    packed, own = (pacc_ref, pm_ref, pl_ref), (acc_ref, m_ref, l_ref)
    chunk = lambda i: pl.ds(pl.multiple_of(i * sub, sub), sub)
    position = lambda at, t: pl.ds(pl.multiple_of(at + t * H, H), H)  # H query rows: one position's

    @pl.when(w == 0)
    def _riders_start():
        def start(i, c):  # state from nothing, and no query a chunk's last rows may lack
            pqc_ref[chunk(i), :] = jnp.zeros((sub, C), pqc_ref.dtype)
            pqr_ref[chunk(i), :] = jnp.zeros((sub, pqr_ref.shape[1]), pqr_ref.dtype)
            pacc_ref[chunk(i), :] = jnp.zeros((sub, C), jnp.float32)
            pm_ref[chunk(i), :] = jnp.full((sub, 128), _NEG_INF, jnp.float32)
            pl_ref[chunk(i), :] = jnp.zeros((sub, 128), jnp.float32)
            return c

        jax.lax.fori_loop(0, n_sub, start, 0)

        def pack(b, c):  # a rider's real positions, H rows each, to the row's packed place
            def one(t, c):
                pqc_ref[position(pack_ref[b], t), :] = qc_ref[position(slot_ref[b] * Rp, t), :]
                pqr_ref[position(pack_ref[b], t), :] = qr_ref[position(slot_ref[b] * Rp, t), :]
                return c

            return jax.lax.fori_loop(0, real_ref[b] // H, one, c)

        jax.lax.fori_loop(0, slot_ref.shape[0], pack, 0)

    @pl.when(w < S)
    def _common():  # every rider sees the whole block: no mask
        def riders(i, c):
            advance(pqc_ref[chunk(i), :], pqr_ref[chunk(i), :], None, packed, chunk(i))
            return c

        jax.lax.fori_loop(0, n_sub, riders, 0)

    @pl.when(jnp.logical_and(w >= S, w < n))
    def _own():
        b, j = row_ref[w], tile_ref[w]
        n_pos = nreal_ref[b]
        first = jnp.logical_or(w == S, row_ref[jnp.maximum(w - 1, 0)] != b)
        last = jnp.logical_or(w == n - 1, row_ref[jnp.minimum(w + 1, row_ref.shape[0] - 1)] != b)

        def piece(size):  # the row's leading ``size`` query rows hold its real positions'
            rows = jax.lax.broadcasted_iota(jnp.int32, (size, 1), 0)

            @pl.when(first)
            def _row_start():
                # a rider's REAL positions go on from what the common pass left
                # them (the merge), every other query row from nothing
                carried = rows < real_ref[b]
                was = pl.ds(pl.multiple_of(pack_ref[b], H), size)
                acc_ref[:size, :] = jnp.where(carried, pacc_ref[was, :], 0.0)
                m_ref[:size, :] = jnp.where(carried, pm_ref[was, :], _NEG_INF)
                l_ref[:size, :] = jnp.where(carried, pl_ref[was, :], 0.0)

            k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (size, bs), 1)
            qpos_rows = jnp.zeros((size, 1), jnp.int32)  # padding rows stay at 0
            for i in range(min(T, -(-size // H))):
                qpos_rows = jnp.where(rows // H == i, qpos_ref[b * T + i], qpos_rows)
            at = pl.ds(pl.multiple_of(slot_ref[b] * Rp, Rp), size)
            # causal + frontier in one mask
            advance(qc_ref[at, :], qr_ref[at, :], k_pos <= qpos_rows, own, slice(0, size))

        for below, size in zip((-1, *pieces), pieces):  # the smallest that holds the row's real positions
            pl.when(jnp.logical_and(n_pos * H > below, n_pos * H <= size))(
                functools.partial(piece, size))

        @pl.when(last)
        def _row_finish():
            l = l_ref[:, :1]
            acc_ref[...] = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)

            @pl.when(n_pos < T)
            def _behind_the_real_ones():
                # a position behind its row's real ones returns the last real
                # one's output (a copy of it: it attends to just that);
                # ascending, so a source is never a row this loop wrote
                for t in range(1, T):
                    acc_ref[t * H:(t + 1) * H, :] = acc_ref[
                        position(0, jnp.maximum(jnp.minimum(t, n_pos - 1), 0)), :]

            # a live row without a real position: zeros
            o_ref[pl.ds(pl.multiple_of(b * Rp, Rp), Rp), :] = jnp.where(
                n_pos > 0, acc_ref[...], 0.0).astype(o_ref.dtype)


# query rows a sub-chunk of the PACKED common pass holds: only sub-chunks that
# hold a real position's run, so smaller wastes fewer rows behind the last one
# (the cell: ~45 positions x 16 heads = 720 rows a layer) and larger is faster
# when every position is real. The kernel alone at 32 rows, us a layer, packed |
# every position real: 128 rows 132.4 | 305.4, 256 128.7 | 275.5, 512 137.8 |
# 264.0 (my chip runs, PR 49)
_PACK_SUB = 256


def _own_pieces(T: int, H: int, Rp: int) -> tuple[int, ...]:
    """The leading query rows an own item advances, by the row's real positions:
    one position's, three positions' (each to whole sublane tiles), the whole
    padded row. At 32 rows of (9, 16) the kernel reads 128.7 us a layer with
    them, 144.6 with the whole row alone; a fourth piece of two positions' or
    32 rows for the first moved nothing (my chip runs, PR 49)."""
    return tuple(sorted({_padded_query_rows(t, H) for t in (1, 3) if t < T} | {Rp}))


def _packed_rows(B: int, T: int, H: int) -> tuple[int, int]:
    """-> (a sub-chunk's query rows, the packed list's: every position of every
    row has a slot, in whole sub-chunks)."""
    sub = min(_PACK_SUB, _padded_query_rows(B * T, H))
    return sub, -(-B * T * H // sub) * sub


def _rows_that_fit(B: int, T: int, H: int, C: int, R: int, itemsize: int) -> int:
    """The largest divisor of B whose rows' resident state — both query halves
    and the output, double-buffered, the rotated half padded to a lane tile;
    the packed copy of both halves; the packed acc, m, l — stays inside the
    block kernel's ``_STATE_BYTES`` (a row's own state, Rp rows, is beside it)."""
    Rp, lanes = _padded_query_rows(T, H), max(R, 128)
    per_row = (Rp * 2 * itemsize * (2 * C + lanes)
               + T * H * (itemsize * (C + lanes) + 4 * C + 2 * 4 * 128))
    return max([c for c in range(1, B + 1) if B % c == 0 and c * per_row <= _STATE_BYTES],
               default=1)


def latent_row_splits(shape: tuple[int, int, int, int, int], block_tables, q_positions, live,
                      bs: int, itemsize: int = 2, n_real=None) -> tuple[BlockSplit, ...]:
    """``common_block_split`` of each group of rows the kernel walks for
    queries of ``shape`` (B, T, H, C, R): one split where the rows' resident
    state fits it whole (the cell's 32 rows of 144 query rows do: 42 MB), else
    one a group — made by the caller once a forward, for all its layers.
    ``n_real`` (B,): the rows' real positions (None: all T), which the kernel
    packs."""
    B, T, H, C, R = shape
    Bg = _rows_that_fit(B, T, H, C, R, itemsize)
    cut = lambda x, g: None if x is None else x[g:g + Bg]
    return tuple(common_block_split(block_tables[g:g + Bg], q_positions[g:g + Bg], cut(live, g),
                                    bs, n_real=cut(n_real, g))
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
    split: tuple | None = None,  # ``latent_row_splits`` of the three above and
    # ``n_real``, when the caller has them already (one forward, many layers)
    n_real: jax.Array | None = None,  # (B,) int32: row b's real positions are
    # t < n_real[b] (None: all T). Both passes multiply those alone; a position
    # behind them returns the row's last real one's output, a row without one
    # zeros. Read where the wrapper makes the split; a caller's was made with it
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
    Bg = _rows_that_fit(B, T, H, C, R, q_c.dtype.itemsize)
    if split is None:
        split = latent_row_splits((B, T, H, C, R), block_tables, q_positions, live, bs,
                                  q_c.dtype.itemsize, n_real)
    if Bg < B:  # groups of rows, each with a split of its own
        return jnp.concatenate([
            paged_latent_attention(
                q_c[g:g + Bg], q_r[g:g + Bg], c_pool, r_pool, block_tables[g:g + Bg],
                q_positions[g:g + Bg], layer, None if live is None else live[g:g + Bg],
                (split[g // Bg],), scale=scale, interpret=interpret)
            for g in range(0, B, Bg)])
    (split,) = split
    sub, P = _packed_rows(B, T, H)

    def lay(q):  # (B, T, H, w) -> (B * Rp, w), riders first, a row padded to whole tiles
        q = q.reshape(B, T * H, q.shape[-1])[split.order]
        return jnp.pad(q, ((0, 0), (0, Rp - T * H), (0, 0))).reshape(B * Rp, q.shape[-1])

    whole = lambda width: pl.BlockSpec((B * Rp, width), lambda w, *_: (0, 0))
    pool = lambda width: pl.BlockSpec(
        (1, 1, bs, width), lambda w, qpos, meta, block, *_: (meta[0], block[w], 0, 0))
    out = pl.pallas_call(
        functools.partial(_latent_kernel, scale=scale, H=H, T=T, bs=bs, Rp=Rp, sub=sub,
                          pieces=_own_pieces(T, H, Rp)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=9,
            grid=(jnp.maximum(split.n_items, 1),),
            in_specs=[whole(C), whole(R), pool(C), pool(R)],
            out_specs=whole(C),
            scratch_shapes=[
                # a row's state is read from its packed place as a whole piece,
                # up to Rp rows: that much room behind the last slot
                pltpu.VMEM((P + Rp, C), q_c.dtype),
                pltpu.VMEM((P + Rp, R), q_r.dtype),
                pltpu.VMEM((P + Rp, C), jnp.float32),
                pltpu.VMEM((P + Rp, 128), jnp.float32),
                pltpu.VMEM((P + Rp, 128), jnp.float32),
                pltpu.VMEM((Rp, C), jnp.float32),
                pltpu.VMEM((Rp, 128), jnp.float32),
                pltpu.VMEM((Rp, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * Rp, C), q_c.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="paged_latent_attention",
    )(q_positions.astype(jnp.int32).reshape(-1),
      jnp.stack([jnp.reshape(layer, ()).astype(jnp.int32), split.n_common, split.n_items,
                 -(-split.counts[2] * H // sub)]),
      split.item_block, split.item_row, split.item_tile, split.slot,
      split.pack_start * H, split.pack_n * H, split.n_real,
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
