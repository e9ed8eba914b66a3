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

``walked_latent_attention`` — the SAME softmax over the same key set with the
keys fetched the other way (ISSUE 62): a tile of positions WALKS its rows'
table columns block by block straight out of the pool (whole (bs, C + R)
blocks as the pool holds them: no row gather, no relayout), every visible key
scored, the selection a MEMBERSHIP MASK ``chosen[p, s]`` that sends an
unchosen key to -inf, online softmax in VMEM. A column whose block id is the
same for every slot of the tile (the cached head's) is read ONCE and
multiplied against all the tile's query rows; a column that differs by slot
goes slot by slot (``walk_split`` writes the list of items from the tables
and the positions, once a forward; a dynamic grid walks it). It does
``nb * bs / K`` times the gathered kernel's FLOPs on ``tile`` times its query
rows a key block, and no gather, which the chip charges by the ROW (~15 ns of
1152 B): ``walks`` says, from the shapes alone, which fetch is the cheaper.

``threshold_members`` — a walked tile's selection (ISSUE 63): ``lax.top_k``'s index set as the
membership mask itself, from the k-th largest score and the last tie ``top_k`` takes — bisection on
the keys' bits, compare-and-count over the tile's keys whole in VMEM — where ``lax.top_k`` sorts
every row and the mask reads two scalars of it. ``chosen_mask`` (``lax.top_k`` +
``top_k_members``) is its twin; the gather branch, which needs the indices, keeps the sort.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from typing import NamedTuple

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


# ---------------------------------------------------------------- the walk


# What a slot of a tile pays for its selected attention, ns, by the two ways to
# fetch its keys — READINGS (``tools/selected_attn_check.py`` on the chip, PR 62:
# a tile of 16 slots behind top-2048 of 8832 keys, 64 columns in common, us a
# tile pass at 64 | 128 heads: gather + gathered kernel 589.1 | 620.1, the
# gathered kernel alone 59.8 | 92.3, the walk 196.6 | 310.7; PERF.md section 6
# has the table): the gather a chosen ROW out of the pool, whatever the heads
# (the chip charges a gather by rows: (589.1 - 59.8) / 16 / 2048), the gathered
# kernel a (chosen key, head) (the cheaper reading, 128 heads': what favours
# the gather), the walk a (key the table spans, head) (the dearer, 64 heads').
_GATHER_ROW_NS = 16.2
_GATHERED_KEY_HEAD_NS = 0.022
_WALKED_KEY_HEAD_NS = 0.0217


def walks(keys: int, topk: int, heads: int) -> bool:
    """Whether a selected layer WALKS its row's blocks under the selection as a
    mask (True) or GATHERS the chosen rows (False): the cheaper by the readings
    above, from the shapes a trace already has — ``keys`` = the positions a
    row's table spans in this program (``nb * bs``), ``topk`` = the keys a
    position attends (K), ``heads`` (H). 8832 keys behind top-2048 walk at 64
    and 128 heads (a few times K: the rule turns at ~26 k keys at 64 heads, ~14 k
    at 128); 131072 gather (60 x the FLOPs)."""
    return keys * heads * _WALKED_KEY_HEAD_NS < topk * (_GATHER_ROW_NS + heads * _GATHERED_KEY_HEAD_NS)


def _total_order(x: jax.Array) -> jax.Array:
    """float32 -> int32 keys in the order ``lax.top_k`` sorts by: XLA's total
    order, in which -0.0 stands BELOW 0.0 (a float compare calls them equal)."""
    bits = jax.lax.bitcast_convert_type(x.astype(F32), jnp.int32)
    return jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)


def top_k_members(mine: jax.Array, vals: jax.Array, sel: jax.Array) -> jax.Array:
    """(P, S) scores and their ``lax.top_k`` (values, indices), (P, k) each ->
    (P, S) bool: s is in ``sel[p]`` — ``top_k``'s index set, ties and -inf rows
    as it takes them (lower index first), with no scatter of k scalars a
    position: every value above the k-th, and of the values EQUAL to it those at
    or before the last one ``top_k`` took (it takes ties in index order, so the
    taken ones are a prefix of them). Above and equal in ``top_k``'s own order."""
    score, took = _total_order(mine), _total_order(vals)
    kth = took[:, -1:]
    last_tie = jnp.max(jnp.where(took == kth, sel, -1), axis=1, keepdims=True)
    seq = jnp.arange(mine.shape[1], dtype=sel.dtype)[None, :]
    return (score > kth) | ((score == kth) & (seq <= last_tie))


def chosen_mask(mine: jax.Array, k: int) -> jax.Array:
    """``top_k_members`` of ``lax.top_k(mine, k)``: the twin of ``threshold_members`` (what a walked
    tile runs with the kernels off), and the yardstick of the tests and the tool."""
    return top_k_members(mine, *jax.lax.top_k(mine, k))


# WHICH BRANCH SORTS. ``lax.top_k`` lowers to a FULL sort of every row ((16, 8832) float32 with its
# indices: 123-136 us a tile in the cells' device traces — ledger, PR 62), and ``top_k_members`` reads two
# scalars a row of the result. The gather branch keeps it: it fetches by the sorted indices. The walk
# branch wants the mask alone, and makes it below by an exact k-th order statistic — the same set bit
# for bit. READINGS (``tools/selected_attn_check.py --select`` on the chip, PR 63: a tile of 16 behind
# top-2048 of 8832 keys, us a tile inside a program whose own share is 26): ``lax.top_k`` +
# ``top_k_members`` 161.9; the steps below as XLA alone, unrolled (93 fusions) 89.7; as ONE kernel over
# the keys resident in VMEM 36.5 — ~11 us of its own against ~136. PERF.md section 6, PR 63.

_INT_MIN = -(1 << 31)


def _count(hit: jax.Array) -> jax.Array:
    """(P, S) bool -> (P, 1) int32: the hits a row."""
    return jnp.sum(hit.astype(jnp.int32), axis=1, keepdims=True)


def _largest(bits: int, rows: int, holds, unroll: bool) -> jax.Array:
    """-> (rows, 1) int32: a row's largest t of ``bits`` bits with ``holds(t)`` (rows, 1) bool — true
    of 0 and of no t past a false one — made bit by bit from the top one down: ``bits`` calls."""
    def step(i, t):
        cand = t | jnp.left_shift(jnp.int32(1), bits - 1 - i)
        return jnp.where(holds(cand), cand, t)

    return jax.lax.fori_loop(0, bits, step, jnp.zeros((rows, 1), jnp.int32), unroll=unroll)


def _select_members(keys, hold, k: int, unroll: bool) -> jax.Array:
    """``top_k_members``' mask, as (P, S) int32, with NO sorted row: ``keys()`` -> (P, S) int32 in
    ``_total_order`` (read anew at every use: a kernel's block stays where it is), k < S. The k-th
    largest key a row is the largest value that k keys reach — 32 compare-and-count steps, its bits
    from the sign down (t holds them offset: t ^ INT_MIN is the key) —; the last tie ``top_k`` takes
    is the position of the ``need``-th key EQUAL to it, need = k - the keys above — the largest
    position with fewer than ``need`` such keys before it, ceil(log2 S) steps more over ``spot``
    (a tied key's position, S elsewhere; ``hold(value)`` keeps it and returns its reader)."""
    P, S = keys().shape
    kth = _largest(32, P, lambda t: _count(keys() >= (t ^ _INT_MIN)) >= k, unroll) ^ _INT_MIN
    need = k - _count(keys() > kth)
    seq = jax.lax.broadcasted_iota(jnp.int32, (P, S), 1)
    spot = hold(jnp.where(keys() == kth, seq, S))
    last_tie = _largest((S - 1).bit_length(), P, lambda t: _count(spot() < t) < need, unroll)
    return ((keys() > kth) | (spot() <= last_tie)).astype(jnp.int32)


def _threshold_kernel(k_ref, o_ref, *, k: int):
    """keys (P, S) int32 -> members (P, S) int32, both whole in VMEM; ``spot`` lives in the output's
    block until the members overwrite it."""
    def hold(v):
        o_ref[...] = v
        return lambda: o_ref[...]

    o_ref[...] = _select_members(lambda: k_ref[...], hold, k, unroll=False)


# analyze: ok[jit-sentinel] -- kernel wrapper traced inline by the watched engine loops, never a serving dispatch entry point
@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def threshold_members(mine: jax.Array, k: int, *, interpret: bool | None = None) -> jax.Array:
    """``chosen_mask(mine, k)`` bit for bit — ``lax.top_k``'s index set of (P, S) float32 scores as
    a (P, S) bool mask: ties in its order, -0.0 below 0.0, -inf keys where fewer than k are finite,
    NaNs where its total order puts them — by an exact k-th order statistic (``_select_members``)
    where ``lax.top_k`` SORTS every row whole and a walked tile reads two scalars a row of the result.
    One call, the tile's keys resident in VMEM (565 KB at (16, 8832)); k >= S chooses everything."""
    P, S = mine.shape
    if k >= S:
        return jnp.ones((P, S), bool)
    Pp, Sp = -(-P // 8) * 8, -(-S // 128) * 128
    # (a padding key stands below every real one of its value: behind them, and k <= S never reaches it)
    keys = jnp.pad(_total_order(mine), ((0, Pp - P), (0, Sp - S)), constant_values=_INT_MIN)
    call = pl.pallas_call(
        functools.partial(_threshold_kernel, k=k),
        out_shape=jax.ShapeDtypeStruct((Pp, Sp), jnp.int32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret if interpret is not None else on_cpu(),
        name="threshold_members",
    )
    with jax.named_scope("threshold_members"):  # the kernel alone, by its name, in the device trace
        return call(keys)[:P, :S] != 0


class WalkSplit(NamedTuple):
    """``walk_split``'s work lists, a row a tile (tables and positions do not
    move inside a forward: made once for all its layers)."""

    n_common: jax.Array  # (tiles,) int32 — the leading items: the COMMON columns, ``_WALK_COLS`` an item
    n_items: jax.Array  # (tiles,) int32 — those + the own (slot, column) pairs
    n_columns: jax.Array  # (tiles,) int32 — the common columns
    keys: jax.Array  # (tiles, (1 + tile) * nb) int32 — what to read, sorted: the common columns'
    # ``col * tile + slot`` (the first slot that sees the column: its table names the block), then
    # the own pairs' ``tile * nb + slot * nb + col``, then what nobody reads


# table columns a common item holds: their blocks stand in line as ONE key tile
# of that many x bs keys, so a query row's state (acc: C float32 values) is
# rescaled once for all of them — at one block an item that rescale was most of
# the kernel's vector work (PERF.md section 6, PR 62: the readings)
_WALK_COLS = 4
# query rows a step of the common pass advances at once (whole slots: the mask
# is a row a slot): the key tile stays in the MXU while they stream. The walk's
# us a tile pass at 64 | 128 heads by (columns an item : rows a step) — 1:256
# 350.9 | 605.0, 2:256 237.1 | 399.6, 2:512 224.4 | 376.0, 4:128 225.4 | 367.7,
# 4:256 204.5 | 333.3, **4:512 196.6 | 310.7**, 8:256 209.7 | 321.6 (my chip
# runs, PR 62, ``tools/selected_attn_check.py --try``)
_WALK_SUB = 512


def walk_split(tables: jax.Array, positions: jax.Array, tile: int, bs: int,
               real: jax.Array | None = None) -> WalkSplit:
    """tables (P, nb), positions (P,), P a multiple of ``tile``; real (P,) bool:
    the slots whose output is read (None: all) -> the work of every tile of
    ``tile`` slots, from what the kernel is handed and nothing else. A real slot
    SEES a column where ``col * bs <= position``; a column some slot sees is
    COMMON where every slot that sees it holds the same block id there, else
    OWN to each slot that sees it. A slot that is not real has no item and
    decides nothing (a tile's filler stands on whatever row the packed order
    ends in — an idle row's table of zeros: it must not part the columns the
    real slots hold in common); what it returns is nobody's to read. Compares,
    sums and ONE sort of small integers a tile: no gather of scalars (the chip
    charges those by the row — the item lists gathered out of an argsort cost
    2.4 ms a forward, more than half the walks they fed: PERF.md section 6, PR 62);
    the kernel reads an item's block id out of the tile's table itself."""
    P, nb = tables.shape
    G = P // tile
    tb = tables.astype(jnp.int32).reshape(G, tile, nb)
    col = jnp.arange(nb, dtype=jnp.int32)
    seen = col * bs <= positions.astype(jnp.int32).reshape(G, tile, 1)
    if real is not None:
        seen &= real.reshape(G, tile, 1)
    first = jnp.argmax(seen, axis=1).astype(jnp.int32)  # (G, nb): the first slot that sees the column
    lead = jnp.take_along_axis(tb, first[:, None, :], axis=1)
    same = jnp.all((tb == lead) | ~seen, axis=1)  # (G, nb)
    common, own = same & jnp.any(seen, axis=1), seen & ~same[:, None, :]
    last = 2 * tile * nb  # past every key: what nobody reads
    slot = jnp.arange(tile, dtype=jnp.int32)[:, None]
    keys = jnp.concatenate([jnp.where(common, col * tile + first, last),
                            jnp.where(own, tile * nb + slot * nb + col, last).reshape(G, tile * nb)], axis=1)
    n_c, n_own = jnp.sum(common, axis=1, dtype=jnp.int32), jnp.sum(own, axis=(1, 2), dtype=jnp.int32)
    groups = -(-n_c // _WALK_COLS)
    return WalkSplit(groups, groups + n_own, n_c, jnp.sort(keys, axis=1))


def walk_entry(w, j, groups, n_columns, keys, tile: int, nb: int):
    """Item ``w``'s j-th column of one tile -> (the slot whose table names its
    block, the table column): a common item's j-th column (its last one again
    where it holds fewer), an own item's one, whatever j. Scalar arithmetic on
    ``keys`` (an array or a ref): the kernel's index maps and its body read
    their blocks, masks and slots through it."""
    common = w < groups
    key = keys[jnp.where(common, jnp.minimum(w * _WALK_COLS + j, n_columns - 1), n_columns + w - groups)]
    key = jnp.clip(key, 0, 2 * tile * nb - 1)  # (a tile without an item reads SOMETHING valid)
    return (jnp.where(common, key % tile, (key - tile * nb) // nb),
            jnp.where(common, key // tile, (key - tile * nb) % nb))


def _walked_kernel(meta_ref, keys_ref, table_ref, q_ref, *refs,
                   scale: float, H: int, sub: int, NK: int, nb: int):
    """meta SMEM [layer, common items, items, common columns], keys and the
    tile's table (tile * nb,) SMEM: ``walk_entry``'s; q (tile * H, C + R) rows
    [q_c | q_r], a slot's H heads in line; NK masks (tile, bs) int32: the slots'
    chosen keys of this item's columns; NK k (1, 1, bs, C + R): their pool
    blocks -> o (tile * H, C). keys (NK * bs, C + R): a common item's blocks in
    line; acc (tile * H, C), m, l (tile * H, 128: a value across its lanes)
    float32 in VMEM."""
    del table_ref  # read by the index maps
    masks, ks, (o_ref, line_ref, acc_ref, m_ref, l_ref) = refs[:NK], refs[NK:2 * NK], refs[2 * NK:]
    w = pl.program_id(0)
    S, n, n_c = meta_ref[1], meta_ref[2], meta_ref[3]
    C, bs = acc_ref.shape[1], ks[0].shape[2]

    @pl.when(w == 0)
    def _start():
        acc_ref[...] = jnp.zeros(acc_ref.shape, F32)
        m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, F32)
        l_ref[...] = jnp.zeros(l_ref.shape, F32)

    def advance(at, size: int, first_slot, slots: int, kv, held):
        """Query rows ``at`` (``slots`` whole slots from ``first_slot``) over the key tile ``kv``
        (its blocks the item's first ``held`` columns, a Python int or a scalar)."""
        cols = kv.shape[0] // bs
        seen = jnp.concatenate([
            jnp.concatenate([jnp.broadcast_to(jnp.logical_and(masks[j][pl.ds(first_slot + i, 1), :] != 0, j < held),
                                              (H, bs)) for j in range(cols)], axis=1)
            for i in range(slots)], axis=0)
        s = jnp.where(seen, _dot(q_ref[at, :], kv, ((1,), (1,))) * scale, _NEG_INF)
        m_prev, l_prev = m_ref[at, :1], l_ref[at, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        c = kv[:, :C]
        acc_ref[at, :] = acc_ref[at, :] * alpha + _dot(p.astype(c.dtype), c, ((1,), (0,)))
        m_ref[at, :] = jnp.broadcast_to(m_new, (size, 128))
        l_ref[at, :] = jnp.broadcast_to(alpha * l_prev + jnp.sum(p, axis=1, keepdims=True), (size, 128))

    @pl.when(w < S)
    def _common():  # the item's blocks, every slot of the tile
        for j in range(NK):
            line_ref[j * bs:(j + 1) * bs, :] = ks[j][0, 0]

        def chunk(i, c):
            advance(pl.ds(pl.multiple_of(i * sub, sub), sub), sub, i * (sub // H), sub // H,
                    line_ref[...], n_c - w * NK)
            return c

        jax.lax.fori_loop(0, acc_ref.shape[0] // sub, chunk, 0, unroll=True)

    @pl.when(jnp.logical_and(w >= S, w < n))
    def _own():  # a slot's own block
        slot = walk_entry(w, 0, S, n_c, keys_ref, q_ref.shape[0] // H, nb)[0]
        advance(pl.ds(pl.multiple_of(slot * H, H), H), H, slot, 1, ks[0][0, 0], 1)

    @pl.when(w == jnp.maximum(n, 1) - 1)
    def _finish():  # (a slot that met no chosen key holds the mean of what it was masked from: zeros)
        met = m_ref[:, :1] > _NEG_INF
        o_ref[...] = jnp.where(met, acc_ref[...] / jnp.where(met, l_ref[:, :1], 1.0), 0.0).astype(o_ref.dtype)


# analyze: ok[jit-sentinel] -- kernel wrapper traced inline by the watched engine loops, never a serving dispatch entry point
@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def walked_latent_attention(q_c: jax.Array, q_r: jax.Array, plane: jax.Array, layer: jax.Array,
                            chosen: jax.Array, tables: jax.Array, split: WalkSplit, *, scale: float,
                            interpret: bool | None = None) -> jax.Array:
    """ONE tile of a selected layer: q_c (tile, H, C), q_r (tile, H, R) in the
    pool's dtype; plane (L, N, bs, C + R), the layer's WHOLE pool plane stack
    and ``layer`` its scalar index (the kernel's index map reads a block out of
    it: never ``plane[layer]``); chosen (tile, nb * bs) bool — slot p attends
    the key at sequence position s of ITS table iff ``chosen[p, s]`` (the
    caller has cut it to ``s <= position``); tables (tile, nb): the slots'
    table columns; split: THIS tile's row of ``walk_split`` -> (tile, H, C),
    softmax over those keys of ``(q_c . c + q_r . r) * scale`` times the
    latents. A slot without a chosen key returns zeros."""
    tile, H, C = q_c.shape
    bs, NK, nb = plane.shape[2], _WALK_COLS, tables.shape[1]
    q = key_row(q_c, q_r).astype(plane.dtype)
    if q.shape[-1] != plane.shape[-1]:
        raise ValueError(f"a plane of {plane.shape[-1]} columns holds no rows [c | r] of {C} + {q_r.shape[-1]}")
    Hp = -(-H // 16) * 16  # a slot's heads in whole sublane tiles of the pool's dtype
    q = jnp.pad(q, ((0, 0), (0, Hp - H), (0, 0))).reshape(tile * Hp, q.shape[-1])
    sub = Hp * max(d for d in range(1, tile + 1) if tile % d == 0 and d * Hp <= max(_WALK_SUB, Hp))
    entry = lambda w, j, meta, keys: walk_entry(w, j, meta[1], meta[3], keys, tile, nb)
    whole = lambda width: pl.BlockSpec((tile * Hp, width), lambda w, *_: (0, 0))
    column = lambda j: pl.BlockSpec((tile, bs), lambda w, meta, keys, table: (0, entry(w, j, meta, keys)[1]))

    def block(j):
        def at(w, meta, keys, table):
            slot, col = entry(w, j, meta, keys)
            return meta[0], table[slot * nb + col], 0, 0
        return pl.BlockSpec((1, 1, bs, plane.shape[-1]), at)

    call = pl.pallas_call(
        functools.partial(_walked_kernel, scale=scale, H=Hp, sub=sub, NK=NK, nb=nb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(jnp.maximum(split.n_items, 1),),
            in_specs=[whole(q.shape[-1]), *map(column, range(NK)), *map(block, range(NK))],
            out_specs=whole(C),
            scratch_shapes=[pltpu.VMEM((NK * bs, plane.shape[-1]), plane.dtype),
                            pltpu.VMEM((tile * Hp, C), F32), pltpu.VMEM((tile * Hp, 128), F32),
                            pltpu.VMEM((tile * Hp, 128), F32)],
        ),
        out_shape=jax.ShapeDtypeStruct((tile * Hp, C), q_c.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret if interpret is not None else on_cpu(),
        name="walked_latent_attention",
    )
    meta = jnp.stack([jnp.reshape(layer, ()).astype(jnp.int32), split.n_common, split.n_items, split.n_columns])
    members = chosen.astype(jnp.int32)
    # (the SCOPE a reader of the device trace finds a full layer's attention kernel by, whichever fetch ran)
    with jax.named_scope("sparse_latent_attention"):
        out = call(meta, split.keys, tables.astype(jnp.int32).reshape(-1), q, *(members,) * NK, *(plane,) * NK)
    return out.reshape(tile, Hp, C)[:, :H]


def walked_latent_attention_reference(q_c, q_r, plane, layer, chosen, tables, split=None, *,
                                      scale: float) -> jax.Array:
    """Pure-jnp twin of ``walked_latent_attention`` (it reads by no item: every slot
    gathers its row's blocks and attends them under the mask), float32 softmax."""
    tile, H, C = q_c.shape
    kv = plane[layer, tables].reshape(tile, -1, plane.shape[-1])  # (tile, nb * bs, C + R)
    s = jnp.einsum("phc,pkc->phk", key_row(q_c, q_r).astype(kv.dtype), kv, preferred_element_type=F32) * scale
    s = jnp.where(chosen[:, None, :], s, _NEG_INF)
    p = jnp.where(chosen[:, None, :], jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    a = jnp.einsum("phk,pkc->phc", p.astype(kv.dtype), kv[..., :C], preferred_element_type=F32)
    return (a / jnp.where(l == 0.0, 1.0, l)).astype(q_c.dtype)
