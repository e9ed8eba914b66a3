"""Grouped matmul: expert-sorted rows × per-group weight, Pallas TPU.

The MoE dispatch (round-2 VERDICT weak #5; decode and int8 since PR 28):
drop-free dense-dispatch routing turns expert choice into (T, E, C) one-hot
einsums — jit-friendly, but the expert FFN then burns FLOPs ∝ E (every
expert's matmul runs over the full capacity C == T). Here the caller GROUPS
the rows by expert (``models.llama._moe_ffn_grouped``; static shapes), each
expert's run padded to a row-tile multiple, and one kernel walks the row
tiles with the expert id in scalar prefetch — the BlockSpec index map picks
the expert's weight plane per tile (the same indirection trick as
paged_attention's block tables). FLOPs become ∝ T·K plus at most one tile
of padding per expert.

Weights are a raw (E, d, f) array or the served int8 leaf ``{"q": (E, d, f)
int8, "s": (E, 1, f) f32}`` — or, with ``layer``, the model's STACKED
(L, E, d, f) leaf and the layer's index: the index rides the scalar prefetch
and the BlockSpec picks the layer's plane, because slicing ``w[layer]`` for
a per-layer operand makes XLA copy the layer's whole expert tensor (134 MB
at OLMoE's widths, three times a layer: 0.30 s of a 2 s trace before this,
PERF.md section 6, PR 28 — the lesson ``paged_attention`` already holds
for the stacked KV pool). The int8 plane goes to the kernel AS int8: a
weight TILE is converted to bf16 in VMEM, multiplied bf16 × bf16 into the
float32 accumulator, and the per-output-channel scale multiplies the output
tile (``(x @ q) * s == x @ (q * s)``, the identity ``models.llama._qe``
uses). Nothing the size of the stacked weights is ever written to HBM.

Tiling. At decode the weights bound the kernel (36 rows an expert at
OLMoE's shapes), so a weight plane must cross HBM once per EXPERT, not once
per row tile: where a whole (d, f) plane fits the VMEM budget the grid is
one step a row tile and the plane's block index repeats over an expert's
consecutive tiles, which Pallas does not fetch again. A plane too large for
that (Mixtral's 4096 × 14336) is walked in (tk, tn) tiles with a float32
accumulator across k (Command A+'s 4096 × 4096, 16 MiB: (4096, 512) tiles,
eight column steps a row tile; PR 34 ran it on a chip first). Row tiles past
``n_tiles`` (the static row bound is one tile an expert more than the routing
needs — and on a chip that holds a SHARE of the experts, where most
assignments fall elsewhere, several times the tiles that hold rows) are
skipped: on the whole-plane path no compute, and — their block index being
the last real tile's — no weight fetch; on the tiled path the grid's row axis
ENDS at ``n_tiles`` and lies inside the column tiles', so a skipped tile costs
no step and a busy expert's plane still crosses HBM once.

Like every kernel in ops/, a pure-jnp reference twin and interpret=True on
CPU keep it testable without a chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import on_cpu

# one weight block as stored (int8: 2 MiB for OLMoE's 2048 x 1024); it is
# double-buffered and converted to bf16 beside the activations' tiles
_PLANE_BYTES = 4 << 20
_VMEM_LIMIT = 48 << 20  # of v5e's 128 MiB; the default scoped limit is 16


def _pick_tile(n: int, cap: int) -> int:
    """Largest power-of-two divisor of n, at most cap."""
    t = 1
    while t * 2 <= cap and n % (t * 2) == 0:
        t *= 2
    return t


def plane_tiles(d: int, f: int, itemsize: int) -> tuple[int, int]:
    """(tk, tn): the whole (d, f) plane where it fits ``_PLANE_BYTES``, else
    the largest power-of-two tiles (lane dimension first) that do."""
    if d * f * itemsize <= _PLANE_BYTES:
        return d, f
    tn = _pick_tile(f, 512)
    return _pick_tile(d, max(128, _PLANE_BYTES // (tn * itemsize))), tn


def _gmm_kernel(sc_ref, x_ref, w_ref, *rest, scaled: bool, m_axis: int = 0):
    s_ref = rest[0] if scaled else None
    o_ref, acc_ref = rest[-2], rest[-1]
    m, k = pl.program_id(m_axis), pl.program_id(2)

    @pl.when(m < sc_ref[sc_ref.shape[0] - 2])  # a tile the routing filled
    def _tile():
        @pl.when(k == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], w_ref[0, 0].astype(x_ref.dtype),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )

        @pl.when(k == pl.num_programs(2) - 1)
        def _finish():
            acc = acc_ref[...]
            if scaled:
                acc = acc * s_ref[0, 0]
            o_ref[...] = acc.astype(o_ref.dtype)


# analyze: ok[jit-sentinel] -- kernel wrapper traced inline by the watched engine/stt loops, never a serving dispatch entry point
@functools.partial(jax.jit, static_argnames=("tm", "tn", "tk", "interpret"))
def grouped_matmul(
    x: jax.Array,  # (M, d) rows, expert-sorted and tile-padded
    w,  # (E, d, f) stacked expert weights, or {"q": int8 (E, d, f), "s": f32 (E, 1, f)};
    # with ``layer``: the same with a leading layer axis, (L, E, d, f) / (L, E, 1, f)
    tile_expert: jax.Array,  # (M // tm,) int32 expert id per row tile
    n_tiles: jax.Array | None = None,  # () int32: row tiles that hold rows (default: all)
    layer: jax.Array | None = None,  # () int32 index into w's leading layer axis
    *,
    tm: int | None = None,
    tn: int | None = None,
    tk: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """out[i] = x[i] @ w[tile_expert[i // tm]]  — (M, f).

    Every row tile belongs to exactly ONE expert (the caller pads each
    expert's run to a tile multiple, and gives tiles past ``n_tiles`` the
    last real tile's expert). Rows of skipped tiles are left unwritten.
    """
    scaled = isinstance(w, dict)
    q, s = (w["q"], w["s"]) if scaled else (w, None)
    if layer is None:  # one layer's weights: a leading axis of one
        q, s, layer = q[None], None if s is None else s[None], jnp.int32(0)
    M, d = x.shape
    _, _, d2, f = q.shape
    assert d == d2, (d, d2)
    tm = tm or _pick_tile(M, 128)
    ptk, ptn = plane_tiles(d, f, q.dtype.itemsize)
    tn, tk = tn or ptn, tk or ptk
    assert M % tm == 0 and f % tn == 0 and d % tk == 0, (M, f, d, tm, tn, tk)
    assert tile_expert.shape == (M // tm,)
    interpret = interpret if interpret is not None else on_cpu()
    if n_tiles is None:
        n_tiles = jnp.int32(M // tm)
    # scalar prefetch: the tiles' experts, how many tiles are real, the layer
    nt = M // tm
    sc = jnp.concatenate([tile_expert.astype(jnp.int32), jnp.reshape(n_tiles, (1,)).astype(jnp.int32),
                          jnp.reshape(layer, (1,)).astype(jnp.int32)])

    x_map = lambda m, n, k, sc: (m, k)
    w_map = lambda m, n, k, sc: (sc[nt + 1], sc[m], k, n)
    s_map = lambda m, n, k, sc: (sc[nt + 1], sc[m], 0, n)
    o_map = lambda m, n, k, sc: (m, n)
    tiled = (tk, tn) != (d, f)
    if not tiled:
        # a whole plane a step: a skipped tile names the last real tile's
        # expert, so its block index repeats and nothing is fetched
        grid = (M // tm, f // tn, d // tk)
    else:
        # a plane walked in (tk, tn) tiles: the COLUMN tiles outermost, so
        # that an expert's consecutive row tiles meet the same weight tile
        # and it is fetched once (row tiles outermost read a plane again for
        # every row tile of a busy expert: 1.6 times the planes at Command
        # A+'s routing, my chip run, PR 34), and the row axis ENDS at the
        # last tile that holds rows — a skipped one would walk the column
        # tiles for nothing, 2 MiB a step
        rows = jnp.maximum(jnp.reshape(n_tiles, ()).astype(jnp.int32), 1)
        grid = (f // tn, rows, d // tk)
        x_map, w_map, s_map, o_map = (
            (lambda n, m, k, sc, fn=fn: fn(m, n, k, sc)) for fn in (x_map, w_map, s_map, o_map))
    in_specs = [pl.BlockSpec((tm, tk), x_map), pl.BlockSpec((1, 1, tk, tn), w_map)]
    operands = [x, q]
    if scaled:
        in_specs.append(pl.BlockSpec((1, 1, 1, tn), s_map))
        operands.append(s.astype(jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tm, tn), o_map),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, scaled=scaled, m_axis=1 if tiled else 0),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, f), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="grouped_matmul",
    )(sc, *operands)


def grouped_matmul_reference(x, w, tile_expert, tm: int) -> jax.Array:
    """Pure-jnp twin: per-row expert gather + batched matmul, the int8 leaf
    resolved as the kernel resolves it (bf16 plane, scale on the output)."""
    row_expert = jnp.repeat(tile_expert, tm)  # (M,)
    if isinstance(w, dict):
        out = jnp.einsum("md,mdf->mf", x, w["q"][row_expert].astype(x.dtype),
                         preferred_element_type=jnp.float32)
        return (out * w["s"][row_expert][:, 0, :].astype(jnp.float32)).astype(x.dtype)
    return jnp.einsum(
        "md,mdf->mf", x.astype(jnp.float32), w[row_expert].astype(jnp.float32)
    ).astype(x.dtype)
