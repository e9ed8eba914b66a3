"""Fused grammar-mask + argmax over the vocab (Pallas, TPU).

The greedy half of grammar-constrained sampling: for each sequence, gather
its FSM state's row of the (n_states, V) mask table and argmax the masked
logits — without ever materializing the masked logits in HBM. The per-row
FSM state rides as a scalar-prefetch operand so the *BlockSpec index map*
does the gather: each grid cell streams the mask tile for exactly the state
its row is in.

TPU tiling: vocab rows are viewed as (V/128, 128) so every block is a
(SUB, 128) tile (f32-legal 8x128 multiples) — a flat (1, V) block would
violate Mosaic's sublane constraint.

This replaces the XLA path ``argmax(where(mask_table[state], logits, -inf))``
(serve/engine.py ``_mask_sample_advance``) for greedy decoding; temperature
sampling stays in XLA (``jax.random.categorical``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import on_cpu

_LANE = 128
_SUB = 8
_TILE = _SUB * _LANE  # vocab elements per grid cell


def _argmax_kernel(
    state_ref,  # scalar prefetch (B,) int32
    logits_ref,  # (1, SUB, 128) f32 tile of row b
    mask_ref,  # (1, SUB, 128) bool tile of row state[b]
    idx_out_ref,  # SMEM (B,) int32 — written at this grid row's slot
    best_val_ref,  # SMEM (1,) f32
    best_idx_ref,  # SMEM (1,) int32
):
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        best_val_ref[0] = -jnp.inf
        best_idx_ref[0] = 0

    s = jnp.where(mask_ref[0], logits_ref[0].astype(jnp.float32), -1e30)  # (SUB, 128)
    sub = jax.lax.broadcasted_iota(jnp.int32, (_SUB, _LANE), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (_SUB, _LANE), 1)
    idx = j * _TILE + sub * _LANE + lane
    tile_max = jnp.max(s)
    # first index achieving the max (argmax tie-break parity with jnp.argmax)
    tile_arg = jnp.min(jnp.where(s == tile_max, idx, jnp.iinfo(jnp.int32).max))

    # strict > keeps the first occurrence across tiles
    @pl.when(tile_max > best_val_ref[0])
    def _update():
        best_val_ref[0] = tile_max
        best_idx_ref[0] = tile_arg

    @pl.when(j == nj - 1)
    def _finish():
        idx_out_ref[b] = best_idx_ref[0]


# analyze: ok[jit-sentinel] -- kernel wrapper traced inline by the watched engine/stt loops, never a serving dispatch entry point
@functools.partial(jax.jit, static_argnames=("interpret",))
def masked_argmax(
    logits: jax.Array,  # (B, V) float
    fsm_state: jax.Array,  # (B,) int32
    mask_table: jax.Array,  # (n_states, V) bool
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns (B,) int32 = argmax_v(logits[b, v] where mask_table[state[b], v])."""
    B, V = logits.shape
    S = mask_table.shape[0]
    interpret = interpret if interpret is not None else on_cpu()
    pad_v = (-V) % _TILE
    if pad_v:
        logits = jnp.pad(logits, ((0, 0), (0, pad_v)), constant_values=-jnp.inf)
        mask_table = jnp.pad(mask_table, ((0, 0), (0, pad_v)))
    Vp = logits.shape[1]
    logits3 = logits.reshape(B, Vp // _LANE, _LANE)
    mask3 = mask_table.reshape(S, Vp // _LANE, _LANE)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Vp // _TILE),
        in_specs=[
            pl.BlockSpec((1, _SUB, _LANE), lambda b, j, state: (b, j, 0)),
            pl.BlockSpec((1, _SUB, _LANE), lambda b, j, state: (state[b], j, 0)),
        ],
        out_specs=pl.BlockSpec((B,), lambda b, j, state: (0,), memory_space=pltpu.SMEM),
        scratch_shapes=[
            pltpu.SMEM((1,), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        _argmax_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B,), jnp.int32),
        interpret=interpret,
        name="masked_argmax",
    )(fsm_state.astype(jnp.int32), logits3, mask3)


def sharded_masked_argmax(
    mesh,
    logits: jax.Array,  # (B, V)
    fsm_state: jax.Array,  # (B,)
    mask_table: jax.Array,  # (n_states, V) bool — replicated
    **kw,
) -> jax.Array:
    """masked_argmax over a (dp, tp) mesh via shard_map: batch over dp, the
    vocab and mask table replicated (default_rules constrains logits to
    P('dp', None)), so every device argmaxes its own rows — no collectives.
    ``mesh=None`` falls through to the plain kernel."""
    if mesh is None:
        return masked_argmax(logits, fsm_state, mask_table, **kw)
    from jax.sharding import PartitionSpec as P

    dp = mesh.shape.get("dp", 1)
    dp_ax = "dp" if (dp > 1 and logits.shape[0] % dp == 0) else None  # B=1: replicate
    fn = jax.shard_map(
        functools.partial(masked_argmax, **kw),
        mesh=mesh,
        in_specs=(P(dp_ax, None), P(dp_ax), P(None, None)),
        out_specs=P(dp_ax),
        check_vma=False,
    )
    return fn(logits, fsm_state, mask_table)


def masked_argmax_reference(
    logits: jax.Array, fsm_state: jax.Array, mask_table: jax.Array
) -> jax.Array:
    """Pure-jnp twin (the engine's original XLA path)."""
    masked = jnp.where(mask_table[fsm_state], logits, -jnp.inf)
    return jnp.argmax(masked, axis=-1).astype(jnp.int32)


# ------------------------------------------------------- fused decode tail
#
# ISSUE 12: the per-step sampling tail was mask -> argmax (the kernel
# above) -> a separate two-gather FSM advance. ``masked_argmax_advance``
# is mask + argmax + FSM advance in ONE kernel. The col_id class tiles
# stream beside the logits tiles, the kernel tracks the argmax position's
# class, and the (1, 1, C) row of the compressed transition table — fetched
# by the row's own state, the same scalar-prefetch trick as the mask tiles —
# yields the next state at finish. Nothing V-sized ever leaves the kernel.


def _argmax_advance_kernel(
    state_ref,  # scalar prefetch (B,) int32 (caller clamps >= 0)
    logits_ref,  # (1, SUB, 128) f32 tile of row b
    mask_ref,  # (1, SUB, 128) bool tile of row state[b]
    col_ref,  # (SUB, 128) int32 col_id tile (token -> class)
    trow_ref,  # (1, 1, C) int32 — row state[b] of the compressed table
    idx_out_ref,  # SMEM (B,) int32
    next_out_ref,  # SMEM (B,) int32
    best_val_ref,  # SMEM (1,) f32
    best_idx_ref,  # SMEM (1,) int32
    best_cls_ref,  # SMEM (1,) int32
):
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        best_val_ref[0] = -jnp.inf
        best_idx_ref[0] = 0
        best_cls_ref[0] = 0  # class 0 is the all-dead column: a fully
        # masked row advances to -1, exactly what the poison gate expects

    s = jnp.where(mask_ref[0], logits_ref[0].astype(jnp.float32), -1e30)
    sub = jax.lax.broadcasted_iota(jnp.int32, (_SUB, _LANE), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (_SUB, _LANE), 1)
    idx = j * _TILE + sub * _LANE + lane
    tile_max = jnp.max(s)
    tile_arg = jnp.min(jnp.where(s == tile_max, idx, jnp.iinfo(jnp.int32).max))
    # the class at the winning position (unique, so min picks exactly it)
    tile_cls = jnp.min(jnp.where(idx == tile_arg, col_ref[...],
                                 jnp.iinfo(jnp.int32).max))

    @pl.when(tile_max > best_val_ref[0])
    def _update():
        best_val_ref[0] = tile_max
        best_idx_ref[0] = tile_arg
        best_cls_ref[0] = tile_cls

    @pl.when(j == nj - 1)
    def _finish():
        idx_out_ref[b] = best_idx_ref[0]
        # Mosaic has no scalar load from VMEM at a dynamic lane index: pick
        # the class lane with an iota compare and reduce (exactly one lane
        # matches, so the sum IS that lane's value, -1 included)
        trow = trow_ref[0]  # (1, C)
        cls = jax.lax.broadcasted_iota(jnp.int32, trow.shape, 1)
        next_out_ref[b] = jnp.sum(jnp.where(cls == best_cls_ref[0], trow, 0))


# analyze: ok[jit-sentinel] -- kernel wrapper traced inline by the watched engine/stt loops, never a serving dispatch entry point
@functools.partial(jax.jit, static_argnames=("interpret",))
def masked_argmax_advance(
    logits: jax.Array,  # (B, V) float
    fsm_state: jax.Array,  # (B,) int32
    mask_table: jax.Array,  # (n_states, V) bool
    table: jax.Array,  # (n_states, C) int32 compressed transitions; -1 dead
    col_id: jax.Array,  # (V,) int32 token -> class
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Returns (tok, next_state), both (B,) int32 — tok is the masked
    argmax (``masked_argmax`` parity) and next_state equals
    ``grammar.fsm.fsm_advance(tables, state, tok)`` for live states.
    Negative (dead) states are clamped to 0; their results are garbage the
    engine's poison gate already fences (it keys on the ENTRY state)."""
    B, V = logits.shape
    S, C = table.shape
    interpret = interpret if interpret is not None else on_cpu()
    state = jnp.maximum(fsm_state.astype(jnp.int32), 0)
    pad_v = (-V) % _TILE
    if pad_v:
        logits = jnp.pad(logits, ((0, 0), (0, pad_v)), constant_values=-jnp.inf)
        mask_table = jnp.pad(mask_table, ((0, 0), (0, pad_v)))
        col_id = jnp.pad(col_id, (0, pad_v))  # class 0: the all-dead column
    pad_c = (-C) % _LANE
    if pad_c:
        table = jnp.pad(table, ((0, 0), (0, pad_c)), constant_values=-1)
    Cp = table.shape[1]
    Vp = logits.shape[1]
    logits3 = logits.reshape(B, Vp // _LANE, _LANE)
    mask3 = mask_table.reshape(S, Vp // _LANE, _LANE)
    col2 = col_id.astype(jnp.int32).reshape(Vp // _LANE, _LANE)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Vp // _TILE),
        in_specs=[
            pl.BlockSpec((1, _SUB, _LANE), lambda b, j, state: (b, j, 0)),
            pl.BlockSpec((1, _SUB, _LANE), lambda b, j, state: (state[b], j, 0)),
            pl.BlockSpec((_SUB, _LANE), lambda b, j, state: (j, 0)),
            # (S, 1, C) view: a (1, C) block of an (S, C) array breaks the
            # 8-sublane rule; as the full last two dims of a 3-D array it
            # is legal
            pl.BlockSpec((1, 1, Cp), lambda b, j, state: (state[b], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((B,), lambda b, j, state: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((B,), lambda b, j, state: (0,), memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[
            pltpu.SMEM((1,), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    tok, nxt = pl.pallas_call(
        _argmax_advance_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B,), jnp.int32),
                   jax.ShapeDtypeStruct((B,), jnp.int32)],
        interpret=interpret,
        name="masked_argmax_advance",
    )(state, logits3, mask3, col2, table.astype(jnp.int32).reshape(S, 1, Cp))
    return tok, nxt


def sharded_masked_argmax_advance(
    mesh,
    logits: jax.Array,  # (B, V)
    fsm_state: jax.Array,  # (B,)
    mask_table: jax.Array,  # (n_states, V) bool — replicated
    table: jax.Array,  # (n_states, C) int32 — replicated
    col_id: jax.Array,  # (V,) int32 — replicated
    **kw,
) -> tuple[jax.Array, jax.Array]:
    """masked_argmax_advance over a (dp, tp) mesh: batch over dp, tables
    replicated — no collectives. ``mesh=None`` falls through."""
    if mesh is None:
        return masked_argmax_advance(logits, fsm_state, mask_table, table,
                                     col_id, **kw)
    from jax.sharding import PartitionSpec as P

    dp = mesh.shape.get("dp", 1)
    dp_ax = "dp" if (dp > 1 and logits.shape[0] % dp == 0) else None
    fn = jax.shard_map(
        functools.partial(masked_argmax_advance, **kw),
        mesh=mesh,
        in_specs=(P(dp_ax, None), P(dp_ax), P(None, None), P(None, None),
                  P(None)),
        out_specs=(P(dp_ax), P(dp_ax)),
        check_vma=False,
    )
    return fn(logits, fsm_state, mask_table, table, col_id)


def masked_argmax_advance_reference(
    logits: jax.Array, fsm_state: jax.Array, mask_table: jax.Array,
    table: jax.Array, col_id: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Pure-jnp twin of ``masked_argmax_advance`` (clamped-state contract)."""
    state = jnp.maximum(fsm_state, 0)
    tok = masked_argmax_reference(logits, state, mask_table)
    return tok, table[state, col_id[tok]]
