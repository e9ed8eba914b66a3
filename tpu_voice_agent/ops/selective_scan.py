"""Selective scan (Mamba-1 recurrence) over per-slot state planes, Pallas TPU.

For a row's positions t = 0..T-1, with a diagonal transition per channel:

    s_t = exp(dt_t * A) * s_{t-1} + (dt_t * x_t) (x) B_t        (ds, di)
    y_t = sum_n s_t[n] * C_t[n]                                  (di,)

``state`` is the model's STACKED (layers, slots, ds, di) float32 planes: a
row's state is ``state[layer, sidx[b]]`` — picked by the BlockSpec's index
map from the scalar prefetch, as the paged kernels pick a pool block, and
written back IN PLACE (``input_output_aliases``), so a forward moves each
live state across HBM once in and once out whatever T is. The XLA twin is a
``lax.scan`` over T, which round-trips the state once a position.

MASKING is the caller's, and it is exact: a position whose ``dt`` is 0
multiplies the state by exp(0) = 1 and adds 0 — the state is bit-equal to
what it was. A row that is idle, a bucket's padding and a fast-forward
block's unused tail all pass dt = 0 there (``models.sambay`` zeroes it from
the row's count of real positions); their ``y`` is finite and unread.

The state's channel axis ``di`` lies on the lanes and its ``ds`` = 16
states on the sublanes, so a step is elementwise work on (ds, tile) vregs
and one sublane reduction; B_t and C_t arrive as (ds, 1) columns. T is
walked in chunks of at most 16 positions along the grid's last axis with
the state resident in the output block between them.

Like every kernel in ops/: a pure-jnp reference twin, interpret=True on the
CPU."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import on_cpu

_T_CHUNK = 16  # positions a grid step walks, unrolled


def _di_tile(di: int) -> int:
    """Channels a grid step holds: the largest multiple of 128 that divides
    ``di``, at most 1280 (5120 = 4 x 1280); a width with no such divisor
    goes whole."""
    fits = [t for t in range(128, min(di, 1280) + 1, 128) if di % t == 0]
    return max(fits, default=di)


def _scan_kernel(sidx_ref, layer_ref, x_ref, dt_ref, a_ref, b_ref, c_ref, si_ref,
                 y_ref, so_ref, *, Tc: int):
    del sidx_ref, layer_ref  # the index maps read them

    @pl.when(pl.program_id(2) == 0)
    def _load():
        so_ref[...] = si_ref[...]

    s = so_ref[0, 0]  # (ds, tile)
    a = a_ref[...]
    for t in range(Tc):
        dt = dt_ref[0, t:t + 1, :]  # (1, tile)
        s = jnp.exp(dt * a) * s + (dt * x_ref[0, t:t + 1, :]) * b_ref[0, t]
        y_ref[0, t:t + 1, :] = jnp.sum(s * c_ref[0, t], axis=0, keepdims=True)
    so_ref[0, 0] = s


# analyze: ok[jit-sentinel] -- kernel wrapper traced inline by the watched engine loops, never a serving dispatch entry point
@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan(
    x: jax.Array,  # (B, T, di) float32
    dt: jax.Array,  # (B, T, di) float32, softplus'd; 0 where the position is not real
    a_t: jax.Array,  # (ds, di) float32: -exp(A_log), transposed
    b: jax.Array,  # (B, T, ds) float32
    c: jax.Array,  # (B, T, ds) float32
    state: jax.Array,  # (layers, slots, ds, di) float32
    sidx: jax.Array,  # (B,) int32 DISTINCT slots
    layer: jax.Array,  # scalar int32
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """-> (y (B, T, di) float32, the state planes with rows ``sidx`` of
    ``layer`` advanced)."""
    B, T, di = x.shape
    ds = a_t.shape[0]
    interpret = interpret if interpret is not None else on_cpu()
    Tc = T if T <= _T_CHUNK else _T_CHUNK
    pad = -T % Tc
    if pad:  # dt = 0: the padding leaves the state as it is
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad), (0, 0))) for v in (x, dt, b, c))
    tile = _di_tile(di)
    seq = pl.BlockSpec((1, Tc, tile), lambda r, i, t, *_: (r, t, i))
    col = pl.BlockSpec((1, Tc, ds, 1), lambda r, i, t, *_: (r, t, 0, 0))
    plane = pl.BlockSpec((1, 1, ds, tile), lambda r, i, t, sidx, layer: (layer[0], sidx[r], 0, i))
    y, state = pl.pallas_call(
        functools.partial(_scan_kernel, Tc=Tc),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, di // tile, (T + pad) // Tc),
            in_specs=[seq, seq, pl.BlockSpec((ds, tile), lambda r, i, t, *_: (0, i)),
                      col, col, plane],
            out_specs=[seq, plane],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, T + pad, di), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={7: 1},  # the planes: updated where rows point
        interpret=interpret,
        name="selective_scan",
    )(sidx.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
      x, dt, a_t, b[..., None], c[..., None], state)
    return y[:, :T], state


def selective_scan_reference(x, dt, a_t, b, c, state, sidx, layer):
    """Pure-jnp twin (the XLA path off the TPU): a ``lax.scan`` over T."""
    s0 = state[layer, sidx]  # (B, ds, di)

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = jnp.exp(dt_t[:, None, :] * a_t[None]) * s + (dt_t * x_t)[:, None, :] * b_t[:, :, None]
        return s, jnp.sum(s * c_t[:, :, None], axis=1)

    s, ys = jax.lax.scan(step, s0, tuple(jnp.swapaxes(v, 0, 1) for v in (x, dt, b, c)))
    return jnp.swapaxes(ys, 0, 1), state.at[layer, sidx].set(s)
