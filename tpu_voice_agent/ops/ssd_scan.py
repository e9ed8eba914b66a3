"""Mamba-2 (SSD) scan over per-slot state planes, Pallas TPU.

For a row's positions t = 0..T-1, with ONE scalar transition a head (H heads
of P channels, G groups of H / G heads that share B and C, N states):

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        (P, N) a head
    y_t = S_t C_t                                         (P,)   a head

``state`` is the model's STACKED (layers, slots, H, P, N) float32 planes — N
on the lanes, so a head's 64 x 128 matrix is eight whole vregs and the plane
is dense in HBM. A row's state is ``state[layer, sidx[b]]``, picked by the
BlockSpec's index map from the scalar prefetch and written back IN PLACE
(``input_output_aliases``): a forward moves each LIVE state across HBM once
in and once out whatever T is. The XLA twin is a ``lax.scan`` over T, which
round-trips the state once a position.

MATMUL-SHAPED over a chunk of Tc <= 16 positions, a GROUP a grid step (its
16 heads share B and C, so their states stand as one (H/G * P, N) operand).
With l_t = sum_{s<=t} dt_s A (a head; differences l_t - l_s <= 0 alone are
ever exponentiated, so nothing overflows):

    y_t   = exp(l_t) C_t S_0^T  +  sum_{s<=t} (C_t . B_s) exp(l_t - l_s) dt_s x_s
    S_Tc  = exp(l_Tc) S_0       +  sum_s exp(l_Tc - l_s) dt_s x_s (x) B_s

The first term and the state's update are ONE matmul each a group on the
MXU (C against the group's states, contracting N; the weighted inputs
against B, contracting the positions), float32 at ``highest``: the state is
what a request carries for its whole life, and a bf16 pass there is the
fault the benchmark's comparison plants. The sum over earlier positions of
the chunk is elementwise work on (Tc, H/G * P) tiles, one pass a source
position. The caller's ``dt`` is per head; it is spread over a head's P
lanes (with x folded in) by XLA before the call.

MASKING is the caller's, and it is exact: a position whose ``dt`` is 0 has
l unchanged and adds 0. A row whose positions are ALL masked (``n_real`` 0:
idle, a bucket's padding) is SKIPPED: the grid walks the rows innermost and
such a row names the state block of the nearest live row — same block
index, no fetch and no write-back of its own — and computes nothing; its
state is not moved and is bit-equal, its ``y`` is 0. (With no live row at
all row 0 stands in as live; its dt is 0, so its state is rewritten as it
was.)

Like every kernel in ops/: a pure-jnp reference twin, interpret=True on the
CPU."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import on_cpu

_T_CHUNK = 16  # positions a grid step takes
_HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _ssd_kernel(sc_ref, xdt_ref, l_ref, b_ref, c_ref, dec_ref, si_ref, y_ref, so_ref, *,
                Tc: int, hg: int, P: int, B: int):
    r, c = pl.program_id(1), pl.program_id(2)
    live = sc_ref[B + 1 + r] > 0

    @pl.when(jnp.logical_not(live))
    def _idle():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(live)
    def _row():
        @pl.when(c == 0)
        def _load():
            so_ref[...] = si_ref[...]

        s0 = so_ref[0, 0]  # (hg * P, N)
        xdt, l = xdt_ref[0], l_ref[0]  # (Tc, hg * P)
        bm, cm = b_ref[0, 0], c_ref[0, 0]  # (Tc, N)
        # what the state before the chunk gives every position
        y = jnp.exp(l) * jax.lax.dot_general(cm, s0, (((1,), (1,)), ((), ())),
                                             precision=_HIGHEST, preferred_element_type=F32)
        cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                                 precision=_HIGHEST, preferred_element_type=F32)  # (Tc, Tc)
        t_idx = jax.lax.broadcasted_iota(jnp.int32, (Tc, 1), 0)
        for s in range(Tc):  # what position s gives the positions from it on
            w = jnp.where(t_idx >= s, jnp.exp(l - l[s:s + 1]), 0.0)
            y = y + (w * cb[:, s:s + 1]) * xdt[s:s + 1]
        y_ref[0] = y
        xw = xdt * jnp.exp(l[Tc - 1:Tc] - l)
        upd = jax.lax.dot_general(xw, bm, (((0,), (0,)), ((), ())),
                                  precision=_HIGHEST, preferred_element_type=F32)  # (hg * P, N)
        for h in range(hg):
            rows = slice(h * P, (h + 1) * P)
            so_ref[0, 0, rows] = s0[rows] * dec_ref[0, 0, h:h + 1] + upd[rows]


# analyze: ok[jit-sentinel] -- kernel wrapper traced inline by the watched engine loops, never a serving dispatch entry point
@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_scan(
    x: jax.Array,  # (B, T, H, P) float32
    dt: jax.Array,  # (B, T, H) float32, softplus'd; 0 where the position is not real
    a: jax.Array,  # (H,) float32: -exp(A_log)
    b: jax.Array,  # (B, T, G, N) float32
    c: jax.Array,  # (B, T, G, N) float32
    state: jax.Array,  # (layers, slots, H, P, N) float32
    sidx: jax.Array,  # (B,) int32 DISTINCT slots
    layer: jax.Array,  # scalar int32
    n_real: jax.Array,  # (B,) int32: a row with none is skipped (its dt is 0 everywhere)
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """-> (y (B, T, H, P) float32, the state planes with the live rows'
    ``sidx`` of ``layer`` advanced)."""
    B, T, H, P = x.shape
    G, N = b.shape[2:]
    hg = H // G
    interpret = interpret if interpret is not None else on_cpu()
    Tc = min(-(-T // 8) * 8, _T_CHUNK)
    pad = -T % Tc
    if pad:  # dt = 0: the padding leaves the state as it is
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    nc = (T + pad) // Tc
    # l restarts every chunk; the chunk's whole decay a head, on the lanes
    la = (dt * a).reshape(B, nc, Tc, H)
    l = jnp.cumsum(la, axis=2)
    dec = jnp.broadcast_to(jnp.exp(l[:, :, -1])[..., None], (B, nc, H, N))
    lanes = lambda v: jnp.repeat(v.reshape(B, nc * Tc, H), P, axis=2)  # (B, T, H * P)
    xdt = (x * dt[..., None]).reshape(B, nc * Tc, H * P)
    by_group = lambda v: jnp.swapaxes(v, 1, 2)  # (B, G, T, N)

    live = n_real > 0
    live = live.at[0].set(live[0] | ~jnp.any(live))
    # an idle row names the block of the live row before it, or the first live one
    rows = jnp.arange(B, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(live, rows, -1))
    src = jnp.where(before >= 0, before, jnp.argmax(live).astype(jnp.int32))
    sc = jnp.concatenate([sidx.astype(jnp.int32)[src], jnp.reshape(layer, (1,)).astype(jnp.int32),
                          live.astype(jnp.int32)])

    w = hg * P
    seq = pl.BlockSpec((1, Tc, w), lambda g, r, t, sc: (r, t, g))
    grp = pl.BlockSpec((1, 1, Tc, N), lambda g, r, t, sc: (r, g, t, 0))
    plane = pl.BlockSpec((1, 1, w, N), lambda g, r, t, sc: (sc[B], sc[r], g, 0))
    planes = state.reshape(*state.shape[:2], H * P, N)
    y, planes = pl.pallas_call(
        functools.partial(_ssd_kernel, Tc=Tc, hg=hg, P=P, B=B),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(G, B, nc),
            in_specs=[seq, seq, grp, grp,
                      pl.BlockSpec((1, 1, hg, N), lambda g, r, t, sc: (r, t, g, 0)), plane],
            out_specs=[seq, plane],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, nc * Tc, H * P), F32),
                   jax.ShapeDtypeStruct(planes.shape, planes.dtype)],
        input_output_aliases={6: 1},  # the planes: updated where live rows point
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssd_scan",
    )(sc, xdt, lanes(l), by_group(b), by_group(c), dec, planes)
    return y[:, :T].reshape(B, T, H, P), planes.reshape(state.shape)


def ssd_scan_reference(x, dt, a, b, c, state, sidx, layer, n_real=None):
    """Pure-jnp twin (the XLA path off the TPU): a ``lax.scan`` over T, the
    recurrence as it is written. ``n_real`` is the kernel's; masking is in
    ``dt`` already."""
    del n_real
    hg = x.shape[2] // b.shape[2]
    s0 = state[layer, sidx]  # (B, H, P, N)

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp  # (B, H, P), (B, H), (B, G, N), (B, G, N)
        b_h, c_h = jnp.repeat(b_t, hg, axis=1), jnp.repeat(c_t, hg, axis=1)  # (B, H, N)
        s = (jnp.exp(dt_t * a)[..., None, None] * s
             + (dt_t[..., None] * x_t)[..., None] * b_h[:, :, None, :])
        return s, jnp.sum(s * c_h[:, :, None, :], axis=-1)

    s, ys = jax.lax.scan(step, s0, tuple(jnp.swapaxes(v, 0, 1) for v in (x, dt, b, c)))
    return jnp.swapaxes(ys, 0, 1), state.at[layer, sidx].set(s)
