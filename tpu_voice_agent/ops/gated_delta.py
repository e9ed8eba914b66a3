"""Gated delta rule (Gated DeltaNet, arXiv:2412.06464) over per-slot state
planes, Pallas TPU.

For a row's positions t = 0..T-1, H heads, a head's state S (d_k, d_v)
float32, its log-decay g_t <= 0 and its write strength beta_t (in (0, 2)
with the negative-eigenvalue range of arXiv:2411.12537):

    S' = exp(g_t) S_{t-1}
    u  = beta_t (v_t - S'^T k_t)         the write READS the state
    S_t = S' + k_t (x) u                 o_t = S_t^T q_t

Unlike ``ssd_scan``'s recurrence (a decay plus a write that is independent
of the state: a cumulative sum), the positions of a block are a triangular
system. This kernel WALKS a row's real positions one at a time on the VPU, in
float32 — exact, the twin's arithmetic but for the order of a 96-term sum —
and never the padding: a 1 + W block holds ~1.3 real positions a row, and a
state is 2.2 MB, so the walk hides behind the state's own DMA (5.4 us a row
a layer at 819 GB/s). The chunked WY / UT form would put 16 positions through
the MXU with each head's 96 x 192 state as the stationary operand: ~15 us a
row a layer of weight loads for the one or two positions a decode row has.

STATE LAYOUT. ``state`` is the model's STACKED (layers, slots, H / hp, d_k,
hp * d_v) float32 planes: ``hp`` heads stand SIDE BY SIDE on the lanes
(``heads_abreast``: 2 where d_v is not a lane multiple). At the published
d_v = 192 a head alone would pad to 256 lanes in HBM and in VMEM (x 1.33
bytes moved and held); two are 384 = three whole lane tiles, and d_k = 96 is
twelve sublane tiles: the plane is DENSE. ``heads_of`` / ``planes_of`` move
between this and (H, d_k, d_v).

A row's state is ``state[layer, sidx[b]]``, picked by the BlockSpec's index
map from the scalar prefetch and written back IN PLACE
(``input_output_aliases``): a forward moves each LIVE state across HBM once
in and once out whatever T is. T > 16 (an admission's suffix, the prefix's
chunks) is walked in grid steps of 16 positions, the state resident in VMEM
across them. The XLA twin is a ``lax.scan`` over T and the specification.

MASKING is the caller's, and it is exact: a position past ``n_real`` has
beta = 0 and g = 0, so S is bit-unchanged in the twin; the kernel does not
visit it. Its output is 0 in both. A row whose positions are ALL masked
(``n_real`` 0: idle, a bucket's padding) is SKIPPED as ``ssd_scan`` skips it:
it names the state block of the nearest live row — same block index, no
fetch and no write-back of its own —, its state is not moved and is
bit-equal. (With no live row at all row 0 stands in as live; it has no real
position, so its state is rewritten as it was.)

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
F32 = jnp.float32
_LANES = 128


def heads_abreast(n_heads: int, d_v: int) -> int:
    """Heads side by side on a plane's lanes: 2 where one head's d_v is not a
    whole number of lane tiles and the heads pair up, else 1."""
    return 2 if d_v % _LANES and n_heads % 2 == 0 else 1


def plane_shape(n_heads: int, d_k: int, d_v: int) -> tuple[int, int, int]:
    """A slot's state of one layer, as the planes hold it."""
    hp = heads_abreast(n_heads, d_v)
    return (n_heads // hp, d_k, hp * d_v)


def heads_of(planes: jax.Array, d_v: int) -> jax.Array:
    """(..., H / hp, d_k, hp * d_v) -> (..., H, d_k, d_v)."""
    *lead, G, dk, w = planes.shape
    hp = w // d_v
    x = planes.reshape(*lead, G, dk, hp, d_v)
    return jnp.moveaxis(x, -2, -3).reshape(*lead, G * hp, dk, d_v)


def planes_of(heads: jax.Array, hp: int) -> jax.Array:
    """(..., H, d_k, d_v) -> (..., H / hp, d_k, hp * d_v)."""
    *lead, H, dk, dv = heads.shape
    x = heads.reshape(*lead, H // hp, hp, dk, dv)
    return jnp.moveaxis(x, -3, -2).reshape(*lead, H // hp, dk, hp * dv)


def _gdn_kernel(sc_ref, q_ref, k_ref, v_ref, gb_ref, si_ref, o_ref, so_ref, *,
                Tc: int, B: int, G: int, hp: int, dv: int):
    r, c = pl.program_id(0), pl.program_id(1)
    live = sc_ref[B + 1 + r] > 0
    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _row():
        @pl.when(c == 0)
        def _load():
            so_ref[...] = si_ref[...]

        lane = jax.lax.broadcasted_iota(jnp.int32, (1, hp * dv), 1)

        def spread(a, p):
            """(rows, H) -> (rows, hp * d_v): head p * hp + j's column on ITS d_v lanes."""
            x = a[:, p * hp:p * hp + 1]
            for j in range(1, hp):
                x = jnp.where(lane >= j * dv, a[:, p * hp + j:p * hp + j + 1], x)
            return x

        def position(t, carry):
            qt, kt = q_ref[0, t], k_ref[0, t]  # (d_k, H)
            gb, vt = gb_ref[0, t], v_ref[0, t]  # (2, H): exp(g), beta; (G, hp * d_v)
            for p in range(G):
                kx, qx, gbx = spread(kt, p), spread(qt, p), spread(gb, p)
                s = so_ref[0, 0, p] * gbx[0:1]
                u = gbx[1:2] * (vt[p:p + 1] - jnp.sum(s * kx, axis=0, keepdims=True))
                s = s + kx * u
                so_ref[0, 0, p] = s
                o_ref[0, t, p:p + 1, :] = jnp.sum(s * qx, axis=0, keepdims=True)
            return carry

        n = jnp.clip(sc_ref[2 * B + 1 + r] - c * Tc, 0, Tc)
        jax.lax.fori_loop(0, n, position, 0)


# analyze: ok[jit-sentinel] -- kernel wrapper traced inline by the watched engine loops, never a serving dispatch entry point
@functools.partial(jax.jit, static_argnames=("impl", "interpret"))
def gated_delta_scan(state, sidx, layer, q, k, v, g, beta, n_real, impl: str = "pallas", *,
                     interpret: bool | None = None):
    """Advance the live rows' states of ``layer`` over their real positions.

    ``state`` (layers, slots, H / hp, d_k, hp * d_v) float32 (``plane_shape``);
    ``sidx`` (B,) DISTINCT slots; ``layer`` scalar int32; ``q`` ``k`` (B, T, H,
    d_k) — normalised and scaled by the caller —, ``v`` (B, T, H, d_v); ``g``
    ``beta`` (B, T, H) float32; ``n_real`` (B,): row b's real positions are
    t < n_real[b] (the twin masks g and beta past them, the kernel does not
    visit them: a caller's own mask changes nothing). ``impl``: ``"pallas"`` |
    ``"xla"`` (the twin).
    -> (o (B, T, H, d_v) float32, 0 past ``n_real``; the planes advanced)."""
    if impl != "pallas":
        return gated_delta_scan_reference(state, sidx, layer, q, k, v, g, beta, n_real)
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    G, hp = state.shape[2], state.shape[4] // dv
    interpret = interpret if interpret is not None else on_cpu()
    Tc = min(T, _T_CHUNK)
    pad = -T % Tc
    n_real = jnp.clip(n_real.astype(jnp.int32), 0, T)
    decay = jnp.exp(g)
    # a position's operands stand on LEADING axes (the walk indexes them by t):
    # q and k with the heads on the lanes, v a row a plane
    by_lane = lambda a: jnp.swapaxes(a.astype(F32), 2, 3)  # (B, T, d_k, H)
    ops = [by_lane(q), by_lane(k), v.astype(F32).reshape(B, T, G, hp * dv),
           jnp.stack([decay, beta], axis=2).astype(F32)]  # (B, T, 2, H)
    if pad:  # never visited: the walk ends at n_real
        ops = [jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))) for a in ops]
    nc = (T + pad) // Tc

    live = n_real > 0
    live = live.at[0].set(live[0] | ~jnp.any(live))
    # an idle row names the block of the live row before it, or the first live one
    rows = jnp.arange(B, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(live, rows, -1))
    src = jnp.where(before >= 0, before, jnp.argmax(live).astype(jnp.int32))
    sc = jnp.concatenate([sidx.astype(jnp.int32)[src], jnp.reshape(layer, (1,)).astype(jnp.int32),
                          live.astype(jnp.int32), n_real])

    seq = lambda *tile: pl.BlockSpec((1, Tc, *tile), lambda r, t, sc: (r, t, 0, 0))
    plane = pl.BlockSpec((1, 1, G, dk, hp * dv), lambda r, t, sc: (sc[B], sc[r], 0, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_gdn_kernel, Tc=Tc, B=B, G=G, hp=hp, dv=dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, nc),
            in_specs=[seq(dk, H), seq(dk, H), seq(G, hp * dv), seq(2, H), plane],
            out_specs=[seq(G, hp * dv), plane],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, nc * Tc, G, hp * dv), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={5: 1},  # the planes: updated where live rows point
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # a state block in and out, double-buffered, beside the positions' tiles
            vmem_limit_bytes=max(32 << 20, 6 * G * dk * hp * dv * 4)),
        interpret=interpret,
        name="gated_delta_scan",
    )(sc, *ops, state)
    return o[:, :T].reshape(B, T, H, dv), state


def gated_delta_scan_reference(state, sidx, layer, q, k, v, g, beta, n_real):
    """Pure-jnp twin (the XLA path off the TPU) and the specification: a
    ``lax.scan`` over T, the recurrence as it is written, float32 at
    ``highest``. A position past ``n_real`` leaves S bit-unchanged (beta = 0,
    g = 0) and reads 0."""
    B, T, H, _ = q.shape
    dv = v.shape[-1]
    hp = state.shape[4] // dv
    real = (jnp.arange(T)[None, :] < n_real[:, None])[..., None]
    g, beta = jnp.where(real, g.astype(F32), 0.0), jnp.where(real, beta.astype(F32), 0.0)
    s0 = heads_of(state[layer, sidx], dv)  # (B, H, d_k, d_v)

    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp  # (B, H, d_k) x 2, (B, H, d_v), (B, H) x 2
        s = jnp.exp(g_t)[..., None, None] * s
        u = b_t[..., None] * (v_t - jnp.sum(s * k_t[..., None], axis=-2))
        s = s + k_t[..., None] * u[..., None, :]
        return s, jnp.sum(s * q_t[..., None], axis=-2)

    seq = tuple(jnp.swapaxes(a.astype(F32), 0, 1) for a in (q, k, v, g, beta))
    s, o = jax.lax.scan(step, s0, seq)
    o = jnp.where(real[..., None], jnp.swapaxes(o, 0, 1), 0.0)
    return o, state.at[layer, sidx].set(planes_of(s, hp))
