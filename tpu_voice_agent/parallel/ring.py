"""Sequence/context parallelism: ring attention + Ulysses (all-to-all).

The reference has no sequence-length concept at all (SURVEY.md §5: its
"long context" is a rolling dict in the voice service). Here long-session
planner contexts and long audio-encoder sequences shard over an ``sp`` mesh
axis:

- ``ring_attention``: blockwise attention with the K/V shards rotating
  around the ring via ``ppermute`` (one ICI hop per step) and online-softmax
  merging — sequence length scales with the number of devices while each
  step's compute overlaps the next shard's transfer.
- ``ulysses_attention``: ``all_to_all`` re-shards sequence-sharding into
  head-sharding, runs exact local attention per head group, and re-shards
  back. Cheaper for moderate sequence lengths when heads divide the axis.

Both are exact (they match full attention to numerical tolerance) and are
expressed with ``shard_map`` so XLA schedules the collectives on ICI.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e30


def sp_mesh(sp: int, devices: list | None = None) -> Mesh:
    """1-D sequence-parallel mesh."""
    devices = devices if devices is not None else jax.devices()
    if sp > len(devices):
        raise ValueError(f"sp={sp} needs {sp} devices, have {len(devices)}")
    return Mesh(np.array(devices[:sp]), ("sp",))


def _block_attn(q, k, v, q_off, k_off, causal: bool, scale: float):
    """Unnormalized blockwise attention for online-softmax merging.

    q (B, Tq, nq, hd), k/v (B, Tk, nkv, hd); offsets are the blocks' global
    sequence starts. Returns acc (B, Tq, nq, hd) f32, m/l (B, Tq, nq) f32.
    """
    B, Tq, nq, hd = q.shape
    Tk, nkv = k.shape[1], k.shape[2]
    group = nq // nkv
    qg = q.reshape(B, Tq, nkv, group, hd)
    s = jnp.einsum("btkgh,bskh->bkgts", qg, k, preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = q_off + jnp.arange(Tq)
        k_pos = k_off + jnp.arange(Tk)
        mask = q_pos[:, None] >= k_pos[None, :]  # (Tq, Tk)
        s = jnp.where(mask[None, None, None, :, :], s, _NEG_INF)
    m = jnp.max(s, axis=-1)  # (B, nkv, group, Tq)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkgts,bskh->btkgh", p, v.astype(jnp.float32))
    to_btn = lambda x: x.transpose(0, 3, 1, 2).reshape(B, Tq, nq)
    return acc.reshape(B, Tq, nq, hd), to_btn(m), to_btn(l)


# analyze: ok[jit-sentinel] -- kernel wrapper traced inline by the watched engine/stt loops, never a serving dispatch entry point
@partial(jax.jit, static_argnames=("mesh", "causal", "scale"))
def ring_attention(
    q: jax.Array,  # (B, T, nq, hd) — T shards over mesh axis "sp"
    k: jax.Array,  # (B, T, nkv, hd)
    v: jax.Array,  # (B, T, nkv, hd)
    mesh: Mesh,
    *,
    causal: bool = True,
    scale: float | None = None,
) -> jax.Array:
    """Exact attention over a sequence sharded on mesh axis "sp"."""
    hd = q.shape[-1]
    scale = scale if scale is not None else hd**-0.5
    n = mesh.shape["sp"]
    spec = P(None, "sp", None, None)

    def local(q, k, v):
        # q/k/v here are the per-device shards (B, T/n, H, hd)
        r = jax.lax.axis_index("sp")
        chunk = q.shape[1]
        q_off = r * chunk
        qf = q.astype(jnp.float32)

        acc0, m0, l0 = _block_attn(qf, k, v, q_off, r * chunk, causal, scale)

        def step(s, carry):
            k_cur, v_cur, acc, m, l = carry
            # rotate: after s hops device r holds block (r - s) mod n
            perm = [(i, (i + 1) % n) for i in range(n)]
            k_cur = jax.lax.ppermute(k_cur, "sp", perm)
            v_cur = jax.lax.ppermute(v_cur, "sp", perm)
            k_off = ((r - s) % n) * chunk
            acc_i, m_i, l_i = _block_attn(qf, k_cur, v_cur, q_off, k_off, causal, scale)
            m_new = jnp.maximum(m, m_i)
            a = jnp.exp(m - m_new)[..., None]
            b = jnp.exp(m_i - m_new)[..., None]
            acc = acc * a + acc_i * b
            l = l * a[..., 0] + l_i * b[..., 0]
            return k_cur, v_cur, acc, m_new, l

        _, _, acc, _, l = jax.lax.fori_loop(1, n, step, (k, v, acc0, m0, l0))
        return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)

    return shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)


# analyze: ok[jit-sentinel] -- kernel wrapper traced inline by the watched engine/stt loops, never a serving dispatch entry point
@partial(jax.jit, static_argnames=("mesh", "causal", "scale"))
def ulysses_attention(
    q: jax.Array,  # (B, T, nq, hd) — T shards over "sp"; nq % sp == 0
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    causal: bool = True,
    scale: float | None = None,
) -> jax.Array:
    """All-to-all head-parallel attention (Ulysses layout): re-shard
    sequence->heads, exact local attention, re-shard back. Requires both head
    counts divisible by the sp axis."""
    hd = q.shape[-1]
    scale = scale if scale is not None else hd**-0.5
    n = mesh.shape["sp"]
    nq, nkv = q.shape[2], k.shape[2]
    if nq % n or nkv % n:
        raise ValueError(f"ulysses needs nq ({nq}) and nkv ({nkv}) divisible by sp ({n})")
    spec = P(None, "sp", None, None)

    def local(q, k, v):
        # shards (B, T/n, H, hd) -> gather sequence, scatter heads
        a2a = lambda x: jax.lax.all_to_all(x, "sp", split_axis=2, concat_axis=1, tiled=True)
        qh, kh, vh = a2a(q), a2a(k), a2a(v)  # (B, T, H/n, hd)
        B, T, nqh, _ = qh.shape
        group = nqh // kh.shape[2]
        qg = qh.reshape(B, T, kh.shape[2], group, hd)
        s = jnp.einsum("btkgh,bskh->bkgts", qg, kh, preferred_element_type=jnp.float32) * scale
        if causal:
            mask = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
            s = jnp.where(mask[None, None, None], s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgts,bskh->btkgh", p.astype(vh.dtype), vh,
                       preferred_element_type=jnp.float32)
        o = o.reshape(B, T, nqh, hd).astype(q.dtype)
        # scatter sequence back, gather heads
        return jax.lax.all_to_all(o, "sp", split_axis=1, concat_axis=2, tiled=True)

    return shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)
