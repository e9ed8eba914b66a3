"""``ops.ssd_scan`` (the Mamba-2 scan over per-slot state planes): the Pallas
kernel, interpreted, against its XLA twin and against the plain reference's
loop over positions; masked positions, idle rows, the stacked planes in place."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import nemotron_h_decoder as ref
from tpu_voice_agent.ops.ssd_scan import ssd_scan, ssd_scan_reference

F32 = jnp.float32
H, P, G, N, L, S = 4, 8, 2, 16, 3, 6


def case(B, T, n_real, seed=0, layer=1):
    ks = jax.random.split(jax.random.key(seed), 6)
    n_real = jnp.asarray(n_real, jnp.int32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H), F32))
    dt = jnp.where(jnp.arange(T)[None, :, None] < n_real[:, None, None], dt, 0.0)
    sidx = jnp.asarray(np.random.RandomState(seed).permutation(S)[:B], jnp.int32)
    return (jax.random.normal(ks[0], (B, T, H, P), F32), dt,
            -jnp.exp(jax.random.uniform(ks[2], (H,), F32, 0.0, 2.7)),  # A in [1, 15]
            jax.random.normal(ks[3], (B, T, G, N), F32), jax.random.normal(ks[4], (B, T, G, N), F32),
            jax.random.normal(ks[5], (L, S, H, P, N), F32), sidx, jnp.int32(layer), n_real)


@pytest.mark.parametrize("T,n_real", [
    (1, [1, 0, 1]), (1, [0, 0, 0]), (9, [0, 9, 4]), (9, [9, 9, 9]), (9, [1, 0, 8]), (9, [0, 0, 3]),
    (21, [21, 5, 0]), (16, [16, 0, 7]), (37, [37, 36, 1])])
def test_the_kernel_is_its_twin(T, n_real):
    """T = 1, a fast-forward block of 9, one chunk of 16 and 16 + a remainder;
    ``n_real`` from 0 to T. The chunk's matmul form against the recurrence as
    written: float32 in another order. An idle row's state is BIT-equal (it is
    not moved), every other layer's planes and every other slot's likewise."""
    args = case(len(n_real), T, n_real, seed=T)
    y, s = ssd_scan(*args)
    with jax.default_matmul_precision("highest"):
        y_t, s_t = ssd_scan_reference(*args)
    live = np.asarray(args[-1]) > 0
    computed = live.copy()
    computed[0] |= not live.any()  # with no live row, row 0 stands in (its dt is 0 everywhere)
    scale = float(jnp.max(jnp.abs(y_t))) or 1.0
    assert np.abs(np.asarray(y) - np.asarray(y_t))[live].max(initial=0.0) < 2e-5 * scale
    assert float(jnp.max(jnp.abs(s - s_t))) < 2e-5 * float(jnp.max(jnp.abs(s_t)))
    state, sidx = np.asarray(args[5]), np.asarray(args[6])
    untouched = np.ones((L, S), bool)
    untouched[1, sidx[live]] = False
    assert np.array_equal(np.asarray(s)[untouched], state[untouched])
    assert np.all(np.asarray(y)[~computed] == 0.0)


def test_the_twin_is_the_references_loop_over_positions():
    """One row from an empty state: the twin's ``y`` and final state against
    ``reference.mamba2``'s inner recurrence written out once more in numpy."""
    x, dt, a, b, c, state, sidx, layer, n_real = case(1, 11, [11], seed=3)
    state = jnp.zeros_like(state)
    y, s = ssd_scan_reference(x, dt, a, b, c, state, sidx, layer)
    st = np.zeros((H, P, N), np.float64)
    for t in range(11):
        bt, ct = (np.repeat(np.asarray(v[0, t], np.float64), H // G, axis=0) for v in (b, c))
        dtt = np.asarray(dt[0, t], np.float64)
        st = (np.exp(dtt * np.asarray(a, np.float64))[:, None, None] * st
              + (dtt[:, None] * np.asarray(x[0, t], np.float64))[:, :, None] * bt[:, None, :])
        assert np.allclose(np.asarray(y[0, t]), np.einsum("hpn,hn->hp", st, ct), atol=1e-4)
    assert np.allclose(np.asarray(s[int(layer), int(sidx[0])]), st, atol=1e-4)
    assert ref.relu2(jnp.asarray([-1.0, 2.0])).tolist() == [0.0, 4.0]


def test_a_long_decay_overflows_nothing():
    """dt A of -60 a position over a chunk: only differences l_t - l_s <= 0 are
    exponentiated, so the chunk form stays finite where exp(-l) would not."""
    x, dt, a, b, c, state, sidx, layer, n_real = case(2, 16, [16, 16], seed=9)
    y, s = ssd_scan(x, dt * 0 + 4.0, a * 0 - 15.0, b, c, state, sidx, layer, n_real)
    y_t, s_t = ssd_scan_reference(x, dt * 0 + 4.0, a * 0 - 15.0, b, c, state, sidx, layer)
    assert np.isfinite(np.asarray(y)).all() and np.isfinite(np.asarray(s)).all()
    assert float(jnp.max(jnp.abs(y - y_t))) < 2e-5 * float(jnp.max(jnp.abs(y_t)))
