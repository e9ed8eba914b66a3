"""``ops.gated_delta_scan`` (the gated delta rule over per-slot state planes):
the Pallas kernel, interpreted, against its XLA twin and against the recurrence
written out once more in numpy; masked positions, idle rows, swapped slots, the
stacked planes in place, the planes' layout."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_voice_agent.ops import gated_delta as gd
from tpu_voice_agent.ops.gated_delta import gated_delta_scan, gated_delta_scan_reference

F32 = jnp.float32
# d_v = 1.5 d_k and no lane multiple: two heads side by side in a plane, as at 96 / 192
H, DK, DV, L, S = 4, 16, 24, 3, 6


def case(B, T, n_real, seed=0, layer=1, sidx=None):
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, T, H, DK), F32)) * DK ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, DK), F32))
    v = jax.random.normal(ks[2], (B, T, H, DV), F32)
    g = -jax.random.uniform(ks[3], (B, T, H), F32) * 0.7
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H), F32))  # past 1 in half of them
    state = jax.random.normal(ks[5], (L, S, *gd.plane_shape(H, DK, DV)), F32)
    if sidx is None:
        sidx = np.random.RandomState(seed).permutation(S)[:B]
    return (state, jnp.asarray(sidx, jnp.int32), jnp.int32(layer), q, k, v, g, beta,
            jnp.asarray(n_real, jnp.int32))


def test_the_planes_hold_two_heads_side_by_side_where_a_head_is_no_lane_multiple():
    assert gd.heads_abreast(30, 192) == 2 and gd.plane_shape(30, 96, 192) == (15, 96, 384)
    assert gd.heads_abreast(4, 24) == 2 and gd.plane_shape(4, 16, 24) == (2, 16, 48)
    assert gd.heads_abreast(8, 128) == 1 and gd.heads_abreast(3, 24) == 1
    heads = jax.random.normal(jax.random.key(0), (5, H, DK, DV), F32)
    planes = gd.planes_of(heads, 2)
    assert planes.shape == (5, 2, DK, 2 * DV)
    assert np.array_equal(np.asarray(planes[:, 1, :, DV:]), np.asarray(heads[:, 3]))  # head 3: pair 1, right
    assert np.array_equal(np.asarray(gd.heads_of(planes, DV)), np.asarray(heads))


@pytest.mark.parametrize("T,n_real", [
    (1, [1, 0, 1]), (1, [0, 0, 0]), (9, [0, 9, 4]), (9, [9, 9, 9]), (9, [1, 0, 8]), (9, [0, 0, 3]),
    (16, [16, 0, 7]), (40, [40, 17, 0]), (40, [3, 40, 33])])
def test_the_kernel_is_its_twin(T, n_real):
    """T = 1, a fast-forward block of 9, one grid step of 16, 40 = two steps
    and a remainder; ``n_real`` 0, partial, full. The kernel's walk against the
    recurrence as written: float32, a 16-term sum in another order at most. An
    idle row's state is BIT-equal (it is not moved), every other layer's planes
    and every other slot's likewise; a position past ``n_real`` reads 0."""
    args = case(len(n_real), T, n_real, seed=T)
    o, s = gated_delta_scan(*args, impl="pallas")
    o_t, s_t = gated_delta_scan(*args, impl="xla")
    live = np.asarray(args[-1]) > 0
    scale = float(jnp.max(jnp.abs(o_t))) or 1.0
    assert np.abs(np.asarray(o) - np.asarray(o_t)).max() < 2e-5 * scale
    assert float(jnp.max(jnp.abs(s - s_t))) < 2e-5 * float(jnp.max(jnp.abs(s_t)))
    state, sidx = np.asarray(args[0]), np.asarray(args[1])
    untouched = np.ones((L, S), bool)
    untouched[1, sidx[live]] = False
    assert np.array_equal(np.asarray(s)[untouched], state[untouched])
    assert np.array_equal(np.asarray(s_t)[untouched], state[untouched])
    past = np.arange(T)[None, :] >= np.asarray(n_real)[:, None]
    assert np.all(np.asarray(o)[past] == 0.0) and np.all(np.asarray(o_t)[past] == 0.0)
    if live.any():
        assert not np.array_equal(np.asarray(s)[1, sidx[live]], state[1, sidx[live]])


def test_the_twin_is_the_recurrence_written_out():
    """One row from a given state, float64 numpy: S' = exp(g) S; u = beta (v -
    S'^T k); S = S' + k (x) u; o = S^T q — with beta past 1 reached."""
    state, sidx, layer, q, k, v, g, beta, n_real = case(1, 11, [11], seed=3)
    assert float(beta.max()) > 1.0
    o, s = gated_delta_scan_reference(state, sidx, layer, q, k, v, g, beta, n_real)
    st = np.asarray(gd.heads_of(state[int(layer), int(sidx[0])], DV), np.float64)  # (H, dk, dv)
    for t in range(11):
        f = lambda a: np.asarray(a[0, t], np.float64)
        st = np.exp(f(g))[:, None, None] * st
        u = f(beta)[:, None] * (f(v) - np.einsum("hkv,hk->hv", st, f(k)))
        st = st + f(k)[:, :, None] * u[:, None, :]
        assert np.allclose(np.asarray(o[0, t]), np.einsum("hkv,hk->hv", st, f(q)), atol=1e-4)
    assert np.allclose(np.asarray(gd.heads_of(s[int(layer), int(sidx[0])], DV)), st, atol=1e-4)


def test_two_rows_that_swap_their_slots_swap_their_states():
    """The state is the SLOT's: the same two rows' inputs against slots (4, 1)
    and (1, 4) advance each slot by the row that names it."""
    a = case(2, 9, [9, 5], seed=5, sidx=[4, 1])
    b = (a[0], jnp.asarray([1, 4], jnp.int32), *a[2:])
    _, sa = gated_delta_scan(*a, impl="pallas")
    _, sb = gated_delta_scan(*b, impl="pallas")
    assert not np.array_equal(np.asarray(sa[1, 4]), np.asarray(sb[1, 4]))
    # row 0 on slot 4's state (a) is row 0 on slot 1's state (b) only where the states agree: feed equal states
    same = a[0].at[1, 1].set(a[0][1, 4])
    _, sa = gated_delta_scan(same, *a[1:], impl="pallas")
    _, sb = gated_delta_scan(same, *b[1:], impl="pallas")
    assert np.array_equal(np.asarray(sa[1, 4]), np.asarray(sb[1, 1]))
    assert np.array_equal(np.asarray(sa[1, 1]), np.asarray(sb[1, 4]))


def test_a_callers_own_mask_changes_nothing_and_a_masked_position_is_exact():
    """beta = 0 and g = 0 past ``n_real`` by the caller too: the same bits; and
    in the twin such a position leaves S BIT-unchanged (S * 1 + k * 0)."""
    state, sidx, layer, q, k, v, g, beta, n_real = case(3, 9, [4, 0, 9], seed=7)
    real = (jnp.arange(9)[None, :] < n_real[:, None])[..., None]
    for impl in ("pallas", "xla"):
        o1, s1 = gated_delta_scan(state, sidx, layer, q, k, v, g, beta, n_real, impl)
        o2, s2 = gated_delta_scan(state, sidx, layer, q, k, v, jnp.where(real, g, 0.0),
                                  jnp.where(real, beta, 0.0), n_real, impl)
        assert np.array_equal(np.asarray(o1), np.asarray(o2)) and np.array_equal(np.asarray(s1), np.asarray(s2))
    short = gated_delta_scan(state, sidx, layer, q[:, :4], k[:, :4], v[:, :4], g[:, :4], beta[:, :4],
                             jnp.minimum(n_real, 4), "xla")[1]
    full = gated_delta_scan(state, sidx, layer, q, k, v, g, beta, jnp.minimum(n_real, 4), "xla")[1]
    assert np.array_equal(np.asarray(short), np.asarray(full))


def test_the_planes_are_advanced_in_place():
    """Donated planes come back as the same buffer's update: no second copy of
    the stacked states in the lowered call (``input_output_aliases``)."""
    args = case(2, 9, [9, 3], seed=11)
    text = jax.jit(gd.gated_delta_scan.__wrapped__, donate_argnums=(0,),
                   static_argnames=("impl", "interpret")).lower(*args, interpret=True).as_text()
    assert "tf.aliasing_output" in text or "jax.buffer_donor" in text
    chunks = gated_delta_scan(*case(1, 40, [40], seed=2), impl="pallas")
    whole = gated_delta_scan(*case(1, 40, [40], seed=2), impl="xla")
    assert float(jnp.max(jnp.abs(chunks[1] - whole[1]))) < 2e-5 * float(jnp.max(jnp.abs(whole[1])))
