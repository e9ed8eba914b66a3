"""Pallas kernels vs their pure-jnp reference twins (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_voice_agent.ops import (
    attention_reference,
    decode_attention,
    decode_attention_reference,
    flash_attention,
    masked_argmax,
    masked_argmax_reference,
)


def _qkv(key, B, T, S, nq, nkv, hd, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, T, nq, hd), dtype)
    k = jax.random.normal(kk, (B, S, nkv, hd), dtype)
    v = jax.random.normal(kv, (B, S, nkv, hd), dtype)
    return q, k, v


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, causal):
        q, k, v = _qkv(jax.random.PRNGKey(0), 2, 64, 64, 8, 4, 32)
        out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
        ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_kv_len_masks_padded_keys(self):
        q, k, v = _qkv(jax.random.PRNGKey(1), 1, 32, 48, 4, 4, 16)
        out = flash_attention(q, k, v, causal=False, kv_len=40, block_q=16, block_k=16)
        ref = attention_reference(q[:, :, :, :], k[:, :40], v[:, :40], causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_ragged_blocks(self):
        # T, S not multiples of the block sizes
        q, k, v = _qkv(jax.random.PRNGKey(2), 1, 50, 50, 4, 2, 32)
        out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_bf16_io(self):
        q, k, v = _qkv(jax.random.PRNGKey(3), 1, 32, 32, 4, 4, 32, dtype=jnp.bfloat16)
        out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
        assert out.dtype == jnp.bfloat16
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2, rtol=3e-2
        )


class TestDecodeAttention:
    def test_matches_reference_ragged_lengths(self):
        key = jax.random.PRNGKey(4)
        B, S, nq, nkv, hd = 3, 64, 8, 2, 32
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (B, nq, hd))
        kc = jax.random.normal(kk, (B, S, nkv, hd))
        vc = jax.random.normal(kv, (B, S, nkv, hd))
        kv_len = jnp.asarray([1, 17, 64], jnp.int32)  # per-row frontiers
        out = decode_attention(q, kc, vc, kv_len, block_k=16)
        ref = decode_attention_reference(q, kc, vc, kv_len)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_single_row_single_key(self):
        q = jnp.ones((1, 4, 16))
        kc = jnp.ones((1, 32, 4, 16))
        vc = jnp.full((1, 32, 4, 16), 2.0)
        out = decode_attention(q, kc, vc, jnp.asarray([1], jnp.int32), block_k=16)
        # only one valid key -> output == its value
        np.testing.assert_allclose(np.asarray(out), 2.0, atol=1e-6)


class TestMaskedArgmax:
    def test_matches_reference(self):
        key = jax.random.PRNGKey(5)
        B, V, S = 4, 300, 7
        logits = jax.random.normal(key, (B, V))
        mask = jax.random.bernoulli(jax.random.PRNGKey(6), 0.3, (S, V))
        mask = mask.at[:, 0].set(True)  # no all-masked state
        state = jnp.asarray([0, 3, 6, 2], jnp.int32)
        out = masked_argmax(logits, state, mask)
        ref = masked_argmax_reference(logits, state, mask)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_mask_forces_choice(self):
        logits = jnp.asarray([[0.0, 100.0, 1.0, 2.0]])
        mask = jnp.asarray([[True, False, False, True]])  # best unmasked is idx 3
        out = masked_argmax(logits, jnp.zeros((1,), jnp.int32), mask)
        assert int(out[0]) == 3

    def test_engine_fsm_tables(self, tiny_engine):
        """The real intent-grammar tables round-trip through the kernel."""
        eng = tiny_engine
        V = eng.tokenizer.vocab_size
        logits = jax.random.normal(jax.random.PRNGKey(7), (2, V))
        state = jnp.asarray([eng.fsm.start, eng.fsm.start], jnp.int32)
        out = masked_argmax(logits, state, eng.tables.dense_mask)
        ref = masked_argmax_reference(logits, state, eng.tables.dense_mask)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_decode_block_attention_matches_reference():
    """(B, T) query blocks against per-row frontiers: parity with the jnp
    twin incl. intra-block causality, idle rows parked at slot 0, and an
    odd cache length exercising the pad path."""
    from tpu_voice_agent.ops import (
        decode_block_attention,
        decode_block_attention_reference,
    )

    B, T, nq, nkv, hd, S = 4, 5, 8, 4, 32, 96  # 96 % 64 != 0 -> pad path
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, T, nq, hd), jnp.float32)
    kc = jax.random.normal(ks[1], (B, S, nkv, hd), jnp.float32)
    vc = jax.random.normal(ks[2], (B, S, nkv, hd), jnp.float32)
    q_pos = jnp.asarray([
        [10, 11, 12, 13, 14],   # mid-sequence chain
        [0, 0, 0, 0, 0],        # idle row parked at slot 0
        [90, 91, 92, 93, 94],   # frontier near the odd end
        [3, 4, 5, 5, 5],        # truncated chain duplicates its tail
    ], jnp.int32)
    ref = decode_block_attention_reference(q, kc, vc, q_pos)
    out = decode_block_attention(q, kc, vc, q_pos, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_decode_block_attention_layer_matches_plain():
    """The stacked-cache layer variant must equal the plain kernel on the
    selected plane (scalar-prefetched layer indexing)."""
    from tpu_voice_agent.ops import (
        decode_block_attention,
        decode_block_attention_layer,
    )

    L, B, T, nq, nkv, hd, S = 3, 2, 4, 8, 4, 32, 128
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (B, T, nq, hd), jnp.float32)
    kc = jax.random.normal(ks[1], (L, B, S, nkv, hd), jnp.float32)
    vc = jax.random.normal(ks[2], (L, B, S, nkv, hd), jnp.float32)
    q_pos = jnp.asarray([[20, 21, 22, 23], [7, 8, 9, 9]], jnp.int32)
    for li in range(L):
        plain = decode_block_attention(q, kc[li], vc[li], q_pos, block_k=64)
        stacked = decode_block_attention_layer(q, kc, vc, q_pos,
                                               jnp.int32(li), block_k=64)
        np.testing.assert_allclose(np.asarray(stacked), np.asarray(plain),
                                   rtol=1e-6, atol=1e-6)


def test_engines_refuse_interpreted_pallas_unless_the_cpu_was_asked_for(monkeypatch):
    """JAX lands on the CPU by itself when no accelerator initialises; that
    must not turn kernels="pallas" into a silent interpret-mode run. Under
    the suite's explicit JAX_PLATFORMS=cpu it is the supported test shape."""
    from tpu_voice_agent.ops import backend

    assert backend.on_cpu() and backend.cpu_requested()
    assert backend.resolve_kernels("auto") == "xla"
    assert backend.resolve_kernels("pallas") == "pallas"
    monkeypatch.setattr(backend, "cpu_requested", lambda: False)  # a silent fallback
    assert backend.resolve_kernels("auto") == "xla"
    with pytest.raises(RuntimeError, match="interpret mode"):
        backend.resolve_kernels("pallas")
    with pytest.raises(SystemExit, match="no TPU found"):
        backend.measurement_devices()
    with pytest.raises(ValueError, match="unknown kernels"):
        backend.resolve_kernels("mosaic")


# ------------------------------------------------- the paged block kernel

# the common pass of the block kernel (ISSUE 31). Tables of 6 columns over
# 16-token blocks; three rows behind a shared prefix of blocks 1, 2, 3 unless
# the case says otherwise; T = 9 queries a row starting at ``pos``.
_PREFIX = [1, 2, 3]
_HEAD = [1, 2, 3, 4, 5, 6]  # a longer one, of which a window leaves a RANGE in common
_TWO_PASS_CASES = {
    # name: (tables, first query position a row, live rows or None, S, riders)
    "all rows ride": ([_PREFIX + [4, 5, 0], _PREFIX + [6, 0, 0], _PREFIX + [7, 8, 0]],
                      [60, 50, 70], None, 3, [1, 1, 1]),
    "one row with a different first block": (
        [_PREFIX + [4, 5, 0], [9, 10, 11, 6, 0, 0], _PREFIX + [7, 8, 0]],
        [60, 50, 70], None, 3, [1, 0, 1]),
    "an idle row among live ones": (
        [_PREFIX + [4, 5, 0], _PREFIX + [6, 0, 0], _PREFIX + [7, 8, 0]],
        [60, 0, 70], [True, False, True], 3, [1, 0, 1]),
    "smallest position on a block edge": (
        [_PREFIX + [4, 5, 0], _PREFIX + [6, 0, 0], _PREFIX + [7, 8, 0]],
        [48, 50, 70], None, 3, [1, 1, 1]),
    "smallest position one under a block edge": (
        [_PREFIX + [4, 5, 0], _PREFIX + [6, 0, 0], _PREFIX + [7, 8, 0]],
        [47, 50, 70], None, 2, [1, 1, 1]),
    "one live row of eight": ([_PREFIX + [4, 5, 0]] + [[0] * 6] * 7, [60] + [0] * 7,
                              [True] + [False] * 7, 3, [1] + [0] * 7),
    "no two rows agree": ([[1, 2, 3, 0, 0, 0], [4, 5, 6, 0, 0, 0], [7, 8, 9, 0, 0, 0]],
                          [30, 20, 40], None, 0, [0, 0, 0]),
}


@pytest.mark.parametrize("group", [4, 1])
@pytest.mark.parametrize("case", list(_TWO_PASS_CASES))
def test_paged_block_attention_common_pass_matches_the_plain_reference(case, group, monkeypatch):
    """Common pass + own pass + merge against ``paged_attention_reference``'s
    arithmetic at T queries a row (interpret mode): the split that was
    derived, the rows that ride, the counts the counters carry, the outputs
    of every live row, and zeros for a row that is not live."""
    from tpu_voice_agent.ops import (
        common_block_split,
        paged_block_attention,
        paged_block_attention_reference,
    )

    tables, pos, live, want_s, want_rides = _TWO_PASS_CASES[case]
    L, N, bs, T, nkv, hd = 2, 12, 16, 9, 2, 32
    B = len(tables)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, T, nkv * group, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (L, N, bs, nkv, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (L, N, bs, nkv, hd), jnp.float32)
    tables = jnp.asarray(tables, jnp.int32)
    q_pos = jnp.asarray(pos, jnp.int32)[:, None] + jnp.arange(T)[None, :]
    live = None if live is None else jnp.asarray(live)
    rows = np.ones(B, bool) if live is None else np.asarray(live)

    split = common_block_split(tables, q_pos, live, bs)
    assert int(split.n_common) == want_s
    assert (np.asarray(split.slot) < int(split.n_riders)).astype(int).tolist() == want_rides
    blocks = (np.asarray(q_pos).max(axis=1) // bs + 1)[rows]  # a live row attends these
    # (the common pass is handed every position of a rider where ``n_real`` names none)
    assert np.asarray(split.counts).tolist() == [want_s * sum(want_rides), int(blocks.sum()),
                                                 T * sum(want_rides)]
    assert int(split.n_items) == want_s + int(blocks.sum()) - want_s * sum(want_rides)

    out = np.asarray(paged_block_attention(q, kp, vp, tables, q_pos, jnp.int32(1), live))
    ref = np.asarray(paged_block_attention_reference(q, kp, vp, tables, q_pos, 1))
    np.testing.assert_allclose(out[rows], ref[rows], rtol=1e-5, atol=1e-5)
    assert (out[~rows] == 0).all()
    if case == "all rows ride":
        # a batch wider than the kernel's VMEM budget goes through in groups
        # of rows, each with its own split: here one row a group
        import sys

        monkeypatch.setattr(sys.modules["tpu_voice_agent.ops.paged_attention"], "_STATE_BYTES", 1)
        jax.clear_caches()
        one_by_one = paged_block_attention(q, kp, vp, tables, q_pos, jnp.int32(1), live)
        np.testing.assert_allclose(np.asarray(one_by_one), ref, rtol=1e-5, atol=1e-5)
        jax.clear_caches()  # the budget is read when the wrapper is traced


# the common pass on the PACKED real positions (ISSUE 48): the same tables,
# each row's ``n_real`` of its T = 9 positions real, the rest copies of its last
# real one (query, position) as the chunk program's ``ff_body`` builds them
_REAL_CASES = {
    # name: (tables, first query position a row, live rows or None, n_real a row, window)
    "ragged riders": ([_PREFIX + [4, 5, 0], _PREFIX + [6, 0, 0], _PREFIX + [7, 8, 0],
                       _PREFIX + [9, 0, 0]], [60, 50, 70, 55], None, [0, 1, 2, 9], None),
    "riders and a row with a different first block": (
        [_PREFIX + [4, 5, 0], [9, 10, 11, 6, 0, 0], _PREFIX + [7, 8, 0]],
        [60, 50, 70], None, [3, 2, 1], None),
    "an idle row among live ones": (
        [_PREFIX + [4, 5, 0], _PREFIX + [6, 0, 0], _PREFIX + [7, 8, 0]],
        [60, 0, 70], [True, False, True], [2, 4, 9], None),
    "no two rows agree": ([[1, 2, 3, 0, 0, 0], [4, 5, 6, 0, 0, 0], [7, 8, 9, 0, 0, 0]],
                          [30, 20, 40], None, [1, 0, 5], None),
    "every position real": ([_PREFIX + [4, 5, 0], _PREFIX + [6, 0, 0]], [60, 50], None,
                            [9, 9], None),
    # behind a WINDOW (ISSUE 51): a head of six blocks, the rows' queries at positions
    # 98-120, so the whole blocks inside EVERY rider's window are a common range
    "behind a window": ([_HEAD + [7, 8], _HEAD + [9, 0], _HEAD + [10, 11]],
                        [100, 98, 104], None, [2, 0, 9], 40),
    "a range of three blocks behind a window": (
        [_HEAD + [7, 8], _HEAD + [9, 0], _HEAD + [10, 11], _HEAD + [18, 0]],
        [100, 98, 104, 99], None, [0, 1, 2, 9], 72),
    "every position real behind a window": (
        [_HEAD + [7, 8], _HEAD + [9, 0], _HEAD + [10, 11]], [100, 98, 104], None, [9, 9, 9], 56),
    "an idle row behind a window": (
        [_HEAD + [7, 8], _HEAD + [9, 0], _HEAD + [10, 11], _HEAD + [18, 0]],
        [100, 0, 104, 99], [True, False, True, True], [2, 4, 9, 1], 56),
    "a row with another first block behind a window": (
        [_HEAD + [7, 8], [12, 13, 14, 15, 16, 17, 9, 0], _HEAD + [10, 11], _HEAD + [18, 0]],
        [100, 98, 104, 99], None, [3, 2, 1, 9], 56),
    "a query inside the first block behind a window": (
        [_HEAD + [7, 8], _HEAD + [9, 0], _HEAD + [10, 11]], [100, 5, 104], None, [2, 9, 1], 56),
    "no whole block inside the windows": (
        [_HEAD + [7, 8], _HEAD + [9, 0], _HEAD + [10, 11]], [100, 98, 104], None, [2, 1, 9], 24),
    "fewer than half the rows agree behind a window": (
        [_HEAD + [7, 8], [12, 13, 14, 15, 16, 17, 9, 0], _HEAD + [10, 11],
         [19, 20, 21, 22, 23, 0, 18, 0], [0, 2, 3, 4, 5, 6, 1, 0]],
        [100, 98, 104, 99, 97], None, [3, 2, 1, 9, 4], 56),
    "one position a row behind a window": (  # T = 1: a windowed layer's every step
        [_HEAD + [7, 8], _HEAD + [9, 0], _HEAD + [10, 11]], [100, 98, 104], None, [1, 1, 1], 40, 1),
    "two groups of rows behind a window": (
        [_HEAD + [7, 8], _HEAD + [9, 0], _HEAD + [10, 11], _HEAD + [18, 0], _HEAD + [19, 20],
         _HEAD + [21, 0]], [100, 98, 104, 99, 111, 97], None, [1, 9, 0, 2, 3, 1], 56),
    # Command A+'s shape in small: the rows' state passes the kernel's budget,
    # so they go through in two groups of rows, each with a split of its own
    "two groups of rows": ([_PREFIX + [4, 5, 0], _PREFIX + [6, 0, 0], _PREFIX + [7, 8, 0],
                            _PREFIX + [9, 0, 0]], [60, 50, 70, 55], None, [1, 9, 0, 2], None),
}


@pytest.mark.parametrize("group", [4, 1, 16, 7])
@pytest.mark.parametrize("case", list(_REAL_CASES))
def test_paged_block_attention_common_pass_takes_the_real_positions(case, group, monkeypatch):
    """``n_real`` packs the riders' real positions for the common pass and
    carries their state into the own pass: a real position's output is the
    ``n_real=None`` call's BIT FOR BIT (a query row's dots do not depend on
    which rows share its tile) and the plain reference's within tolerance; a
    position behind them returns its row's last real one's output; a row with
    none, or not live, returns zeros and disturbs nobody; ``common_query_rows``
    counts the riders' real positions.

    Behind a WINDOW the common pass is a RANGE of columns between a rider's
    low walk and its high one (ISSUE 51), each row's blocks still in ascending
    order: a real position's output is, bit for bit, that of the same call
    with the SAME K/V laid under block ids of each row's own — nobody rides:
    the one walk a row made before — and the masked dense reference's within
    tolerance. (The scale is a power of two there: in interpret mode the CPU's
    compiler contracts ``dot * scale - m`` into ONE fused multiply-add in the
    pass that has no mask between the two, which rounds as the masked pass does
    only where ``dot * scale`` is exact. The chip has no such contraction:
    ``tools/block_attn_check.py --window`` holds the same there at 128 ** -0.5.)"""
    import sys

    from tpu_voice_agent.ops import (
        common_block_split,
        paged_block_attention,
        paged_block_attention_reference,
        row_group_splits,
    )

    tables, pos, live, n_real, window, *rest = _REAL_CASES[case]
    (T,) = rest or (9,)
    L, bs, nkv, hd = 2, 16, 2, 32
    B, M = np.shape(tables)
    own_ids = 24 + np.arange(B * M).reshape(B, M)  # behind every id a case names
    N = 12 if window is None else 24 + B * M
    scale = None if window is None else 0.125
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    n_real = np.asarray(n_real, np.int32)
    t_of = np.minimum(np.arange(T)[None, :], np.maximum(n_real[:, None] - 1, 0))  # the copies
    q = jax.random.normal(ks[0], (B, T, nkv * group, hd), jnp.float32)
    q = jnp.take_along_axis(q, jnp.asarray(t_of)[:, :, None, None], axis=1)
    kp = jax.random.normal(ks[1], (L, N, bs, nkv, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (L, N, bs, nkv, hd), jnp.float32)
    tables = jnp.asarray(tables, jnp.int32)
    q_pos = jnp.asarray(np.asarray(pos, np.int32)[:, None] + t_of)
    live = None if live is None else jnp.asarray(live)
    rows = (np.ones(B, bool) if live is None else np.asarray(live)) & (n_real > 0)
    real = (np.arange(T)[None, :] < n_real[:, None]) & rows[:, None]

    if "two groups of rows" in case:
        mod = sys.modules["tpu_voice_agent.ops.paged_attention"]
        half = nkv * T * group * (2 * hd * 4 + 2 * 4 * hd + 2 * 4 * 128) * (B // 2)
        monkeypatch.setattr(mod, "_STATE_BYTES", half)
        jax.clear_caches()
        shape = (B, T, nkv * group, nkv, hd)
        made = lambda n, tables=tables: row_group_splits(shape, tables, q_pos, live, bs, window=window,
                                                         itemsize=4, n_real=n)
        assert len(made(None)) == 2
    else:
        made = lambda n, tables=tables: common_block_split(tables, q_pos, live, bs, window=window,
                                                           n_real=n)
    win = None if window is None else jnp.int32(window)
    call = lambda n, split, kp=kp, vp=vp, tables=tables: np.asarray(paged_block_attention(
        q, kp, vp, tables, q_pos, jnp.int32(1), live, split, win, n, scale=scale))
    whole = call(None, made(None))
    got = call(jnp.asarray(n_real), made(jnp.asarray(n_real)))
    if window is None:  # the split is the wrapper's own where the caller hands none
        np.testing.assert_array_equal(call(jnp.asarray(n_real), None), got)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got[real], whole[real])
    np.testing.assert_array_equal(got, np.take_along_axis(got, t_of[:, :, None, None], axis=1))
    assert (got[~rows] == 0).all()
    if window is None:
        ref = np.asarray(paged_block_attention_reference(q, kp, vp, tables, q_pos, 1))
    else:
        # the XLA attention of the model that brought the window: a row's blocks gathered,
        # every query against every key, masked to the query's last ``window`` positions
        from tpu_voice_agent.models.sambay import _attend

        kl, vl = (p[1][tables].reshape(B, M * bs, nkv, hd) for p in (kp, vp))
        ref = np.asarray(_attend(q, kl, vl, q_pos, window, scale))
    np.testing.assert_allclose(got[rows], ref[rows], rtol=1e-5, atol=1e-5)
    as_tuple = lambda x: x if isinstance(x, tuple) and not hasattr(x, "counts") else (x,)
    splits = as_tuple(made(jnp.asarray(n_real)))
    rides = np.concatenate([np.asarray(s.slot) < int(s.n_riders) for s in splits])
    counted = sum(int(s.counts[2]) for s in splits)
    assert counted == int(n_real[rides].sum())
    assert sum(int(s.counts[0]) for s in splits) == sum(int(s.n_common) * int(s.n_riders) for s in splits)
    if window is not None:
        # the SAME K/V under ids of each row's own: no two rows agree, nobody rides
        flat = np.asarray(tables).reshape(-1)
        kp2, vp2 = (p.at[:, own_ids.reshape(-1)].set(p[:, flat]) for p in (kp, vp))
        alone = made(jnp.asarray(n_real), jnp.asarray(own_ids, jnp.int32))
        assert all(int(s.n_riders) == 0 and int(s.n_common) == 0 for s in as_tuple(alone))
        walked = call(jnp.asarray(n_real), alone, kp2, vp2, jnp.asarray(own_ids, jnp.int32))
        np.testing.assert_array_equal(got[real], walked[real])
        # a query in the first block, no whole block, too few riders: no range
        ranged = "inside" not in case and "fewer" not in case
        assert all((int(s.n_common) > 0) == ranged for s in splits)
        assert all(int(s.n_items) == int(a.n_items) - int(s.counts[0]) + int(s.n_common)
                   for s, a in zip(splits, as_tuple(alone)))
    if "two groups of rows" in case:
        jax.clear_caches()  # the budget is read when the wrapper is traced


# what ``common_block_split(window=...)`` must find in the windowed cases above:
# (S0, S1, the rows that ride), or None where it is the split without a range
_RANGE_WANT = {
    "behind a window": (5, 6, [1, 1, 1]),
    "a range of three blocks behind a window": (3, 6, [1, 1, 1, 1]),
    "every position real behind a window": (4, 6, [1, 1, 1]),
    "an idle row behind a window": (4, 6, [1, 0, 1, 1]),
    "a row with another first block behind a window": (4, 6, [1, 0, 1, 1]),
    "a query inside the first block behind a window": None,
    "no whole block inside the windows": None,
    "fewer than half the rows agree behind a window": None,
    "one position a row behind a window": (5, 6, [1, 1, 1]),
}


@pytest.mark.parametrize("case", list(_RANGE_WANT))
def test_common_block_split_finds_the_common_range_behind_a_window(case):
    """The split behind a window (ISSUE 51), item for item: the riders' LOW
    walks from each one's boundary block to ``S0`` (rows in order, every rider
    with one item at least), the range's ``S1 - S0`` blocks once, then every
    live row's other blocks — a rider's from ``S1``, another row's whole
    window; without a range (no whole block inside every rider's window, a
    query inside the first block, fewer than half the live rows agreeing) one
    walk a live row from its boundary block, as before. ``counts``: the
    row-blocks the range took off the walks (a caller's
    ``window_common_row_blocks``), those live rows hold, the riders' real
    positions."""
    from tpu_voice_agent.ops import common_block_split

    tables, pos, live, n_real, window, *rest = _REAL_CASES[case]
    (T,) = rest or (9,)
    bs, tables, n_real = 16, np.asarray(tables), np.asarray(n_real, np.int32)
    B = len(tables)
    q_pos = np.asarray(pos)[:, None] + np.minimum(np.arange(T)[None, :], np.maximum(n_real[:, None] - 1, 0))
    alive = np.ones(B, bool) if live is None else np.asarray(live)
    S0, S1, rides = _RANGE_WANT[case] or (0, 0, [0] * B)
    rides = np.asarray(rides, bool)
    first = np.maximum(q_pos.min(axis=1) - (window - 1), 0) // bs
    last = q_pos.max(axis=1) // bs
    low = [(b, j) for b in np.flatnonzero(rides) for j in range(first[b], S0)]
    own = [(b, j) for b in np.flatnonzero(alive) for j in range(S1 if rides[b] else first[b], last[b] + 1)]
    leader = int(np.flatnonzero(rides)[0]) if rides.any() else 0

    split = common_block_split(jnp.asarray(tables), jnp.asarray(q_pos, jnp.int32),
                               None if live is None else jnp.asarray(live), bs, window=window,
                               n_real=jnp.asarray(n_real))
    n, n_low = int(split.n_items), int(split.n_low)
    assert (int(split.n_common), n_low, n) == (S1 - S0, len(low), len(low) + S1 - S0 + len(own))
    assert (np.asarray(split.slot) < int(split.n_riders)).tolist() == rides.tolist()
    item_rows, item_tiles, item_blocks = (np.asarray(x)[:n] for x in
                                          (split.item_row, split.item_tile, split.item_block))
    walks = low + own
    in_walks = np.r_[:n_low, n_low + S1 - S0:n]
    assert list(zip(item_rows[in_walks], item_tiles[in_walks])) == walks
    assert item_blocks[in_walks].tolist() == [tables[b, j] for b, j in walks]
    assert item_tiles[n_low:n_low + S1 - S0].tolist() == list(range(S0, S1))
    assert item_blocks[n_low:n_low + S1 - S0].tolist() == tables[leader, S0:S1].tolist()
    assert all(sum(b == r for b, _ in low) >= 1 for r in np.flatnonzero(rides))
    assert np.asarray(split.attended).tolist() == alive.tolist()
    assert np.asarray(split.counts).tolist() == [(S1 - S0) * rides.sum(), (last + 1)[alive].sum(),
                                                 n_real[rides].sum()]
    if rides.any():  # a rider's real positions, packed in row order
        assert np.asarray(split.pack_n).tolist() == np.where(rides, n_real, 0).tolist()
        assert np.asarray(split.pack_start)[rides].tolist() == (
            np.cumsum(np.where(rides, n_real, 0)) - n_real)[rides].tolist()


# sha256 of the text ``paged_block_attention`` lowers to WITHOUT a window
# (interpret mode, scope names in, Python frames out), taken on ISSUE 51's
# parent (dc7f00a): the range of ISSUE 51 lives behind the kernel's static
# ``windowed`` and ``common_block_split``'s ``window``, and every program without
# a binding window keeps the text — and the chip's compiled executable — it had.
# A PR that changes the unwindowed kernel on purpose re-derives them on its
# parent's tree first and says so.
_UNWINDOWED_SHA256 = {
    "a block told its real positions": "0badcddf7b591b122b6c0822b6d192a0ad81d06d8ad1432ff8b1c27bf84e576a",
    "one position a row, every row live": "c72d4005d89f1566d4841216debf01c5dd0b3a0acee37e47c9f775e6f3fdba34",
}


@pytest.mark.parametrize("case", list(_UNWINDOWED_SHA256))
def test_the_unwindowed_block_kernel_lowers_to_the_text_it_had(case):
    import hashlib

    from tpu_voice_agent.ops import paged_block_attention

    told = case == "a block told its real positions"
    B, T, nq, nkv, hd, M = (4, 9, 8, 2, 32, 6) if told else (4, 1, 4, 4, 32, 6)
    S = jax.ShapeDtypeStruct
    args = [S((B, T, nq, hd), jnp.bfloat16), S((2, 12, 16, nkv, hd), jnp.bfloat16),
            S((2, 12, 16, nkv, hd), jnp.bfloat16), S((B, M), jnp.int32), S((B, T), jnp.int32),
            S((), jnp.int32)]
    if told:
        fn = lambda q, kp, vp, bt, qp, layer, live, n_real: paged_block_attention(
            q, kp, vp, bt, qp, layer, live, None, None, n_real, interpret=True)
        args += [S((B,), jnp.bool_), S((B,), jnp.int32)]
    else:
        fn = lambda q, kp, vp, bt, qp, layer: paged_block_attention(q, kp, vp, bt, qp, layer,
                                                                    interpret=True)
    frames = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    finally:
        jax.config.update("jax_traceback_in_locations_limit", frames)
    assert hashlib.sha256(text.encode()).hexdigest() == _UNWINDOWED_SHA256[case]
