"""An admission's covered blocks leave the pool in ONE gather on (plane, block)
(``llama.gather_row_blocks``): the bits of ``pool[plane][tbl]``, and no
``dynamic_slice`` of a whole plane in a scanned admission's program — behind a
scan that carries the pools that slice is an HBM copy of every block of the
plane, a layer, for K and for V (0.21 s of ``ouro_flood``'s traced stretch;
ledger, PR 57)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_voice_agent.models import llama
from tpu_voice_agent.models.llama import LlamaConfig, gather_row_blocks

L, N, BS, H, HD = 3, 6, 8, 2, 16

# rows' tables: distinct blocks; a block two rows share and one a row names twice;
# rows parked on the trash block (0) behind what they cover
TABLES = {
    "distinct": [[1, 2, 3], [4, 5, 1]],
    "repeated": [[2, 2, 5], [2, 3, 3]],
    "trash": [[4, 0, 0], [0, 0, 0]],
}


def _pool(kind: str):
    """An fp pool (planes, N, bs, H, hd), or an int8 one with its scale plane (planes, N, bs, H)."""
    key = jax.random.PRNGKey(0)
    if kind == "fp":
        return (jax.random.normal(key, (L, N, BS, H, HD), jnp.bfloat16),)
    kv, ks = jax.random.split(key)
    return (jax.random.randint(kv, (L, N, BS, H, HD), -127, 128, jnp.int8),
            jax.random.normal(ks, (L, N, BS, H), jnp.bfloat16))


@pytest.mark.parametrize("table", sorted(TABLES))
@pytest.mark.parametrize("kind", ["fp", "int8"])
@pytest.mark.parametrize("plane", ["traced", "constant"])
def test_the_helper_is_the_old_expression_bit_for_bit(plane, kind, table):
    tbl = jnp.asarray(TABLES[table], jnp.int32)
    for pool in _pool(kind):
        if plane == "traced":  # the scan's index, as a scanned model's layers have it
            over = lambda f: jax.jit(lambda p, t: jax.lax.scan(
                lambda c, li: (c, f(p, li, t)), 0, jnp.arange(L))[1])(pool, tbl)
        else:  # a Python int, as an unrolled model's layers have it
            over = lambda f: jax.jit(lambda p, t: jnp.stack([f(p, li, t) for li in range(L)]))(pool, tbl)
        new = over(gather_row_blocks)
        old = over(lambda p, li, t: p[li][t])
        assert new.shape == (L, *tbl.shape, *pool.shape[2:]) and new.dtype == pool.dtype
        np.testing.assert_array_equal(np.asarray(new.astype(jnp.float32)),
                                      np.asarray(old.astype(jnp.float32)))


def test_a_scanned_admission_slices_no_whole_plane():
    """T = 8 behind a cached block, the layers a scan: the lowered program's
    gathers index (plane, block) in the pool, and no ``dynamic_slice`` hands
    back a plane ``[1, N, bs, H, hd]``."""
    cfg = LlamaConfig(vocab_size=64, dim=32, n_layers=L, n_heads=4, n_kv_heads=H, ffn_dim=64,
                      max_seq_len=4 * BS)
    assert not cfg.layer_types  # layers of one kind: a scan
    hd = cfg.head_dim
    params = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    pool = jax.ShapeDtypeStruct((L, N, BS, H, hd), jnp.bfloat16)
    ids = jax.ShapeDtypeStruct((1, 8), jnp.int32)
    text = llama.forward_paged.__wrapped__.lower(
        params, cfg, ids, ids, pool, pool, jax.ShapeDtypeStruct((1, 3), jnp.int32),
        attn_impl="xla", fresh_block=False, gather_blocks=2).as_text()
    plane = f"tensor<1x{N}x{BS}x{H}x{hd}xbf16>"
    sliced = [ln for ln in text.splitlines() if "dynamic_slice" in ln and ln.rstrip().endswith(plane)]
    assert not sliced, sliced
    gathers = re.findall(r"stablehlo\.gather.*?slice_sizes = array<i64: ([\d, ]+)>", text)
    assert gathers.count(f"1, 1, {BS}, {H}, {hd}") == 2  # K and V, the one layer body's
