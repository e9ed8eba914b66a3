"""A layer's cache write moves a forward's REAL positions and no others
(``llama.write_rows`` over ``llama.write_walk``'s tiles, ISSUE 60): told its rows'
real positions, ``forward_paged`` leaves the pools the pair of whole-block scatters
left — bit for bit at every index but the trash slot — and the same logits at every
real position; told none, it makes the old scatter and no loop."""

import dataclasses
import functools
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import cohere2moe_stack, moonlight_stack, ouro_stack, parse_stack
from tpu_voice_agent.models import llama, mla, sambay
from tpu_voice_agent.models.family import family
from tpu_voice_agent.serve.paged import build_pools

ROOT = Path(__file__).parents[1]
B, T, BS, N = 4, 5, 8, 10  # four rows of a 1 + 4 block; two blocks of its own a row behind block 0
TILE = llama.write_tile(B * T)


def _bench(file: str, builder):
    m, s = parse_stack.as_run(json.loads((ROOT / "benchmark/configs" / file).read_text()), True)
    return dataclasses.replace(builder.llama_config(m, s), max_seq_len=256)


@functools.cache
def config(name: str):
    """-> (cfg, the module whose site writes its cache)."""
    if name == "llama":  # layers of one kind: a scan, the flat view
        return llama.LlamaConfig(vocab_size=64, dim=32, n_layers=3, n_heads=4, n_kv_heads=2, ffn_dim=64,
                                 max_seq_len=256), llama
    if name == "ouro":  # the scan of passes around the scan of layers: a plane a (pass, layer)
        return _bench("ouro-2.6b-int8.json", ouro_stack), llama
    if name == "unrolled":  # layers of two kinds (``layer_types``): the pool as it is shaped
        return _bench("command-a-plus-05-2026-int8.json", cohere2moe_stack), llama
    if name == "mla":  # a latent and a rotated key: two pools of their own widths
        return _bench("moonlight-16b-a3b-int8.json", moonlight_stack), mla
    return sambay.PRESETS["sambay-test"], sambay  # K/V beside a recurrent state, told every forward


CONFIGS = ("llama", "ouro", "unrolled", "mla", "sambay")

# (n_real, live): the real positions of each row and whether it is live
CASES = {
    "random": ([2, 0, T, 1], [True, True, True, True]),  # 0, 1 and T among them: a live row with none
    "not_live": ([3, 4, 2, 5], [True, False, True, False]),
    "none_live": ([1, 1, 1, 1], [False, False, False, False]),
    "over_a_tile": ([T, T, T, TILE + 1 - 3 * T], [True, True, True, True]),
    "a_tile": ([T, T, T, TILE - 3 * T], [True, True, True, True]),
    "whole_block": ([T, T, T, T], [True, True, True, True]),
}


def _raw(fn):
    """The Python function under ``forward_paged``'s jit (and its compile watch)."""
    while hasattr(fn, "__wrapped__"):
        fn = fn.__wrapped__
    return fn


@functools.cache
def programs(name: str):
    """-> (params, walk, scatter, before): ``forward_paged`` told ``n_real``, the same with
    every position written (``write_walk`` handing no tiles: the scatter every tree made), each
    compiled once for all cases, and the pools a case starts from."""
    cfg, mod = config(name)
    fam = family(cfg)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    pools = jax.tree.map(lambda z: jax.random.normal(jax.random.PRNGKey(z.ndim), z.shape, jnp.float32).astype(z.dtype),
                         build_pools(fam.cache, N, BS, B))

    def program():  # a function of its own a trace: jit caches traces by the function
        def run(params, toks, pos, kp, vp, tables, live, n_real):
            return _raw(llama.forward_paged)(params, cfg, toks, pos, kp, vp, tables, attn_impl="xla",
                                             write_mask=live, gather_blocks=2, n_real=n_real, kv_stats=True)
        return jax.jit(run).lower(params, toks, pos, *pools, *rest).compile()

    toks, pos, *rest = inputs(name, *CASES["random"])
    walk = program()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(mod, "write_walk", lambda n_real, T, where: (None, where))
        scatter = program()
    return params, walk, scatter, pools


def inputs(name: str, n_real, live):
    """-> (tokens, positions, tables, live, n_real) as the chunk program's ``ff_body`` builds a
    block: a position behind a row's real ones is a copy of the last real one, a row that
    is not live stands at position 0."""
    cfg, _ = config(name)
    rng = np.random.default_rng(7)
    n_real, live = np.asarray(n_real, np.int32), np.asarray(live, bool)
    t = np.minimum(np.arange(T)[None, :], np.maximum(n_real[:, None] - 1, 0))
    start = np.where(live, rng.integers(1, 2 * BS - T, size=B), 0)
    toks = np.take_along_axis(rng.integers(0, cfg.vocab_size, (B, T)), t, axis=1)
    tables = 1 + np.arange(2 * B).reshape(B, 2)
    if family(cfg).cache["state_column"]:
        tables = np.concatenate([tables, np.arange(B)[:, None]], axis=1)
    return tuple(jnp.asarray(a) for a in (toks.astype(np.int32), (start[:, None] + t).astype(np.int32),
                                          tables.astype(np.int32), live, n_real))


def block_planes(pool) -> list[np.ndarray]:
    """A pool's block planes, each (layers, N * bs, width) float32."""
    return [np.asarray(p, np.float32).reshape(p.shape[0], N * BS, -1)
            for p in jax.tree.leaves(pool) if p.shape[1:3] == (N, BS)]


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("name", CONFIGS)
def test_the_walk_leaves_the_pools_the_scatter_left(name, case):
    cfg, _ = config(name)
    fam = family(cfg)
    params, walk, scatter, before = programs(name)
    toks, pos, tables, live, n_real = inputs(name, *CASES[case])
    new = walk(params, toks, pos, *before, tables, live, n_real)
    old = scatter(params, toks, pos, *before, tables, live, n_real)
    n, alive = np.asarray(n_real), np.asarray(live)
    real = (np.arange(T)[None, :] < n[:, None]) & alive[:, None]
    # the pools: equal outside the trash slot (block 0, offset 0) — and, for a model
    # that writes a padded position where it stands, outside the ONE index a live row
    # with no real position wrote its T copies to (unread: the row's next real write
    # lands there first) — where the walk writes nothing
    flat = np.asarray(tables)[:, :2][np.arange(B)[:, None], np.asarray(pos) // BS] * BS + np.asarray(pos) % BS
    unwritten = sorted({int(i) for i in flat[alive & (n == 0)].ravel()})
    held = np.ones(N * BS, bool)
    held[[0, *unwritten]] = False
    for side in (1, 2):
        for got, want, was in zip(block_planes(new[side]), block_planes(old[side]), block_planes(before[side - 1])):
            np.testing.assert_array_equal(got[:, held], want[:, held])
            np.testing.assert_array_equal(got[:, unwritten], was[:, unwritten])
        # the per-slot planes beside them (a recurrent state's) are no business of the write
        for got, want in zip(jax.tree.leaves(new[side]), jax.tree.leaves(old[side])):
            if got.shape[1:3] != (N, BS):
                np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    np.testing.assert_array_equal(np.asarray(new[0])[real], np.asarray(old[0])[real])
    # what each counted: the tiles that hold real positions, a cache layer; every position
    planes = jax.tree.leaves(fam.cache["planes"]["k"], is_leaf=lambda p: isinstance(p, tuple))[0][0]
    tiles = max(-(-int(real.sum()) // TILE), 1)
    assert int(new[-1][0]) == planes * tiles * TILE and int(old[-1][0]) == planes * B * T


def _whiles_under(jaxpr, scope: str) -> int:
    found = 0
    for eqn in jaxpr.eqns:
        found += eqn.primitive.name == "while" and scope in str(eqn.source_info.name_stack)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _whiles_under(sub, scope)
    return found


@pytest.mark.parametrize("told", [False, True])
@pytest.mark.parametrize("name", CONFIGS)
def test_a_forward_told_no_real_positions_makes_the_old_scatter(name, told):
    """``n_real`` None — ``refcheck``'s forwards, the llama family's admissions — is a
    static branch: no ``while`` under ``layer/kv_write``; told, the walk is one."""
    cfg, _ = config(name)
    params, _, _, before = programs(name)
    toks, pos, tables, live, n_real = inputs(name, *CASES["random"])
    jaxpr = jax.make_jaxpr(lambda *a: _raw(llama.forward_paged)(
        a[0], cfg, *a[1:6], attn_impl="xla", write_mask=a[6], gather_blocks=2,
        n_real=a[7] if told else None))(params, toks, pos, *before, tables, live, n_real)
    assert (_whiles_under(jaxpr.jaxpr, "layer/kv_write") > 0) == told


@pytest.mark.parametrize("rows,tile", [(288, 48), (72, 16), (256, 48), (32, 16), (8, 8)])
def test_the_tile_follows_the_blocks_shape(rows, tile):
    """A sixth of the block in whole eights, sixteen at the least; a block no larger
    than its tile keeps the scatter (``write_walk`` hands no tiles)."""
    assert llama.write_tile(rows) == tile
    where = (jnp.zeros((rows, 1), jnp.int32),)
    tiles, at = llama.write_walk(jnp.ones((rows,), jnp.int32), 1, where)
    assert (tiles is None) == (tile >= rows) and (at is where) == (tiles is None)
    assert tiles is None or (tiles.tile == tile and at[0].shape == (rows,))


def test_the_check_tool_walks_toy_shapes_on_the_cpu():
    """``tools/kv_write_check.py --interpret``: both forms at toy shapes, the walk's
    pools the scatters' outside the trash slot (exit code 0)."""
    done = subprocess.run([sys.executable, str(ROOT / "tools/kv_write_check.py"), "--interpret"],
                          capture_output=True, text=True, cwd=ROOT, timeout=600,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(ln) for ln in done.stdout.splitlines() if ln.startswith("{")]
    assert {ln["form"] for ln in lines} >= {"scatter", "walk"}
    assert all(ln["equal"] for ln in lines if ln["form"] == "walk")
