"""The position-wise work of a fast-forward block runs on its real positions
(ISSUES 37, 41).

A (B, 1 + W) block holds ``1 + k_b`` real positions a live row and copies of
the last one behind them; ``forward_paged`` told ``n_real`` and a packed
width P gathers the real ones into (1, P, ...) and runs BOTH regions of every
layer on those — norm, q/k/v, rotary; output projection, residuals, MLP — the
residual staying packed from layer to layer, q/k/v read back into the layout
the attention call and the K/V write keep (``llama.FfnPack``), every
position its slot once, after the last layer. What it leaves — logits at
the real positions, the K/V pool at the real AND the padded ones — is what the
full-width regions leave; more real positions than P take the full-width
branches of the same program; a call without ``n_real`` is the parent's
program, text for text."""

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_voice_agent.models import llama
from tpu_voice_agent.models.llama import LlamaConfig, forward_paged
from tpu_voice_agent.serve import ContinuousBatcher, PagedDecodeEngine
from tpu_voice_agent.serve.paged import FFN_PACK_ROWS
from tpu_voice_agent.services.brain import install_prompt_prefix
from tpu_voice_agent.services.prompts import render_prompt
from tpu_voice_agent.utils import get_metrics

MODELS = ["dense", "routed", "share"]
SLOTS, W = 8, 8  # a block of 72 positions: no wider than the engine's ``FFN_PACK_ROWS``, so
PACK = 24  # the packed width is put on it here, a third of the block as 96 is of the cells' 288
TEXTS = ["search for laptops under 1000", "go back", "take a screenshot of this page",
         "scroll down", "play some jazz", "upload my resume and submit",
         "open the settings page, then turn on dark mode and go back to the start"]


def _float32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a, tree)


def _engine(model: str, prefix: bool = False, float32: bool = False) -> PagedDecodeEngine:
    kw = dict(max_len=1536, batch_slots=SLOTS, prefill_buckets=(128, 256, 1024), block_size=128,
              pool_blocks=8 * SLOTS + 8, fast_forward=W)
    if model == "dense":
        eng = PagedDecodeEngine(preset="test-tiny", **kw)
    elif model == "routed":
        eng = PagedDecodeEngine(cfg=LlamaConfig(
            vocab_size=1024, dim=128, n_layers=2, n_heads=4, n_kv_heads=4, ffn_dim=64,
            max_seq_len=1536, n_experts=8, top_k=2, capacity_factor=4.0, norm_topk=False,
            qk_norm=True), **kw)
    else:
        # "share": layers of two kinds, a parallel block, shared + held experts, a tied head;
        # "ahead": a router that reads the layer's input, a window that binds; "latent": a latent
        # cache, leading dense layers, its MLPs packed alone — each at its file's rehearsal widths
        import json
        from pathlib import Path

        from benchmark.builders import cohere2moe_stack, moonlight_stack, parse_stack, smallthinker_stack

        name, stack = {"share": ("command-a-plus-05-2026-int8", cohere2moe_stack),
                       "ahead": ("smallthinker-21b-a3b-int8", smallthinker_stack),
                       "latent": ("moonlight-16b-a3b-int8", moonlight_stack)}[model]
        conf = json.loads((Path(__file__).parents[1] / f"benchmark/configs/{name}.json").read_text())
        run, serving = parse_stack.as_run(conf, True)
        cfg = stack.llama_config(run, {**serving, "site_context_tokens": 0})  # (no site context left behind)
        eng = PagedDecodeEngine(cfg=dataclasses.replace(cfg, max_seq_len=1536), quant=None, **kw)
    if float32:  # weights and pools: a served plan then turns on no rounding
        eng.params, eng.k_pool, eng.v_pool = _float32(eng.params), _float32(eng.k_pool), _float32(eng.v_pool)
    if prefix:
        install_prompt_prefix(eng)
    return eng


def _rel(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(float(np.max(np.abs(want))), 1e-30))


def _block(eng, n_real, seed: int = 3):
    """A (rows, 1 + W) block as ``ff_body`` builds it — row b's positions
    past its ``n_real[b]`` hold copies of its last real one; a row of 0 is
    idle (write mask off, parked at position 0) — over pools of seeded K/V,
    each row behind 200 positions of its own blocks. -> the call's
    arguments, the pools as fresh copies (they are donated)."""
    rng = np.random.default_rng(seed)
    n_real = np.asarray(n_real, np.int32)
    B, T, bs = len(n_real), 1 + W, eng.block_size
    live = n_real > 0
    iw = np.minimum(np.arange(T)[None, :], np.maximum(n_real[:, None] - 1, 0))
    tokens = np.take_along_axis(rng.integers(3, 600, size=(B, T)), iw, axis=1)
    positions = np.where(live[:, None], 200 + 7 * np.arange(B)[:, None] + iw, 0)
    tables = np.zeros((B, eng.max_blocks), np.int32)
    tables[:, :3] = 1 + 3 * np.arange(B)[:, None] + np.arange(3)[None, :]

    def seeded(pool, k):
        return jax.tree.map(lambda a: (jax.random.normal(jax.random.PRNGKey(k), a.shape, jnp.float32)
                                       * 0.3).astype(a.dtype), pool)

    pools = lambda: (seeded(eng.k_pool, 11), seeded(eng.v_pool, 12))
    one_head = bool(eng.cfg.layer_types)
    kw = dict(attn_impl="xla", write_mask=jnp.asarray(live),
              **({"logit_pos": jnp.asarray(np.maximum(n_real - 1, 0))} if one_head else {}))
    args = (eng.params, eng.cfg, jnp.asarray(tokens, jnp.int32), jnp.asarray(positions, jnp.int32))
    return args, pools, jnp.asarray(tables), kw, one_head


def _flat(pool):
    return [np.asarray(x, np.float32) for x in jax.tree.leaves(pool)]


@pytest.fixture(scope="module", params=MODELS)
def engine(request):
    return request.param, _engine(request.param)


# rows of k = 0, of k = W, idle rows, and chains between: 1+1+9+0+3+2+0+1 = 17 of 72
FITS = [1, 1, 1 + W, 0, 3, 2, 0, 1]
OVERFLOWS = [1 + W, 1 + W, 1 + W, 0, 1 + W, 3, 2, 1]  # 42 > PACK


def _in_float32(args, pools):
    """The call's weights and pools in float32: no rounding to tell apart."""
    return (_float32(args[0]), *args[1:]), lambda: _float32(pools())


@pytest.mark.parametrize("precision", ["bfloat16", "float32"])
@pytest.mark.parametrize("case", ["fits", "overflows"])
def test_the_packed_regions_leave_what_the_full_ones_leave(engine, case, precision):
    """Logits at the real positions, the pools whole (the K/V every real AND
    every padded position wrote) and ``FFN_STATS``, packed against full. In
    float32 the two are one arithmetic (1e-5); in bfloat16 a packed region's
    inputs and outputs are buffers, where the full path may fuse a rounding
    away (on the CPU the residual stays float32 from layer to layer): 2e-2.
    Past P real positions the full-width branches run: the computation it was
    — bit for bit, but where a parallel block's one norm fed both regions
    from one buffer and now each branch makes its own."""
    model, eng = engine
    n_real = FITS if case == "fits" else OVERFLOWS
    P = PACK
    assert P < SLOTS * (1 + W) <= eng.ffn_pack_rows == FFN_PACK_ROWS
    args, pools, tables, kw, one_head = _block(eng, n_real)
    if precision == "float32":
        args, pools = _in_float32(args, pools)
    n = jnp.asarray(n_real, jnp.int32)
    want = forward_paged(*args, *pools(), tables, **kw)
    got = forward_paged(*args, *pools(), tables, **kw, n_real=n, ffn_pack=P)
    assert len(got) == len(want) + 1
    fits = sum(n_real) <= P
    assert np.asarray(got[-1]).tolist() == [int(fits), P if fits else SLOTS * (1 + W)]
    real = np.arange(1 + W)[None, :] < np.asarray(n_real)[:, None]
    lw, lg = np.asarray(want[0], np.float32), np.asarray(got[0], np.float32)
    pick = (lambda x: x[np.asarray(n_real) > 0, 0]) if one_head else (lambda x: x[real])
    assert np.abs(pick(lw)).max() > 0
    if precision == "float32":
        tol = 1e-5
    else:
        tol = 2e-2 if fits or model == "share" else 1e-6
    assert _rel(pick(lg), pick(lw)) < tol
    assert np.array_equal(np.argmax(pick(lg), -1), np.argmax(pick(lw), -1))
    # the pools whole but the trash block (0: idle rows park there whatever
    # they hold): the real positions' K/V, the duplicates of a row's last real
    # position (equal values under one index)
    for pool_w, pool_g in zip(want[1:3], got[1:3]):
        for w_, g_ in zip(_flat(pool_w), _flat(pool_g)):
            if w_.ndim == 5:  # (planes, blocks, block_size, heads, width)
                w_, g_ = w_[:, 1:], g_[:, 1:]
            assert _rel(g_, w_) < tol


def test_a_packed_region_hands_every_position_its_rows():
    """``FfnPack.rows`` / ``.block`` and ``packed_ffn`` on a position-wise
    region by hand: real positions get their own rows, a padded one its row's
    LAST real position's (what the K/V write needs: one index, one value), a
    per-position table gathered by the same index lines up with the rows, what
    else the region returns passes through, and past P the whole region runs."""
    T, P = 1 + W, 32
    x = jax.random.normal(jax.random.PRNGKey(0), (SLOTS, T, 6), jnp.float32)
    table = jnp.arange(SLOTS * T, dtype=jnp.float32).reshape(SLOTS, T, 1)
    region = lambda x, n_rows=None: (x * 2, jnp.float32(3))  # (told the packed rows that are real)
    whole = x * 2 + table
    for n_real in (FITS, OVERFLOWS):
        pack = llama.ffn_pack_index(jnp.asarray(n_real, jnp.int32), T, P)
        assert pack.rows(x).shape == (1, P, 6) and pack.block(pack.rows(x)).shape == x.shape
        y, rest = jax.jit(lambda x: llama.packed_ffn(region, x, pack))(x)
        by_hand = pack.block(region(pack.rows(x))[0] + pack.rows(table))
        assert float(rest) == 3 and y.shape == x.shape
        for r, k in enumerate(n_real):
            if sum(n_real) > P:  # the whole region: every position its own
                assert np.array_equal(y[r], x[r] * 2)
            elif k:
                last = np.minimum(np.arange(T), k - 1)
                assert np.array_equal(y[r], (x[r] * 2)[last]) and np.array_equal(by_hand[r], whole[r][last])
    assert llama.packed_ffn(region, x, None)[1] == 3  # no pack: the region itself


def test_a_padded_position_reads_its_rows_last_real_slot():
    n = jnp.asarray(FITS, jnp.int32)
    pack = llama.ffn_pack_index(n, 1 + W, 32)
    idx, inv = np.asarray(pack.idx), np.asarray(pack.inv)
    assert bool(pack.fits) and np.asarray(pack.stats).tolist() == [1, 32]
    total, T = sum(FITS), 1 + W
    # the real positions in row order, each once; a slot reads itself back
    want = [b * T + t for b, k in enumerate(FITS) for t in range(k)]
    assert idx[:total].tolist() == want
    for b, k in enumerate(FITS):
        for t in range(T):
            if k:
                assert idx[inv[b, t]] == b * T + min(t, k - 1)
            assert 0 <= inv[b, t] < 32
    assert ((0 <= idx) & (idx < SLOTS * T)).all()
    over = llama.ffn_pack_index(jnp.asarray(OVERFLOWS, jnp.int32), T, 32)
    assert not bool(over.fits) and np.asarray(over.stats).tolist() == [0, SLOTS * T]
    assert ((0 <= np.asarray(over.inv)) & (np.asarray(over.inv) < 32)).all()


# ---------------------------------------------------------------- the filler (ISSUE 56)

ROUTED = ["routed", "share", "ahead", "latent"]
CELL_PACK = FFN_PACK_ROWS  # 96 slots, as the cells serve; 12 rows x 9 positions = 108 are wider
REAL = {1: [0, 0, 1] + [0] * 9,  # k real positions in all, 96 - k slots of filler behind them
        40: [1 + W, 1, 5, 0, 3, 1 + W, 2, 1, 4, 0, 5, 1],
        96: [1 + W] * 10 + [6, 0]}
TOO_MANY = [1 + W] * 11 + [5]  # 104 > 96: the whole-width branches


class _Programs:
    """One routed engine's ``forward_paged`` over a 12-row block with
    ``moe_stats``, each variant under a jit of its OWN, traced while its
    patches stand on ``models.llama`` (the module's jit would hand back
    whichever variant it traced first for these shapes): ``told`` — this
    tree's program at P = 96; ``parent`` — the same with ``n_rows`` WITHHELD
    from the routed block, the packed path as ISSUE 56's parent ran it;
    ``alone(k)`` — packed at P = k slots, so no slot is filler, at P = 96's
    row tile: the k real rows dispatched alone."""

    def __init__(self, model: str):
        self.model, self.eng = model, _engine(model)
        cfg = self.eng.cfg
        self.names = llama.moe_stat_names(cfg)
        self.routed_layers = cfg.n_layers - cfg.first_dense_layers
        moe_ffn, tile = llama._moe_ffn, llama.moe_row_tile(CELL_PACK * cfg.top_k, cfg.n_experts)
        self.told = self._variant(CELL_PACK)
        self.parent = self._variant(CELL_PACK, _moe_ffn=lambda p, h, cfg, lat=None, n_rows=None, picks=None:
                                    moe_ffn(p, h, cfg, lat, None, picks))
        self.alone = functools.cache(lambda k: self.parent if k == CELL_PACK else self._variant(
            k, moe_row_tile=lambda assignments, n_experts: tile))
        self._runs = {}

    def _variant(self, P: int, **patches):
        fwd, cfg = forward_paged.__wrapped__.__wrapped__, self.eng.cfg

        @jax.jit
        def program(params, tokens, positions, k_pool, v_pool, tables, n_real, rest):
            return fwd(params, cfg, tokens, positions, k_pool, v_pool, tables, attn_impl="xla",
                       n_real=n_real, ffn_pack=P, moe_stats=True, **rest)

        def run(n_real):
            (params, _, tokens, positions), pools, tables, kw, _ = _block(self.eng, n_real)
            rest = {k: v for k, v in kw.items() if k != "attn_impl"}
            with pytest.MonkeyPatch.context() as mp:
                for name, value in patches.items():
                    mp.setattr(llama, name, value)
                return program(params, tokens, positions, *pools(), tables,
                               jnp.asarray(n_real, jnp.int32), rest)
        return run

    def run(self, which: str, n_real) -> dict:
        """{"logits", "pools", "moe" (by name), "ffn"} of one variant over one block, run once."""
        key = (which, tuple(n_real))
        if key not in self._runs:
            variant = self.alone(sum(n_real)) if which == "alone" else getattr(self, which)
            out = variant(n_real)
            live = np.asarray(n_real) > 0
            real = np.arange(1 + W)[None, :] < np.asarray(n_real)[:, None]
            logits = np.asarray(out[0], np.float32)
            self._runs[key] = {
                "logits": logits[live, 0] if self.eng.cfg.layer_types else logits[real],
                # the pools whole but the trash block (0: idle rows park there whatever they hold)
                "pools": [a[:, 1:] for pool in out[1:3] for a in _flat(pool)],
                "moe": dict(zip(self.names, map(int, out[5]), strict=True)), "ffn": np.asarray(out[6]).tolist()}
        return self._runs[key]


@pytest.fixture(scope="module", params=ROUTED)
def progs(request):
    return _Programs(request.param)


@pytest.mark.parametrize("k", sorted(REAL))
def test_a_packed_blocks_filler_goes_to_no_expert(progs, k):
    """k real positions and 96 - k slots of filler through ``forward_paged``
    with ``moe_stats``: the rows its routed layers computed, the experts with
    a row and the busiest expert's rows are those of the k real rows
    dispatched ALONE, and ``assigned_rows`` is k x K a routed layer — with
    picks the layer routes itself, picks made ahead on its input, a chip's
    share (its fifth count too) and a latent model's MLPs packed alone."""
    told, alone = progs.run("told", REAL[k]), progs.run("alone", REAL[k])
    assert sum(REAL[k]) == k and told["ffn"] == [1, CELL_PACK] and alone["ffn"] == [1, k]
    assert told["moe"] == alone["moe"]
    assert told["moe"]["assigned_rows"] == k * progs.eng.cfg.top_k * progs.routed_layers
    assert 0 < told["moe"]["load_max"] <= k * progs.routed_layers
    assert np.array_equal(np.argmax(told["logits"], -1), np.argmax(alone["logits"], -1))
    withheld = progs.run("parent", REAL[k])["moe"]  # the filler routed: every slot's K picks
    assert withheld["assigned_rows"] == CELL_PACK * progs.eng.cfg.top_k * progs.routed_layers
    assert k == CELL_PACK or withheld["load_max"] > told["moe"]["load_max"]


@pytest.mark.parametrize("k", sorted(REAL))
def test_the_real_positions_are_the_parents_bit_for_bit(progs, k):
    """Told ``n_rows`` or not, the real positions' logits and every K/V
    write outside the trash block are the SAME BITS, in the engines' bfloat16:
    a real row keeps its rank in its expert's run (the filler ranked behind
    it) and the kernel's row does not depend on its tile-mates."""
    told, parent = progs.run("told", REAL[k]), progs.run("parent", REAL[k])
    assert told["logits"].size and np.abs(told["logits"]).max() > 0
    assert np.array_equal(told["logits"], parent["logits"])
    assert len(told["pools"]) == len(parent["pools"]) >= 2
    for ours, theirs in zip(told["pools"], parent["pools"]):
        assert np.array_equal(ours, theirs)


def test_the_whole_width_branch_counts_what_it_counted(progs):
    """More real positions than slots: the whole-width branches of the same
    program run — counts of the packed branch's SHAPE (four; a share's five),
    the numbers and the bits the parent's program returned: every position
    of the block assigned, filler or not."""
    told, parent = progs.run("told", TOO_MANY), progs.run("parent", TOO_MANY)
    assert told["ffn"] == [0, len(TOO_MANY) * (1 + W)]
    assert list(told["moe"]) == list(progs.names) == list(progs.run("told", REAL[40])["moe"])
    assert told["moe"] == parent["moe"]
    assert told["moe"]["assigned_rows"] == len(TOO_MANY) * (1 + W) * progs.eng.cfg.top_k * progs.routed_layers
    assert np.array_equal(told["logits"], parent["logits"])
    for ours, theirs in zip(told["pools"], parent["pools"]):
        assert np.array_equal(ours, theirs)


# sha256 of the lowered text (scope names in, Python frames out: what the
# compile cache keys on) of ``forward_paged`` over ``_block`` WITHOUT
# ``n_real`` as the PARENT of ISSUE 37 (commit 0cf90c6) lowers it: admission,
# refcheck, spec decode's verify block, the T = 1 body and the compacted
# chunk trace the program they traced. ISSUE 58 re-derived the three (they held
# on its parent's tree, 40ebd89): these engines attend through XLA, where the
# block's covered blocks leave the pool in one gather on (plane, block)
# (``llama.gather_row_blocks``), no slice of the plane before it. ISSUE 60
# re-derived the three (they held in the driver's run of its parent's tree,
# adb1d6a): without ``n_real`` the K/V write is the same pair of scatters,
# issued through ``llama.write_rows`` (both before the reshapes back).
PARENT_SHA256 = {
    "dense": "d756397fb03ec282da89c59974ff84a51bd22aa22c33ef4124d7ca3f9a59986c",
    "routed": "e942cbc59ecb3c90ed8fca7ad7a3b512a61e1d850db901099fb418763078e8e0",
    "share": "53a63a11ed81c63de41a82fa84702910c708c6bf0a3e1309428299e37edb5939",
}


def _lowered_sha(eng) -> str:
    args, pools, tables, kw, _ = _block(eng, FITS)
    frames = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        text = forward_paged.__wrapped__.lower(*args, *pools(), tables, **kw).as_text(debug_info=True)
    finally:
        jax.config.update("jax_traceback_in_locations_limit", frames)
    return hashlib.sha256(text.encode()).hexdigest()


def test_without_a_packed_width_the_program_is_the_parents(engine):
    model, eng = engine
    assert _lowered_sha(eng) == PARENT_SHA256[model]


@pytest.fixture(scope="module", params=MODELS)
def served(request):
    """One engine's plans at its derived packed width and with none in reach.
    The parallel-block model in float32: packed, each region makes its own
    copy of the block's one norm and holds its edges in buffers, and in
    bfloat16 a seeded router's near tie then falls the other way (the block
    test's 0.9 % of the largest logit) and a plan of random weights with it."""
    eng = _engine(request.param, prefix=True, float32=request.param == "share")
    derived, P = eng.ffn_pack_rows, PACK
    prompts = [render_prompt(t, {}) for t in TEXTS]
    runs = {}
    for name, rows in (("packed", P), ("full", SLOTS * (1 + W))):
        eng.ffn_pack_rows = rows
        before = dict(get_metrics().counter_state()[0])
        outs = ContinuousBatcher(eng, chunk_steps=8, max_new_tokens=48).generate_many(prompts)
        after = get_metrics().counter_state()[0]
        runs[name] = (outs, {k: after.get(k, 0.0) - before.get(k, 0.0) for k in after})
    eng.ffn_pack_rows = derived
    return request.param, eng, runs


def test_the_chunk_loop_gives_the_same_plans_packed_and_full(served):
    _, eng, runs = served
    (packed, _), (full, _) = runs["packed"], runs["full"]
    assert len(packed) == len(TEXTS)
    for a, b in zip(packed, full):
        assert a.token_ids == b.token_ids and a.text == b.text
        assert a.finished == b.finished


def test_the_batcher_publishes_the_packed_forwards_and_the_rows(served):
    _, eng, runs = served
    P, block = PACK, SLOTS * (1 + W)
    for name, (_, d) in runs.items():
        fwds, rows = d["scheduler.forwards"], d["scheduler.forward_rows"]
        packed = d.get("ffn.forwards_packed", 0.0)
        assert 0 <= packed <= fwds
        # a full-width chunk's forwards: P rows packed, the block otherwise; a
        # compacted chunk's (2 rows x 9 <= P) always its own block
        assert d["ffn.rows"] == P * packed + (1 + W) * rows - block * packed
        if name == "full":
            assert packed == 0
    assert runs["packed"][1]["ffn.forwards_packed"] > 0
    assert runs["packed"][1]["ffn.rows"] < runs["full"][1]["ffn.rows"]


def test_an_engine_of_the_cells_width_packs_on_its_own():
    """32 slots x 9 positions are wider than ``FFN_PACK_ROWS``: the engine's
    own chunk program has the branch and takes it, nothing put on it by hand;
    at 8 slots (every other engine of this file) it derives the same rows and
    its programs hold no branch."""
    eng = PagedDecodeEngine(preset="test-tiny", max_len=1536, batch_slots=32, block_size=128,
                            prefill_buckets=(128, 256, 1024), pool_blocks=8 * 32 + 8, fast_forward=W)
    install_prompt_prefix(eng)
    assert eng.compact_rows * (1 + W) <= eng.ffn_pack_rows == FFN_PACK_ROWS < 32 * (1 + W)
    before = dict(get_metrics().counter_state()[0])
    outs = ContinuousBatcher(eng, chunk_steps=8, max_new_tokens=24).generate_many(
        [render_prompt(t, {}) for t in TEXTS * 3])
    after = get_metrics().counter_state()[0]
    d = {k: after.get(k, 0.0) - before.get(k, 0.0) for k in after}
    assert all(o.error is None for o in outs)
    assert 0 < d["ffn.forwards_packed"] <= d["scheduler.forwards"]
    assert d["ffn.rows"] < (1 + W) * d["scheduler.forward_rows"]


def test_a_model_with_a_recurrent_state_is_asked_for_no_packed_width():
    """``models.sambay``'s MLPs have no packed branch: its engine derives no
    width (its programs are the ones they were) and its forward refuses one."""
    from benchmark.builders import sambay_stack
    from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
    from tpu_voice_agent.models import sambay

    cfg = dataclasses.replace(sambay.PRESETS["sambay-test"], vocab_size=1024, max_seq_len=1536,
                              window=384)
    eng = PagedDecodeEngine(cfg=cfg, tokenizer=default_tokenizer(), quant="int8", init_weights=False,
                            max_len=1536, batch_slots=SLOTS, prefill_buckets=(128, 256, 1024),
                            block_size=128, pool_blocks=8 * SLOTS + 8, fast_forward=W)
    assert eng.hybrid and eng.ffn_pack_rows == 0
    params = jax.eval_shape(lambda: sambay_stack.make_params(eng.cfg, 23))
    tok = jax.ShapeDtypeStruct((SLOTS, 1 + W), jnp.int32)
    with pytest.raises(NotImplementedError, match="no packed branch"):
        jax.eval_shape(lambda p, t, k, v: forward_paged(
            p, eng.cfg, t, t, k, v, jnp.zeros((SLOTS, eng.max_blocks + 1), jnp.int32),
            n_real=jnp.ones((SLOTS,), jnp.int32), ffn_pack=PACK), params, tok, eng.k_pool, eng.v_pool)


def test_the_metric_catalog_knows_the_counters():
    import sys
    from pathlib import Path

    root = Path(__file__).parents[1]
    sys.path.insert(0, str(root / "tools"))
    import metrics_lint

    reg = metrics_lint.scan_source(root / "tpu_voice_agent")
    assert list(reg["ffn.forwards_packed"]) == list(reg["ffn.rows"]) == ["counter"]
    # registered AND in the operator's catalog, under their type
    assert metrics_lint.main([str(root / "tpu_voice_agent"), str(root / "docs/OBSERVABILITY.md")]) == 0
