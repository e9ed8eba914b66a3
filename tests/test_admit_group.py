"""Grouped admission (ISSUE 35): requests that wait together behind the static
prefix share ONE ``forward_paged`` at (``admit_rows``, bucket).

The engine's half: ``PagedDecodeEngine.prepare_admission`` (host, a request)
and ``admit_group`` (device, a group) leave what ``prefill_slot`` a request
leaves. (In a module of its own: ``tests/test_paged.py`` is one of
``conftest.SLOW_MODULES``, which tier-1 leaves out.)"""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_voice_agent.models.llama import LlamaConfig
from tpu_voice_agent.serve import ContinuousBatcher, PagedDecodeEngine
from tpu_voice_agent.serve import scheduler as sched
from tpu_voice_agent.serve.paged import kv_planes
from tpu_voice_agent.services.brain import install_prompt_prefix
from tpu_voice_agent.services.prompts import render_prompt
from tpu_voice_agent.utils import get_metrics
from tpu_voice_agent.utils.compilewatch import get_compile_watcher

TEXTS = ["search for laptops under 1000",
         "open the settings page, then turn on dark mode and go back to the start",  # bucket 64
         "go back", "take a screenshot of this page", "scroll down", "play some jazz",
         "upload my resume and submit"]
SLOTS = 32  # admit_rows = 4


def _model(model: str) -> dict:
    """The engine arguments that name a test-size model of each family
    (``tests/test_family_contract.py`` builds its engines from them too)."""
    if model == "dense":
        return dict(preset="test-tiny")
    if model == "routed":
        return dict(cfg=LlamaConfig(
            vocab_size=1024, dim=128, n_layers=2, n_heads=4, n_kv_heads=4, ffn_dim=64,
            max_seq_len=1536, n_experts=8, top_k=2, capacity_factor=4.0, norm_topk=False,
            qk_norm=True))
    if model == "hybrid":
        from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
        from tpu_voice_agent.models import sambay

        return dict(cfg=dataclasses.replace(sambay.PRESETS["sambay-test"], vocab_size=1024,
                                            max_seq_len=1536, window=384),
                    tokenizer=default_tokenizer())
    # "share": layers of two kinds, a parallel block, held experts, a tied head; "latent": a
    # latent cache, leading dense layers; "sparse": an indexer over planes by layer kind —
    # each at its file's rehearsal widths
    import json
    from pathlib import Path

    from benchmark.builders import cohere2moe_stack, dots3_stack, moonlight_stack, parse_stack

    name, stack = {"share": ("command-a-plus-05-2026-int8", cohere2moe_stack),
                   "latent": ("moonlight-16b-a3b-int8", moonlight_stack),
                   "sparse": ("dots3-note-prev-int8", dots3_stack)}[model]
    conf = json.loads((Path(__file__).parents[1] / f"benchmark/configs/{name}.json").read_text())
    run, serving = parse_stack.as_run(conf, True)
    cfg = stack.llama_config(run, {**serving, "site_context_tokens": 0})
    return dict(cfg=dataclasses.replace(cfg, max_seq_len=1536), quant=None)


def _engine(model: str) -> PagedDecodeEngine:
    kw = dict(max_len=1536, batch_slots=SLOTS, prefill_buckets=(128, 256, 1024), block_size=128,
              pool_blocks=96, fast_forward=8, **_model(model))
    if model == "hybrid":
        from benchmark.builders import sambay_stack

        eng = PagedDecodeEngine(quant="int8", init_weights=False, **kw)
        eng.load_params(sambay_stack.make_params(eng.cfg, 23))
    else:
        eng = PagedDecodeEngine(**kw)
    install_prompt_prefix(eng)
    return eng


@pytest.fixture(scope="module", params=["dense", "routed", "hybrid", "share"])
def pair(request):
    """Two engines of one model on the same weights: what the per-slot path
    leaves on one is compared with what the grouped path leaves on the other.
    Both see the same allocations and releases in the same order, case after
    case, so their allocators hand out the same blocks."""
    return request.param, _engine(request.param), _engine(request.param)


def _pool_bytes(eng):
    return np.asarray(kv_planes(eng.k_pool), np.float32), np.asarray(kv_planes(eng.v_pool), np.float32)


def _books(eng, n):
    return (np.asarray(eng.block_tables)[:n].tolist(), eng._covered[:n], eng._next_pos[:n],
            eng._slot_owned[:n], eng._slot_shared[:n], dict(eng.allocator._refs))


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9))


def _logits_and_first_tokens(logits, *a, **kw):
    """The batcher's pick, and the logits it picked from beside it."""
    return logits, sched._first_tokens_into_slots(logits, *a, **kw)


@pytest.mark.parametrize("n", [2, 3, 4])
def test_a_group_leaves_what_n_per_slot_admissions_leave(pair, n):
    """n in {2, A - 1, A} static-prefix admissions through
    ``prepare_admission`` + ``admit_group``: the same table rows, coverage,
    frontier, refcounts and owned / shared lists as n ``prefill_slot`` calls;
    K/V at the prompts' positions and the last-position logits equal to
    within rounding (a row's matmuls are tiled for another row count — on
    the CPU they come out bit-equal or a last bit apart); the first tokens
    identical; and rows the group does not fill write nothing: every pool
    block but the members' own and the trash block keeps its bytes, every
    slot outside the group its recurrent state."""
    model, one, grp = pair
    A = grp.admit_rows
    assert A == 4 == one.admit_rows and n <= A
    ids = [one.tokenizer.encode(render_prompt(t, {}), bos=True) for t in TEXTS[:n]]
    # a short and a long suffix: one bucket where admissions are grouped
    assert min(len(i) for i in ids) - 879 <= 32 < max(len(i) for i in ids) - 879
    assert one.suffix_buckets == (64,) and one._suffix_bucket(8, 64) == 64
    state = lambda: (jnp.full((SLOTS,), one.pad_id, jnp.int32), jnp.zeros((SLOTS,), jnp.int32),
                     jnp.full((SLOTS,), 7, jnp.int32), jnp.zeros((SLOTS,), jnp.int32),
                     jnp.zeros((SLOTS,), jnp.int32), jnp.zeros((SLOTS,), bool))
    consts = (jnp.full((1,), one.fsm.start, jnp.int32), jnp.float32(0.7), jnp.int32(24))
    kw = dict(greedy=True, constrained=True, kernels=one.kernels, rules=None,
              logit_mask=one.logit_mask)
    # the per-slot path, on ``one``
    logits_one, st, rng = [], state(), jax.random.PRNGKey(0)
    for slot, i in enumerate(ids):
        lg = one.prefill_slot(i, slot)
        logits_one.append(np.asarray(lg[0], np.float32))
        st, rng = sched._first_token_into_slot(lg, st, rng, jnp.int32(slot), jnp.int32(len(i)),
                                               *consts, one.tables, **kw)
    # the grouped path, on ``grp``
    before = _pool_bytes(grp)
    hybrid_before = (np.asarray(grp.k_pool["conv"]), np.asarray(grp.v_pool["ssm"])) if grp.hybrid else None
    preps = [grp.prepare_admission(i, slot) for slot, i in enumerate(ids)]
    assert all(p is not None and p.cached == len(grp.prefix_ids) == 879 for p in preps)
    out = grp.admit_group(preps, pick=_logits_and_first_tokens, state=state(),
                          pick_args=(jax.random.PRNGKey(0), *consts, grp.tables, grp.logit_mask),
                          pick_kw=tuple((k, v) for k, v in kw.items() if k != "logit_mask"))
    logits_grp, (st_g, _) = out.picked
    assert logits_grp.shape[:2] == (A, 1) and out.logits is None
    assert [(r.slot, r.rows, r.width, r.cached_tokens, r.bucket) for r in out.records] == [
        (s, n, A, 879, 64) for s in range(n)]
    assert _books(one, n) == _books(grp, n)
    for a, b in zip(st, st_g):  # cur, fsm, pos, nbytes, tokens_left, active: all 32 slots
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.asarray(st_g[5]).sum() == n and np.asarray(st_g[2])[:n].tolist() == [len(i) for i in ids]
    for slot in range(n):
        assert _rel(logits_grp[slot, 0], logits_one[slot]) < 2e-2
    bs, after, ref = grp.block_size, _pool_bytes(grp), _pool_bytes(one)
    mine = {b for s in range(n) for b in grp._slot_owned[s]}
    for plane in (0, 1):
        for slot, i in enumerate(ids):
            (first, *_), R = grp._slot_owned[slot], len(grp.prefix_ids) % bs
            got, want = (p[plane][:, first, : R + len(i) - 879] for p in (after, ref))
            assert np.abs(want).max() > 0 and _rel(got, want) < 2e-2
            # the prefix's sub-block tail is a copy: bit-equal
            assert np.array_equal(got[:, :R], want[:, :R])
        others = [b for b in range(after[plane].shape[1]) if b not in mine and b != 0]
        assert np.array_equal(after[plane][:, others], before[plane][:, others])
    if grp.hybrid:
        conv, ssm = np.asarray(grp.k_pool["conv"]), np.asarray(grp.v_pool["ssm"])
        assert np.array_equal(conv[:, n:], hybrid_before[0][:, n:])
        assert np.array_equal(ssm[:, n:], hybrid_before[1][:, n:])
        assert _rel(conv[:, :n], np.asarray(one.k_pool["conv"])[:, :n]) < 2e-2
        assert _rel(ssm[:, :n], np.asarray(one.v_pool["ssm"])[:, :n]) < 2e-2
        assert np.abs(ssm[:, :n]).max() > 0
    for eng in (one, grp):
        for slot in range(n):
            eng.release_slot(slot, ok=False)
    assert _books(one, n) == _books(grp, n)


def _one_row_prefill_text(eng) -> str:
    """The lowered text (scope names in, Python frames out: what the compile
    cache keys on) of the (1, 64) suffix prefill ``prefill_slot`` dispatches
    for a short and for a long suffix: one program."""
    from tpu_voice_agent.serve import paged

    texts, forward = [], paged.forward_paged

    def spy(*a, **kw):
        frames = jax.config.jax_traceback_in_locations_limit
        jax.config.update("jax_traceback_in_locations_limit", 0)
        try:
            texts.append(forward.__wrapped__.lower(*a, **kw).as_text(debug_info=True))
        finally:
            jax.config.update("jax_traceback_in_locations_limit", frames)
        return forward(*a, **kw)

    paged.forward_paged = spy
    try:
        for slot, t in enumerate(TEXTS[:2]):
            eng.prefill_slot(eng.tokenizer.encode(render_prompt(t, {}), bos=True), slot)
    finally:
        paged.forward_paged = forward
        for slot in (0, 1):
            eng.release_slot(slot, ok=False)
    assert len(texts) == 2 and texts[0] == texts[1] and "tensor<1x64xi32>" in texts[0]
    return texts[0]


# sha256 of the lowered text of the one-row suffix prefill at bucket 64 that
# ``prefill_slot`` dispatches on this module's four engines, as the PARENT of
# ISSUE 35 (commit 857ed88) lowers it (its (1, 32) program is not dispatched
# where admissions are grouped: ``suffix_buckets``): ``parse_solo`` and every
# lone admission still run these programs (what the entry points' compile
# cache keys on, so a chip run LOADS the parent's executables). A PR that
# changes ``forward_paged`` on purpose re-derives them (``_one_row_prefill_text``
# on its parent's tree) and says so. ISSUE 58 re-derived all four, each held on
# its parent's tree (40ebd89) first: the covered blocks leave the pool in ONE
# gather on (plane, block) (``llama.gather_row_blocks``) where a ``dynamic_slice``
# of the whole plane stood before the gather, for K and for V — the only ops
# that moved, in every text. ISSUE 60 re-derived all four (each held by the
# driver's run of its parent's tree, adb1d6a): the K/V write is
# ``llama.write_rows`` — told no real positions (the dense, routed and "share"
# kinds' admissions) the SAME pair of scatters, K's and V's issued before the
# reshapes back (the "share" kind's (block, offset) made once a forward, not a
# layer); the hybrid's admission is told and walks tiles of its real rows.
ONE_ROW_SHA256 = {
    "dense": "ead99bde7608d5c63381a639ff0449781ef6e6df8a9d6cdedb5cd87e715494ad",
    "routed": "888881fc064f8dda83f27a6bfc880a0ba5015543c71399297a5282209792c55e",
    "hybrid": "ee3c33c14a3cd7bed5fa393bfc3c280e7e8eea64f42ecb225c4f9b2146b7aee9",
    "share": "61edb0c62eb00a1690472a813c135b1a1afefbba03ffbcfd4e554ce5fa7ddf21",
}


def test_the_one_row_prefill_programs_are_the_parents(pair):
    """The (1, bucket) prefill program lowers to the parent's text, through
    ``prefill_slot`` and through a group of ONE (``admit_group`` runs
    ``prefill_slot``'s launches for it): the grouped path added arguments to
    no call of the per-slot one."""
    model, one, grp = pair
    assert hashlib.sha256(_one_row_prefill_text(one).encode()).hexdigest() == ONE_ROW_SHA256[model]

    def lone(ids, slot):
        return grp.admit_group([grp.prepare_admission(ids, slot)]).logits

    real, grp.prefill_slot = grp.prefill_slot, lone
    try:
        text = _one_row_prefill_text(grp)
    finally:
        grp.prefill_slot = real
    assert hashlib.sha256(text.encode()).hexdigest() == ONE_ROW_SHA256[model]


def _chunk_program_shas(eng) -> list[str]:
    """sha256 of the lowered text (scope names in, Python frames out) of every
    chunk program a batcher dispatches while it serves more requests than the
    compacted width holds, then one alone: the full width's, the compacted one's."""
    from tpu_voice_agent.serve import paged

    texts, loop = {}, paged.paged_chunk_decode_loop

    def spy(*a, **kw):
        rows = len(kw["rows_idx"]) if "rows_idx" in kw else a[4].shape[0]  # the program's width
        if rows not in texts:
            frames = jax.config.jax_traceback_in_locations_limit
            jax.config.update("jax_traceback_in_locations_limit", 0)
            try:
                texts[rows] = loop.__wrapped__.lower(*a, **kw).as_text(debug_info=True)
            finally:
                jax.config.update("jax_traceback_in_locations_limit", frames)
        return loop(*a, **kw)

    paged.paged_chunk_decode_loop = spy
    try:
        bat = ContinuousBatcher(eng, chunk_steps=4, max_new_tokens=12)
        outs = [o for texts_ in (TEXTS + TEXTS[:5], TEXTS[:1])
                for o in bat.generate_many([render_prompt(t, {}) for t in texts_])]
    finally:
        paged.paged_chunk_decode_loop = loop
    assert all(o.error is None for o in outs)
    assert sorted(texts) == [eng.compact_rows, eng.batch_slots]
    return [hashlib.sha256(texts[rows].encode()).hexdigest() for rows in sorted(texts, reverse=True)]


# the chunk programs of this module's four engines — the kinds of the four
# configurations the benchmark held before ISSUE 38 — at the full and at the
# compacted width, as the PARENT of ISSUE 38 (commit e3ab964) lowers them: a
# model with a LATENT cache compiles a variant of its own
# (``paged_chunk_decode_loop``'s ``lat``), and every other model's program
# is the text it was. A PR that changes the chunk loop on purpose re-derives
# them (``_chunk_program_shas`` on its parent's tree) and says so. ISSUE 41
# did, for the FULL width of the dense, routed and parallel-block kinds alone
# (both position-wise regions of a layer run packed there, two conditionals a
# layer in ``llama.forward_paged``); the compacted width (72 positions <= 96), the
# hybrid's both widths and the latent model's both (taken on ISSUE 41's
# parent, commit 4349cdf: it packs its MLPs alone, ``llama.packed_ffn`` on the
# ``FfnPack`` the other models' two regions share) are unedited. ISSUE 48
# re-derived all ten: every chunk program carries a third attention count
# (``attn.common_query_rows``), and a plain model's 1 + W block is told
# ``n_real`` at BOTH widths, for the block kernel's common pass to pack (the
# grouped admission's and the one-row prefill's texts above did not move).
# ISSUE 49 re-derived the latent model's COMPACTED width alone: its 1 + W block
# is told ``n_real`` there too (``Family.block_real``), for the latent kernel
# to pack — an argument this module's XLA engines do not read; the full width,
# which was told already, is the text it was. ISSUE 50 re-derived the "share"
# model's two alone: at this module's max_len of 1536 its rehearsal window of 16
# BINDS, and a plain model whose window binds carries one more count
# (``llama.WINDOW_STATS``: the row-blocks its windowed layers walk, of those
# held); the Command A+ CELL serves max_len 1536 under the published window of
# 4096, which cannot bind, and its programs are the texts they were. Every other
# pin here, in ``tests/test_older_programs_pinned.py`` and in ``tests/test_dots3.py``
# holds as it was: with ``router_input`` and ``gate_act`` at their defaults nothing moved.
# ISSUE 51 re-derived the hybrid's two and the "share" model's two, each taken on
# its parent's tree (dc7f00a) first, where all four held: the programs whose
# window binds carry one more count (``attn.window_common_row_blocks``: fifth of
# ``sambay.HYBRID_STATS``, third of ``llama.WINDOW_STATS`` — the row-blocks the
# block kernel's common RANGE took off the rows' walks), and the hybrid's
# ``attn.common_query_rows`` adds the positions handed to that range. This
# module's engines attend through XLA: the kernel's own text is in none of them.
# The dense, routed and latent kinds' six, the grouped admissions, the one-row
# prefills and blocks of all five hold UNEDITED. ISSUE 56 re-derived the FULL
# width of the routed, the "share" and the latent kinds, each held on its
# parent's tree (0971bae) first: a packed region tells its routed block how
# many of its rows are real (``FfnPack.n_rows`` -> ``_moe_ffn_grouped(n_rows=…)``:
# one ``where`` on the picks, and the mask a share's absent pick already had),
# so a filler row goes to no expert. The dense and the hybrid kinds' four, the
# three compacted widths (72 positions <= 96: nothing packs), the grouped
# admissions, the one-row prefills and blocks of all five, ``tests/test_olmoe.py``'s
# two (2 slots), ``tests/test_ffn_pack.py``'s three (no ``n_real``) and every pin
# of ``dots3``, ``nemotron_h`` and ``olmo_hybrid`` hold UNEDITED. ISSUE 58
# re-derived all ten, each held on its parent's tree (40ebd89) first: this
# module's engines attend through XLA, so their 1 + W block runs the branch an
# admission runs (``kv_gather``), and that branch gathers (plane, block) out of
# the pool in one op (``llama.gather_row_blocks``) where it sliced the plane
# first. The chip's chunk programs go through the block kernel and hold no
# such branch: theirs are the texts they were. ISSUE 60 re-derived all ten
# (each held by the driver's run of its parent's tree, adb1d6a): every chunk
# program carries one more count (``kv.rows_written``) and its 1 + W block's
# K/V write walks tiles of the real rows (``llama.write_rows``).
CHUNK_SHA256 = {
    "dense": ["715920e5f63af8fd588662720307e6b544fe9ea0ea6f4a87e402948e61f51bae",
              "e9f530c32a04113bbbcea4210d685b8f3170ddd3bed80a78bc909aeed7649bf4"],
    "routed": ["80badec7520045d49ec39c94e779929c4ee54c4152ad0ab07600c83f46ca6981",
               "04e819b74bcd38686278fd5a231c9d5aad0ae2ab38d70de56c3ad0d2022d86ec"],
    "hybrid": ["61590178b262c90714d9afbf5c356332061ca1b720523e97f82847d2f0cc3a25",
               "16eafb49e588d50fd58dbae07e95a887cad025ce3ec17bb738c6ee1a7dbdf7e0"],
    "share": ["fe8ea32f9f7ffc84cc6bc7c14daee28cfa86428517c389046cafd8ba9d228a39",
              "a68822cc133b6a92415367512c1674a84c1a6321857f2cb5d21f914680e227ce"],
    "latent": ["69e9841980a745b3f23c75e02409575c8d1efeeb8051368c8b0780e5a7bbaf0f",
               "3fd94d97143fc35e06d8e372168848af25524ba575eb9ffddfb325d613eb036f"],
}


def test_the_chunk_programs_are_the_parents(pair):
    model, one, _ = pair
    assert _chunk_program_shas(one) == CHUNK_SHA256[model]


def test_the_latent_chunk_programs_are_the_parents():
    """A model with a latent cache (``models/mla.py``: the benchmark's
    ``moonlight-16b-a3b-int8`` at its rehearsal widths) packs its MLPs alone
    (``llama.packed_ffn``), on the ``FfnPack`` the other models pack both
    regions with: at 32 slots its full-width chunk program holds that branch
    and lowers to ISSUE 56's text (the branch tells its experts ``n_rows``;
    ISSUE 41's parent's until then); the compacted width to ISSUE 49's (told
    ``n_real``)."""
    eng = _engine("latent")
    assert eng.latent and eng.compact_rows * 9 <= eng.ffn_pack_rows < SLOTS * 9
    assert _chunk_program_shas(eng) == CHUNK_SHA256["latent"]


@pytest.mark.parametrize("model", ["dense", "hybrid"])
def test_warmup_leaves_no_grouped_shape_uncompiled(model):
    """After ``warmup()`` — which RUNS the grouped admission and leaves
    nothing behind — steps that admit 2, A and A + 1 requests
    together compile nothing, by the compile watcher's count and by JAX's own
    ``backend_compile`` events."""
    eng = _engine(model)
    bat = ContinuousBatcher(eng, chunk_steps=4, max_new_tokens=8)
    calls0 = get_metrics().counter_state()[0].get("admit.calls", 0.0)
    bat.warmup()
    # one grouped call beside the per-slot walk and the lone request's
    assert get_metrics().counter_state()[0]["admit.batched_rows"] >= 2
    assert get_metrics().counter_state()[0]["admit.calls"] - calls0 >= 2
    assert not bat._active_h.any() and not any(eng._slot_owned) and not bat.results and not bat.pending
    assert eng.allocator.blocks_in_use == len(eng._prefix_blocks[0])
    watched, compiled = get_compile_watcher().state()["compiles"], []
    listener = lambda ev, _d, **_kw: compiled.append(ev) if ev.endswith(
        "backend_compile_duration") else None
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        for n, texts in ((2, TEXTS[2:4]), (4, TEXTS[:4]), (5, TEXTS[:5])):  # short suffixes, a long one, a lone one left over
            rids = [bat.submit(render_prompt(t, {})) for t in texts]
            bat.run_until_done()
            assert all(bat.results.pop(r).error is None for r in rids)
    finally:
        jax._src.monitoring.unregister_event_duration_listener(listener)
    assert not compiled and get_compile_watcher().state()["compiles"] == watched
