"""Continuous batching: concurrent slots must be isolated and all outputs
grammar-valid; batch composition must not change a greedy request's tokens."""

import functools

import pytest

from tpu_voice_agent.schemas import parse_response_from_json
from tpu_voice_agent.serve.scheduler import ContinuousBatcher


@pytest.fixture()
def batcher(tiny_batch_engine):
    return ContinuousBatcher(tiny_batch_engine, chunk_steps=16, max_new_tokens=300)


PROMPTS = [
    "search for laptops under 1000",
    "upload my resume and submit",
    "take a screenshot of this page",
]


def _assert_grammar_consistent(batcher, r):
    """Finished outputs must validate; truncated ones must be live DFA
    prefixes (the constraint never went off the rails mid-decode)."""
    if r.finished:
        model, err = parse_response_from_json(r.text)
        assert model is not None, f"finished slot failed schema: {err} :: {r.text[:100]}"
    else:
        state = batcher.engine.fsm.walk(r.token_ids)
        assert state >= 0, f"truncated slot left the grammar: {r.text[:100]}"


def test_batched_outputs_are_all_grammar_consistent(batcher):
    results = batcher.generate_many(PROMPTS)
    assert len(results) == 3
    for r in results:
        _assert_grammar_consistent(batcher, r)


def test_batch_composition_does_not_change_greedy_output(batcher):
    """Trash-slot isolation: a greedy request decodes identically whether it
    runs alone or alongside other slots."""
    solo = batcher.generate_many([PROMPTS[0]])[0]
    packed = batcher.generate_many(PROMPTS)[0]
    assert solo.token_ids == packed.token_ids


def test_more_requests_than_slots_queue_up(batcher):
    results = batcher.generate_many(PROMPTS + ["scroll down", "go back"])
    assert len(results) == 5
    for r in results:
        _assert_grammar_consistent(batcher, r)


def test_warmup_compiles_ahead_and_leaves_no_trace(batcher):
    """``warmup()`` is what the service mains run before they listen
    (services.warm_up): afterwards a request compiles nothing in the
    serving loop — a cold compile there runs under the stall watchdog —
    and the batcher is as clean as a fresh one (same tokens, no slot, no
    result, no queue left behind)."""
    from tpu_voice_agent.utils.compilewatch import get_compile_watcher

    batcher.warmup()
    assert not batcher.pending and not batcher.results
    assert all(sl.request_id < 0 for sl in batcher.slots)
    assert not batcher._active_h.any()

    compiles = get_compile_watcher().state()["compiles"]
    warmed = batcher.generate_many([PROMPTS[0]])[0]
    assert get_compile_watcher().state()["compiles"] == compiles
    _assert_grammar_consistent(batcher, warmed)
    fresh = ContinuousBatcher(batcher.engine, chunk_steps=16, max_new_tokens=300)
    assert fresh.generate_many([PROMPTS[0]])[0].token_ids == warmed.token_ids


@pytest.fixture(scope="module")
def tiny_paged_engine():
    from tpu_voice_agent.serve.paged import PagedDecodeEngine

    return PagedDecodeEngine(preset="test-tiny", max_len=1024, batch_slots=3,
                             prefill_buckets=(64, 128), radix_enable=False)


@pytest.mark.parametrize("greedy", [True, False], ids=["greedy", "sampled"])
@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_fused_admission_tail_equals_the_eager_writes(layout, greedy, request):
    """The admission tail is one jitted program (``_first_token_into_slot``);
    what it leaves behind is bit-equal to the sequence it replaced — the
    eager key split, the standalone ``_first_token`` and six eager
    ``.at[slot].set`` — on the same inputs, for the middle slot of a live
    batch whose batch-mates' entries stay as they were."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_voice_agent.serve.engine import _first_token
    from tpu_voice_agent.utils.steplog import get_steplog

    eng = request.getfixturevalue(
        "tiny_batch_engine" if layout == "dense" else "tiny_paged_engine")
    bat = ContinuousBatcher(eng, chunk_steps=4, greedy=greedy, temperature=0.7,
                            max_new_tokens=300)
    rids = [bat.submit(p) for p in PROMPTS]
    bat.step()  # three live rows, one chunk in
    assert bat._active_h.all()
    bat.cancel(rids[1])  # the middle slot frees; its batch-mates decode on
    state = ("cur", "fsm", "pos", "nbytes", "tokens_left", "active")
    before = {k: getattr(bat, k) for k in state}
    rng_before = bat._rng

    seen = {}
    prefill_slot = eng.prefill_slot

    def spy(ids, slot):
        seen["n"], seen["slot"] = len(ids), slot
        seen["logits"] = prefill_slot(ids, slot)
        return seen["logits"]

    bat.submit("open the settings page and turn on dark mode")
    rid, prompt = bat.pending.pop()
    eng.prefill_slot = spy
    timer = get_steplog().timer()
    try:
        bat._admit(1, rid, prompt, timer, 0.0)
    finally:
        timer.close()
        del eng.prefill_slot
    assert seen["slot"] == 1

    rng, k = jax.random.split(rng_before)
    tok0, fsm0 = _first_token(
        seen["logits"], jnp.full((1,), eng.fsm.start, dtype=jnp.int32),
        eng.tables, k, jnp.float32(0.7), greedy=greedy, constrained=True,
        kernels=eng.kernels, rules=eng.rules, logit_mask=eng.logit_mask)
    want = {"cur": before["cur"].at[1].set(tok0[0]),
            "fsm": before["fsm"].at[1].set(fsm0[0]),
            "pos": before["pos"].at[1].set(seen["n"]),
            "nbytes": before["nbytes"].at[1].set(0),
            "tokens_left": before["tokens_left"].at[1].set(300),
            "active": before["active"].at[1].set(True)}
    for key in state:
        got = getattr(bat, key)
        assert got.dtype == want[key].dtype and got.shape == want[key].shape
        assert np.array_equal(np.asarray(got), np.asarray(want[key])), key
        mates = np.asarray(got)[[0, 2]]
        assert np.array_equal(mates, np.asarray(before[key])[[0, 2]]), key
    assert np.array_equal(np.asarray(bat._rng), np.asarray(rng))
    # the admitted row decodes on from that state, beside its batch-mates
    bat._active_h[1] = True
    bat.run_until_done()
    for r in (bat.results[rids[0]], bat.results[rid], bat.results[rids[2]]):
        assert r.error is None
        _assert_grammar_consistent(bat, r)


# ------------------------------------------------- the chunk program's width
#
# ISSUE 29: a paged engine's chunk program has two widths — ``batch_slots``
# and the compacted ``compact_rows`` (a quarter of the slots: 2 of these 8) —
# and ``decode_chunk`` takes the one the batcher's live count allows.

@functools.lru_cache(maxsize=None)
def _wide(ff: int):
    """One 8-slot paged engine per fast-forward setting, built once."""
    from tpu_voice_agent.serve.paged import PagedDecodeEngine

    eng = PagedDecodeEngine(preset="test-tiny", max_len=1024, batch_slots=8, prefill_buckets=(64, 128),
                            radix_enable=False, fast_forward=ff)
    assert eng.compact_rows == 2
    return eng


@functools.lru_cache(maxsize=None)
def _plain(ff: int):
    """The un-paged engine with the same weights and fast-forward setting (a
    forced chain is emitted as the grammar spells it, so the setting is part
    of which tokens a greedy decode gives)."""
    from tpu_voice_agent.serve import DecodeEngine

    return DecodeEngine(preset="test-tiny", max_len=1024, prefill_buckets=(64, 128), fast_forward=ff)


WIDE_PROMPTS = PROMPTS + ["scroll down", "go back", "open the settings page", "sort by price"]
FF = pytest.mark.parametrize("ff", [0, 8], ids=["ff-off", "ff-on"])


@pytest.mark.parametrize("live,rows", [
    ([], None), ([5], [5, 0]), ([3, 6], [3, 6]), ([0, 1], [0, 1]), ([1, 3, 6], None),
    (list(range(8)), None)],
    ids=["none-live", "one-live", "R-live", "first-two", "R-plus-one", "all-live"])
def test_the_width_follows_the_live_count(live, rows):
    """``live <= R`` rides ``R`` rows — the live slots first, idle slots
    after them, none twice (the program scatters the rows back) — and one
    more live row takes the full width (``None``: the call as it always was)."""
    import numpy as np
    from types import SimpleNamespace

    from tpu_voice_agent.serve.paged import PagedDecodeEngine

    mirror = np.zeros((8,), dtype=bool)
    mirror[live] = True
    got = PagedDecodeEngine._rows_of(SimpleNamespace(compact_rows=2), mirror)
    assert (got is None) if rows is None else (got.dtype == np.int32 and got.tolist() == rows)
    assert PagedDecodeEngine._rows_of(SimpleNamespace(compact_rows=0), mirror) is None


@FF
def test_a_compacted_chunk_is_the_full_width_chunk_on_its_rows(ff):
    """ONE chunk from ONE state, dispatched at both widths: slots 3 and 6
    live, slot 5 idle but still owning its blocks (what a slot mid-way through
    a chunked admission is to the program), the rest released. Everything the
    scheduler reads back is equal, the idle slots' state and slot 5's pool
    blocks are bit-for-bit what they were, and the compacted program left
    them so by never touching them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    eng = _wide(ff)
    bat = ContinuousBatcher(eng, chunk_steps=8, max_new_tokens=64)
    rids = [bat.submit(p) for p in WIDE_PROMPTS]
    first = bat.step()  # slots 0-6 admitted, one full-width chunk in
    assert bat._active_h[:7].all() and first.rows == 8
    for slot in (0, 1, 2, 4):
        bat.cancel(rids[slot])
    live = np.zeros((8,), dtype=bool)
    live[[3, 6]] = True
    state = dict(cur=bat.cur, pos=bat.pos, fsm=bat.fsm, active=bat.active & jnp.asarray(live),
                 nbytes=bat.nbytes, tokens_left=bat.tokens_left)
    idle = [b for b in range(8) if not live[b]]
    pools = (np.asarray(eng.k_pool), np.asarray(eng.v_pool))
    book = (list(eng._next_pos), list(eng._covered))
    names = ("out", "n", "eos", "cur", "pos", "fsm", "active", "nbytes", "tokens_left")

    def chunk(**width):
        eng.k_pool, eng.v_pool = jnp.asarray(pools[0]), jnp.asarray(pools[1])
        eng._next_pos[:] = book[0]
        res = eng.decode_chunk(*state.values(), jax.random.PRNGKey(0), 0.7, 3900, 8, True, **width)
        got = dict({name: np.asarray(getattr(res, name)) for name in names + ("poison",)},
                   fwds=int(res.fwds), k=np.asarray(eng.k_pool), v=np.asarray(eng.v_pool))
        return got, res.rows

    full, rows_full = chunk()
    compact, rows_compact = chunk(live=live)
    assert (rows_full, rows_compact) == (8, 2) and full["fwds"] == compact["fwds"] > 0
    assert compact["n"][[3, 6]].min() > 0 and not compact["n"][idle].any()
    for key in names + ("poison",):
        assert np.array_equal(full[key], compact[key]), key
    for key in ("cur", "pos", "fsm", "nbytes", "tokens_left"):
        assert np.array_equal(compact[key][idle], np.asarray(state[key])[idle]), key
    parked = eng._slot_owned[5]
    assert parked and eng._covered == book[1]  # the second dispatch grew no table
    for got, was in ((compact["k"], pools[0]), (compact["v"], pools[1])):
        assert np.array_equal(got[:, parked], was[:, parked])
    written = eng._slot_owned[3] + eng._slot_owned[6]
    assert np.allclose(compact["k"][:, written], full["k"][:, written], atol=1e-5)
    assert not np.array_equal(compact["k"][:, written], pools[0][:, written])
    bat.reset()


@FF
def test_a_run_through_both_widths_compiles_nothing_and_keeps_its_tokens(ff, monkeypatch):
    """``warmup()`` RUNS both widths (its lone request rides the compacted
    one, then the full one for a forward), so staggered arrivals that take the batcher R → B → R compile
    nothing; ``scheduler.forward_rows`` over ``scheduler.forwards`` and the
    step ledger's ``rows`` read the width of each chunk; and the tokens are
    the un-paged ``DecodeEngine``'s."""
    from tpu_voice_agent.utils import get_metrics
    from tpu_voice_agent.utils.compilewatch import get_compile_watcher
    from tpu_voice_agent.utils.steplog import get_steplog

    eng = _wide(ff)
    bat = ContinuousBatcher(eng, chunk_steps=8, max_new_tokens=40)
    chunks, decode_chunk = [], eng.decode_chunk
    monkeypatch.setattr(eng, "decode_chunk", lambda *a, **kw: chunks.append(decode_chunk(*a, **kw)) or chunks[-1])
    bat.warmup()
    # its last dispatch ran the FULL width for one real forward, and it left nothing behind
    assert [c.rows for c in chunks] == [2, 8] and int(chunks[-1].fwds) == 1
    assert not bat._active_h.any() and not any(eng._slot_owned) and not bat.results
    compiles = get_compile_watcher().state()["compiles"]
    widths = []

    def step():
        before = dict(get_metrics().counter_state()[0])
        res = bat.step()
        after = get_metrics().counter_state()[0]
        fwds, rows = (after.get(k, 0.0) - before.get(k, 0.0)
                      for k in ("scheduler.forwards", "scheduler.forward_rows"))
        assert fwds > 0 and get_steplog().last()["rows"] == rows / fwds == res.rows
        widths.append(int(rows / fwds))

    rids = [bat.submit(p) for p in PROMPTS[:2]]
    step()  # two live = R
    rids.append(bat.submit(PROMPTS[2]))
    step()  # three live = R + 1
    while any(sl.request_id >= 0 for sl in bat.slots):
        step()
    assert widths[:2] == [2, 8] and widths[-1] == 2 and set(widths) == {2, 8}
    assert get_compile_watcher().state()["compiles"] == compiles
    for rid, prompt in zip(rids, PROMPTS):
        got = bat.results[rid]
        assert got.error is None
        assert got.token_ids == _plain(ff).generate(prompt, max_new_tokens=40, greedy=True).token_ids


def test_a_sampled_chunk_keeps_the_full_width():
    """Non-greedy decode draws a row's noise at the batch's shape, so a row's
    place in the batch is part of its tokens: only greedy chunks compact."""
    eng = _wide(8)
    from tpu_voice_agent.utils.steplog import get_steplog

    bat = ContinuousBatcher(eng, chunk_steps=4, greedy=False, max_new_tokens=64)
    bat.submit(PROMPTS[0])
    res = bat.step()
    rec = get_steplog().last()
    assert (rec["occupancy"], rec["rows"], res.rows) == (1, 8, 8)
    bat.reset()


def test_slots_of_two_dp_groups_never_share_a_compacted_program():
    """On a mesh with ``dp > 1`` a slot's pool blocks live in its group's
    shard: the compacted batch axis would mix groups, so such an engine has
    no compacted width and every chunk computes ``batch_slots`` rows."""
    from tpu_voice_agent.parallel.mesh import make_mesh
    from tpu_voice_agent.serve.paged import PagedDecodeEngine
    from tpu_voice_agent.utils import get_metrics

    eng = PagedDecodeEngine(preset="test-tiny", max_len=1024, batch_slots=8, prefill_buckets=(64, 128),
                            radix_enable=False, mesh=make_mesh(dp=2, tp=1))
    assert eng.dp == 2 and eng.compact_rows == 0
    before = dict(get_metrics().counter_state()[0])
    bat = ContinuousBatcher(eng, chunk_steps=8, max_new_tokens=16)
    rid = bat.submit(PROMPTS[0])
    widths = set()
    while rid not in bat.results:
        widths.add(bat.step().rows)
    after = get_metrics().counter_state()[0]
    fwds, rows = (after.get(k, 0.0) - before.get(k, 0.0)
                  for k in ("scheduler.forwards", "scheduler.forward_rows"))
    assert bat.results[rid].error is None and widths == {8} and rows == 8 * fwds > 0


# ------------------------------------------------- the seam (ISSUE 30)
#
# ``decode_chunk`` hands its chunk back as ONE value, a ``ChunkResult``, under
# one signature in all three implementations; nothing of a chunk is left on
# the engine, and the batcher writes nothing there.

@functools.lru_cache(maxsize=None)
def _implementer(kind: str):
    """A 2-slot engine of each ``decode_chunk`` implementation; the
    confidence lanes off on one of them, so both kinds of ``conf`` are met."""
    from tpu_voice_agent.parallel.pipeline import pp_tp_mesh
    from tpu_voice_agent.serve import DecodeEngine, PagedDecodeEngine, PPDecodeEngine

    kw = dict(preset="test-tiny", max_len=512, batch_slots=2, prefill_buckets=(64, 128))
    if kind == "dense":
        return DecodeEngine(quality_lanes=False, **kw)
    if kind == "pp":
        return PPDecodeEngine(mesh=pp_tp_mesh(2, 1), **kw)
    return PagedDecodeEngine(radix_enable=False, **kw)


@pytest.mark.parametrize("kind", ["dense", "paged", "pp"])
def test_every_decode_chunk_returns_one_record_under_one_signature(kind, monkeypatch):
    import inspect

    import jax
    import numpy as np

    from tpu_voice_agent.serve import DecodeEngine
    from tpu_voice_agent.serve.engine import ChunkResult

    eng = _implementer(kind)
    B = eng.batch_slots
    assert inspect.signature(type(eng).decode_chunk) == inspect.signature(DecodeEngine.decode_chunk)

    # the batcher reads a chunk back with ONE device_get, counted from the
    # moment ``decode_chunk`` returned
    gets, device_get, decode_chunk = [], jax.device_get, eng.decode_chunk
    monkeypatch.setattr(jax, "device_get", lambda x: gets.append(1) or device_get(x))
    monkeypatch.setattr(eng, "decode_chunk", lambda *a, **kw: (decode_chunk(*a, **kw), gets.clear())[0])
    bat = ContinuousBatcher(eng, chunk_steps=4, max_new_tokens=64)
    bat.submit(PROMPTS[0])
    res = bat.step()
    assert len(gets) == 1 and bat._active_h.tolist() == [True, False]
    monkeypatch.undo()
    held = set(vars(eng))
    assert {k for k in held if k.startswith("_last_")} == {"_last_prefill_compute_ms", "_last_cached_tokens"}

    def check(res, rows):
        assert type(res) is ChunkResult and res.rows == rows
        for name in ("n", "eos", "cur", "pos", "fsm", "active", "nbytes", "tokens_left", "poison"):
            assert np.asarray(getattr(res, name)).shape == (B,), name
        assert np.asarray(res.out).shape[0] == B and int(res.fwds) > 0 and int(np.asarray(res.n)[0]) > 0
        if eng.quality_lanes:
            assert len(res.conf) == 5 and all(np.asarray(lane).shape == (B,) for lane in res.conf)
        else:
            assert res.conf is None
        assert "moe" not in res.counts  # a dense model's chunk program has no such output

    check(res, eng.compact_rows if kind == "paged" else B)  # one live row of two: the compacted width
    # the same call by hand, with both keywords: the chaos mask is THIS chunk's
    # (row 0 poisoned, its idle neighbour untouched), and no width without ``live``
    mask = np.array([True, False])
    res = eng.decode_chunk(bat.cur, bat.pos, bat.fsm, bat.active, bat.nbytes, bat.tokens_left,
                           jax.random.PRNGKey(0), 0.7, 3900, 4, True, live=None, nan_inject=mask)
    assert np.asarray(res.poison).tolist() == [1, 0] and res.rows == B
    assert set(vars(eng)) == held  # a chunk leaves nothing new on the engine
    bat.reset()
    eng.release_slot(0, ok=False)


@FF
def test_two_chunks_in_flight_are_independent_values(ff):
    """What overlapping chunk N's readback with chunk N+1's dispatch needs
    (ROADMAP S4c): the second ``decode_chunk`` — another width, a poisoned
    row — is dispatched BEFORE the first one's record is read, and the first
    record still reads what its own chunk produced."""
    import jax
    import numpy as np

    eng = _wide(ff)
    bat = ContinuousBatcher(eng, chunk_steps=4, max_new_tokens=64)
    for p in PROMPTS[:2]:
        bat.submit(p)
    bat.step()
    args = (jax.random.PRNGKey(0), 0.7, 3900, 4, True)
    first = eng.decode_chunk(bat.cur, bat.pos, bat.fsm, bat.active, bat.nbytes, bat.tokens_left, *args,
                             live=bat._active_h)
    mask = np.zeros((8,), dtype=bool)
    mask[1] = True
    second = eng.decode_chunk(first.cur, first.pos, first.fsm, first.active, first.nbytes, first.tokens_left,
                              *args, nan_inject=mask)
    a, b = jax.device_get(((first.n, first.fwds, first.poison), (second.n, second.fwds, second.poison)))
    assert (first.rows, second.rows) == (2, 8) and first is not second
    assert int(a[1]) > 0 and a[0][:2].min() > 0 and not a[2].any()
    assert b[2].tolist() == [0, 1] + [0] * 6 and b[0][0] > 0
    bat.reset()
    for slot in (0, 1):
        eng.release_slot(slot, ok=False)


def test_common_pass_counters_behind_the_batcher_match_the_tables(monkeypatch):
    """A ``test-tiny`` paged engine WITH its pinned prompt prefix, three rows
    behind the batcher through the Pallas block kernel: the tokens are the
    un-paged ``DecodeEngine``'s, and ``attn.common_row_blocks`` /
    ``attn.row_blocks`` / ``attn.common_query_rows`` are what the tables and
    positions say, worked out here by hand (the third: the riders' real
    positions — the tokens the forward leaves them — over every layer's read). Chunks of ONE forward, so a forward's positions are the
    chunk's: a live row's queries run from ``pos`` to the position before
    the one it is left at. Two or more live rows hold the prefix's full
    blocks in common and nothing after them; one live row alone holds every
    block under its first query 'in common' with itself."""
    import numpy as np

    from tpu_voice_agent.utils import tracing

    fresh = tracing.Metrics()
    monkeypatch.setattr(tracing, "_GLOBAL_METRICS", fresh)
    from tpu_voice_agent.serve import DecodeEngine, PagedDecodeEngine
    from tpu_voice_agent.services.brain import install_prompt_prefix
    from tpu_voice_agent.services.prompts import render_prompt

    kw = dict(preset="test-tiny", max_len=2048, batch_slots=3, fast_forward=8,
              prefill_buckets=(128, 256, 512, 1024))
    dense = DecodeEngine(**kw)
    paged = PagedDecodeEngine(kernels="pallas", **kw)
    install_prompt_prefix(dense)
    bs = paged.block_size
    shared = install_prompt_prefix(paged) // bs
    assert shared >= 2

    want, decode_chunk = np.zeros(3, np.int64), paged.decode_chunk

    def spy(cur, pos, fsm, active, *a, **kw):
        tables = np.asarray(paged.block_tables)
        res = decode_chunk(cur, pos, fsm, active, *a, **kw)
        live, first, last = np.asarray(active), np.asarray(pos), np.asarray(res.pos) - 1
        assert int(res.fwds) == 1 and (tables[live, :shared] == tables[live][0, :shared]).all()
        common = shared if live.sum() > 1 else first[live][0] // bs
        handed = paged.cfg.n_layers * (last - first + 1)[live].sum() if common else 0
        by_hand = [common * live.sum(), (last[live] // bs + 1).sum(), handed]
        assert np.asarray(res.counts["attn"]).tolist() == by_hand
        want[:] += by_hand
        return res

    monkeypatch.setattr(paged, "decode_chunk", spy)
    prompts = [render_prompt(t, {}) for t in PROMPTS]
    rd = ContinuousBatcher(dense, chunk_steps=1, max_new_tokens=24).generate_many(prompts)
    rp = ContinuousBatcher(paged, chunk_steps=1, max_new_tokens=24).generate_many(prompts)
    assert [r.token_ids for r in rp] == [r.token_ids for r in rd]
    assert all(r.error is None for r in rp)
    counters = fresh.snapshot()["counters"]
    assert [counters["attn.common_row_blocks"], counters["attn.row_blocks"],
            counters["attn.common_query_rows"]] == want.tolist()
    assert want[2] < paged.cfg.n_layers * 9 * want[0] / shared / 2  # far under the blocks' whole width
    assert want[0] / want[1] > 0.6  # the prefix is most of what a row attends


# ---------------------------------------------------------------- grouped admission (ISSUE 35)

GROUP_TEXTS = ["search for laptops under 1000",
               "open the settings page, then turn on dark mode and go back to the start",
               "go back", "take a screenshot of this page", "scroll down", "play some jazz",
               "upload my resume and submit"]


@functools.lru_cache(maxsize=None)
def _grouping(**kw):
    """A paged engine that groups admissions: 32 slots (``admit_rows`` 4)
    behind the 879-token prompt prefix; with ``kw`` (radix) one that
    does not. Shared: every test leaves it with no slot held."""
    from tpu_voice_agent.serve.paged import PagedDecodeEngine
    from tpu_voice_agent.services.brain import install_prompt_prefix

    eng = PagedDecodeEngine(preset="test-tiny", max_len=1536, batch_slots=32, block_size=128,
                            pool_blocks=96, prefill_buckets=(128, 256, 1024), fast_forward=8,
                            **{"radix_enable": False, **kw})
    install_prompt_prefix(eng)
    return eng


def _group_prompts(n):
    from tpu_voice_agent.services.prompts import render_prompt

    return [render_prompt(t, {}) for t in GROUP_TEXTS[:n]]


def _admit_counts(fn):
    """What ``fn()`` moved of (admit.calls, admit.rows, admit.batched_rows)."""
    from tpu_voice_agent.utils import get_metrics

    names = ("admit.calls", "admit.rows", "admit.batched_rows")
    before = dict(get_metrics().counter_state()[0])
    out = fn()
    after = get_metrics().counter_state()[0]
    return out, tuple(int(after.get(k, 0.0) - before.get(k, 0.0)) for k in names)


@functools.lru_cache(maxsize=None)
def _per_slot_plans(n):
    """The n plans through ``prefill_slot`` alone, one step's admissions."""
    eng = _grouping()
    mp = pytest.MonkeyPatch()
    mp.setattr(type(eng), "admit_rows", 0)
    try:
        out, counts = _admit_counts(lambda: ContinuousBatcher(
            eng, chunk_steps=8, max_new_tokens=96).generate_many(_group_prompts(n)))
    finally:
        mp.undo()
    assert counts == (n, n, 0) and all(r.error is None for r in out)
    return [r.token_ids for r in out]


@pytest.mark.parametrize("n", [2, 4, 5, 7])
def test_requests_that_wait_together_are_admitted_together(n):
    """A step that opens with n requests waiting beside free slots makes
    ceil(n / A) prefill calls, the last of them at one row when one request
    is left over; the requests take the slots in the order they came; and
    every plan is the per-slot path's, token for token."""
    eng = _grouping()
    A = eng.admit_rows
    assert A == 4
    bat = ContinuousBatcher(eng, chunk_steps=8, max_new_tokens=96)
    rids = [bat.submit(p) for p in _group_prompts(n)]
    _, (calls, rows, batched) = _admit_counts(bat.step)
    assert (calls, rows) == (-(-n // A), n) and batched == n - (n % A == 1)
    assert [bat.slots[b].request_id for b in range(n)] == rids  # FIFO, slot by slot
    assert bat._active_h[:n].all() and not bat._active_h[n:].any()
    assert all(bat.slots[b].cached_tokens == 879 and bat.slots[b].prefill_ms > 0 for b in range(n))
    bat.run_until_done()
    got = [bat.results.pop(r) for r in rids]
    assert all(r.error is None and r.cached_tokens == 879 for r in got)
    assert [r.token_ids for r in got] == _per_slot_plans(n)
    assert eng.allocator.blocks_in_use == len(eng._prefix_blocks[0])
    assert {k for k in vars(eng) if k.startswith("_last_")} <= {
        "_last_prefill_compute_ms", "_last_cached_tokens"}


@pytest.mark.parametrize("fault", ["oversized", "prefill_exc", "pool_exhausted"])
def test_a_member_that_fails_in_its_host_half_fails_alone(fault):
    """Four requests wait; the SECOND one's host half raises — a prompt past
    every bucket (``ValueError``), a chaos fault at the top of admission, the
    allocator out of blocks. It alone fails (typed) or, for the pool, is put
    back at the head and admitted by the next step; the others are admitted
    in this step, as a group, and decode the tokens they decode undisturbed."""
    from tpu_voice_agent.utils import chaos

    eng = _grouping()
    want = _per_slot_plans(4)  # before this batcher holds slots: it decodes on the same engine
    prompts = _group_prompts(4)
    if fault == "oversized":
        prompts[1] = prompts[1] + " and then scroll down" * 200
    bat = ContinuousBatcher(eng, chunk_steps=8, max_new_tokens=96)
    rids = [bat.submit(p) for p in prompts]
    if fault != "oversized":
        chaos.configure({"prefill_exc": "prefill_exc@2", "pool_exhausted": "alloc_fail@2"}[fault], seed=0)
    try:
        _, (calls, rows, batched) = _admit_counts(bat.step)
    finally:
        chaos.reset()
    if fault == "pool_exhausted":
        # the first is launched alone when the second breaks the loop off
        assert (calls, rows, batched) == (1, 1, 0) and [r for r, _ in bat.pending] == rids[1:]
        _, (calls, rows, batched) = _admit_counts(bat.step)
        assert (calls, rows, batched) == (1, 3, 3)
    else:
        assert (calls, rows, batched) == (1, 3, 3) and not bat.pending
        assert bat.results[rids[1]].error and not bat.results[rids[1]].token_ids
    bat.run_until_done()
    got = [bat.results.pop(r) for r in rids]
    for i, r in enumerate(got):
        if i == 1 and fault != "pool_exhausted":
            assert r.error.startswith("chaos: injected" if fault == "prefill_exc" else "prompt length")
        else:
            assert r.error is None and r.token_ids == want[i]
    assert eng.allocator.blocks_in_use == len(eng._prefix_blocks[0])


@pytest.mark.parametrize("case", ["lone-waiter", "no-prefix-match", "radix", "chunked"])
def test_what_the_grouped_path_does_not_take_goes_through_prefill_slot(case, monkeypatch):
    """One request waiting; prompts that do not start with the cached prefix;
    an engine with radix reuse on (``admit_rows`` 0); and
    admissions that PREFILL_CHUNK_TOKENS chunks: none reaches ``admit_group``,
    all are admitted, a slot at a time."""
    if case == "chunked":
        monkeypatch.setenv("PREFILL_CHUNK_TOKENS", "512")
    eng = _grouping(**({"radix_enable": True} if case == "radix" else {}))
    assert eng.admit_rows == (0 if case == "radix" else 4)
    grouped, per_slot, chunked = [], [], []
    for name, seen in (("admit_group", grouped), ("prefill_slot", per_slot),
                       ("begin_chunked_prefill", chunked)):
        fn = getattr(eng, name)
        monkeypatch.setattr(eng, name, lambda *a, _f=fn, _s=seen, **kw: _s.append(1) or _f(*a, **kw))
    prompts = _group_prompts(1 if case == "lone-waiter" else 3)
    if case == "no-prefix-match":
        prompts = ["turn on the lights", "play some jazz", "what time is it"]
    bat = ContinuousBatcher(eng, chunk_steps=8, max_new_tokens=16)
    for p in prompts:
        bat.submit(p)
    _, (calls, rows, batched) = _admit_counts(bat.step)
    assert not grouped and batched == 0
    if case == "chunked":
        assert len(chunked) == len(prompts) and not per_slot
    else:
        assert len(per_slot) == len(prompts) == calls == rows
    bat.run_until_done()
    assert all(r.error is None for r in bat.results.values()) and len(bat.results) == len(prompts)
    bat.reset()
    if case != "radix":  # whose tree keeps the finished requests' chains
        assert eng.allocator.blocks_in_use == len(eng._prefix_blocks[0])


# ---------------------------------------------------------------- the head's ids, kept (ISSUE 53)

SITE_TOKENS = 145  # the rehearsal's: 879 + 145 = 1024, eight whole blocks


@functools.lru_cache(maxsize=None)
def _two_slots():
    """A dense engine whose top bucket holds the prompt head with the
    rehearsal's site context in it. Shared: a test installs the head it needs."""
    from tpu_voice_agent.serve import DecodeEngine

    return DecodeEngine(preset="test-tiny", max_len=2048, batch_slots=2, prefill_buckets=(64, 1024))


@pytest.fixture()
def site():
    """``site(tokens)`` puts a site context of so many tokens into the
    process's prompt head (the cells' own text, seed 60); gone behind the test."""
    from benchmark.builders.dots3_stack import site_context_text
    from tpu_voice_agent.services import prompts

    yield lambda tokens: prompts.set_site_context(site_context_text(_two_slots().tokenizer, tokens, 60))
    prompts.set_site_context("")


@pytest.mark.parametrize("site_tokens", [0, SITE_TOKENS], ids=["bare-head", "site-context"])
def test_encode_prompt_is_the_tokenizers_whole_walk_id_for_id(site_tokens, site):
    """Every text of the cells' corpus, behind the bare head and behind a site
    context: the memo's ids and the walk behind them are ``encode(prompt,
    bos=True)``, and all but the head's last pieces came from the memo."""
    from benchmark.lib.corpus import texts
    from tpu_voice_agent.services.brain import install_prompt_prefix
    from tpu_voice_agent.services.prompts import render_prompt

    eng = _two_slots()
    site(site_tokens)
    assert install_prompt_prefix(eng) == 879 + site_tokens
    head, kept, tail = eng._head
    assert head.endswith('<|user|>\n{"text":"') and kept[:len(eng.prefix_ids) - 8] == eng.prefix_ids[:-8]
    assert 0 < len(tail) < 28 and len(eng.prefix_ids) - 8 < len(kept) <= len(eng.tokenizer.encode(head, bos=True))
    for text in texts(64):
        for context in ({}, {"last_query": "red shoes", "page": 2}):
            prompt = render_prompt(text, context)
            assert eng.encode_prompt(prompt) == (eng.tokenizer.encode(prompt, bos=True), len(kept))


def test_a_prompt_without_the_head_and_a_list_of_ids_pass_through(site, tiny_batch_engine):
    """What does not start with the head's text is encoded whole — a bare
    text, a ``feed_prefix`` partial cut inside the head, a prompt that leaves
    the head a character early — and ids come back as they went in."""
    from tpu_voice_agent.services.brain import install_prompt_prefix
    from tpu_voice_agent.services.prompts import render_prompt

    eng = _two_slots()
    site(0)
    install_prompt_prefix(eng)
    tok, head = eng.tokenizer, eng._head[0]
    for prompt in ("go back", head[:len(head) // 2], head[:-1], head[:-3] + "x" + head[-2:] + 'go back"}', ""):
        assert eng.encode_prompt(prompt) == (tok.encode(prompt, bos=True), 0)
    ids = tok.encode(render_prompt("go back", {}), bos=True)
    assert eng.encode_prompt(ids) == (ids, 0) == eng.encode_prompt(tuple(ids))
    # the head's text alone: all of the memo, the walk over its last bytes
    assert eng.encode_prompt(head) == (tok.encode(head, bos=True), len(eng._head[1]))
    # and an engine that was told no head keeps none
    assert tiny_batch_engine._head is None
    assert tiny_batch_engine.encode_prompt(render_prompt("go back", {})) == (ids, 0)


def test_the_memo_follows_the_head_that_set_prompt_prefix_is_given(site):
    """``set_site_context`` + ``set_prompt_prefix`` again replace the head's
    text and ids together with ``prefix_ids``; between the two a prompt is
    rendered with a head the engine does not hold, and is encoded whole."""
    from tpu_voice_agent.services.brain import install_prompt_prefix
    from tpu_voice_agent.services.prompts import render_prompt

    eng = _two_slots()
    tok = eng.tokenizer

    def reused(text="scroll down"):
        prompt = render_prompt(text, {})
        ids, n = eng.encode_prompt(prompt)
        assert ids == tok.encode(prompt, bos=True)
        return n

    site(0)
    install_prompt_prefix(eng)
    bare = reused()
    assert 850 < bare == len(eng._head[1]) <= 879 + 8
    site(SITE_TOKENS)
    assert reused() == 0  # the new head's prompts miss the old head's text
    install_prompt_prefix(eng)
    assert reused() == len(eng._head[1]) == bare + SITE_TOKENS and len(eng.prefix_ids) == 1024
    site(0)
    assert reused() == 0
    install_prompt_prefix(eng)
    assert reused() == bare


def test_an_admissions_entry_says_how_many_ids_the_memo_gave():
    """``head_ids_reused`` in the step ledger's entry and ``admit.head_ids_reused``
    in the registry: the memo's ids for a prompt behind the head (grouped or
    not), 0 for one that is not; the parts still tile the request."""
    from tpu_voice_agent.utils import get_metrics
    from tpu_voice_agent.utils.steplog import ADMISSION_PARTS, get_steplog

    eng = _grouping()
    kept = len(eng._head[1])
    assert 850 < kept <= 879 + 8
    bat = ContinuousBatcher(eng, chunk_steps=8, max_new_tokens=16)
    hits = [bat.submit(p) for p in _group_prompts(3)]
    misses = [bat.submit(t) for t in GROUP_TEXTS[:2]] + [bat.submit(eng.tokenizer.encode(GROUP_TEXTS[2], bos=True))]
    before = get_metrics().counter_state()[0].get("admit.head_ids_reused", 0.0)
    seq = (get_steplog().last() or {"seq": -1})["seq"]
    bat.run_until_done()
    assert get_metrics().counter_state()[0]["admit.head_ids_reused"] - before == 3 * kept
    entries = {a["rid"]: a for s in get_steplog().steps() if s["seq"] > seq for a in s.get("admissions", [])}
    assert set(entries) == set(hits + misses) and all(bat.results[r].error is None for r in entries)
    for rid, a in entries.items():
        assert a["head_ids_reused"] == (kept if rid in hits else 0) <= a["prompt_tokens"]
        assert (a["cached_tokens"] == 879) == (rid in hits)  # ``_split_prefix`` matched the memo's ids
        assert "tokenize_ms" in a
        assert sum(a.get(f"{p}_ms", 0.0) for p in ADMISSION_PARTS) <= a["request_ms"] + 1e-3, a
    bat.reset()
