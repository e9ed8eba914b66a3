"""Incremental streaming prefill (ISSUE 19): chunked prefill in the
batcher + prefill-only prefix feeds — FAST tier, because both identity
contracts gate tier-1.

The non-negotiable contracts, in the PR 3/4/5 differential style:
PREFILL_CHUNK_TOKENS unset keeps the one-shot barrier admission
byte-identical; set, a chunked admission produces TOKEN-IDENTICAL output
for the chunked request AND its batch-mates; a prefix feed is pure cache
warming — the eventual real parse is token-identical to a cold parse,
including when STT RETRACTS a committed prefix (the radix match falls
back to the longest still-valid cached prefix); and no interleaving of
ok/retracted/cancelled work leaks a block (allocator refcounts are the
single source of truth)."""

import random

import pytest

from tpu_voice_agent.serve import PagedDecodeEngine
from tpu_voice_agent.serve.scheduler import ContinuousBatcher
from tpu_voice_agent.services.brain import install_prompt_prefix
from tpu_voice_agent.services.prompts import render_prompt
from tpu_voice_agent.services.voice import _PrefixFeedTracker, _prefill_remaining

BUCKETS = (128, 256, 512, 1024, 2048)


def _paged(radix: bool, **kw):
    return PagedDecodeEngine(
        preset="test-tiny", max_len=2048, batch_slots=2,
        prefill_buckets=BUCKETS, radix_enable=radix, **kw)


def _run(eng, prompts, max_new=48, chunk_tokens=None, monkeypatch=None):
    if monkeypatch is not None:
        if chunk_tokens:
            monkeypatch.setenv("PREFILL_CHUNK_TOKENS", str(chunk_tokens))
        else:
            monkeypatch.delenv("PREFILL_CHUNK_TOKENS", raising=False)
    return ContinuousBatcher(eng, chunk_steps=16,
                             max_new_tokens=max_new).generate_many(prompts)


def _leak_check(eng):
    """With no live slots, every resident block is tree-owned."""
    trees = eng.radix or []
    assert eng.allocator.blocks_in_use == sum(t.nodes for t in trees)


# ------------------------------------------------------------- tracker unit


def test_tracker_commits_only_after_k_stable_partials():
    tr = _PrefixFeedTracker(k=3, min_chars=4)
    assert tr.observe("open the") is None          # ring not full
    assert tr.observe("open the second") is None   # ring not full
    # stable prefix across the 3 = "open the " -> trimmed to "open the"
    assert tr.observe("open the second result") == "open the"
    assert tr.committed == "open the"


def test_tracker_min_chars_growth_gate():
    tr = _PrefixFeedTracker(k=2, min_chars=8)
    tr.observe("search for wireless")
    assert tr.observe("search for wireless head") == "search for wireless"
    # grows by < 8 committable chars -> no new commit yet
    assert tr.observe("search for wireless headph") is None
    tr.observe("search for wireless headphones now")
    got = tr.observe("search for wireless headphones now please")
    assert got == "search for wireless headphones now"


def test_tracker_trims_to_whitespace_boundary():
    tr = _PrefixFeedTracker(k=2, min_chars=1)
    tr.observe("naviga")
    # stable prefix "naviga" is mid-word -> nothing commits
    assert tr.observe("navigate") is None
    tr.observe("navigate to example")
    assert tr.observe("navigate to example dot") == "navigate to example"


def test_tracker_retraction_rebaselines():
    tr = _PrefixFeedTracker(k=2, min_chars=4)
    tr.observe("recognize speech today")
    assert tr.observe("recognize speech today ok") == "recognize speech today"
    # STT revises the committed text ("wreck a nice beach"): the old
    # baseline no longer prefixes the stable text -> re-baseline and
    # commit the revised prefix fresh
    tr.observe("wreck a nice beach today")
    got = tr.observe("wreck a nice beach today ok")
    assert got == "wreck a nice beach today"
    assert tr.committed == "wreck a nice beach today"


def test_tracker_reset():
    tr = _PrefixFeedTracker(k=2, min_chars=1)
    tr.observe("scroll down")
    tr.observe("scroll down now")
    assert tr.committed
    tr.reset()
    assert tr.committed == "" and tr.observe("fresh text") is None


# ------------------------------------------------------------- gauge helper


def test_prefill_remaining_every_utterance_shape():
    # speculative pre-parse: prompt fully prefilled before the endpoint
    assert _prefill_remaining({"prompt_tokens": 900.0}, True, False) == 0.0
    # cold engine parse: whatever the cache did not absorb was outstanding
    assert _prefill_remaining(
        {"prompt_tokens": 900.0, "cached_tokens": 880.0}, False, False) == 20.0
    # cache can block-round past the prompt -> clamped, never negative
    assert _prefill_remaining(
        {"prompt_tokens": 10.0, "cached_tokens": 16.0}, False, False) == 0.0
    # degraded (rule fallback) and headerless parses had no engine prefill
    # pending at the endpoint — recorded as 0, not skipped (the old bug)
    assert _prefill_remaining({"prompt_tokens": 900.0}, False, True) == 0.0
    assert _prefill_remaining({}, False, False) == 0.0


# ---------------------------------------------------------- chunked prefill


@pytest.fixture(scope="module")
def eng_off():
    eng = _paged(False)
    install_prompt_prefix(eng)
    return eng


@pytest.fixture(scope="module")
def eng_on():
    eng = _paged(True)
    install_prompt_prefix(eng)
    return eng


@pytest.fixture(scope="module")
def eng_plain():
    # NO pinned static prefix: the whole ~900-token rendered prompt is
    # computed suffix, so a 64-token chunk size genuinely interleaves
    # many prefill chunks with the batch-mate's decode steps
    return _paged(False)


PROMPTS = [
    render_prompt("search for wireless headphones", {}),
    render_prompt("open the second result please", {"last_query": "x"}),
]


def test_chunk_knob_unset_keeps_barrier_path(eng_off, monkeypatch):
    monkeypatch.delenv("PREFILL_CHUNK_TOKENS", raising=False)
    b = ContinuousBatcher(eng_off, chunk_steps=16, max_new_tokens=8)
    assert b._prefill_chunk == 0 and b._admitting == {}


def test_chunked_prefill_token_identity_and_batchmate_isolation(
        eng_plain, monkeypatch):
    """THE chunked differential: a long cold prompt admitted in 64-token
    chunks yields the same tokens as the barrier admission — and so does
    the batch-mate decoding while the chunks interleave."""
    from tpu_voice_agent.utils import get_metrics
    before = get_metrics().counter_state()[0]
    barrier = _run(eng_plain, PROMPTS, monkeypatch=monkeypatch)
    chunked = _run(eng_plain, PROMPTS, chunk_tokens=64,
                   monkeypatch=monkeypatch)
    for b, c in zip(barrier, chunked):
        assert b.error is None and c.error is None, (b.error, c.error)
        assert b.token_ids == c.token_ids
    after = get_metrics().counter_state()[0]
    adm = after.get("prefill.chunked_admissions", 0) - before.get(
        "prefill.chunked_admissions", 0)
    chunks = after.get("prefill.chunks", 0) - before.get("prefill.chunks", 0)
    assert adm >= 2
    assert chunks > adm  # ~900-token suffixes -> many chunks each
    assert eng_plain.allocator.blocks_in_use == 0  # radix off: all reclaimed


def test_chunked_admissions_land_in_the_ledger_with_their_last_chunk(
        eng_plain, monkeypatch):
    """A chunked admission's start and middle chunks are spans on the trace
    and no ledger entry; the chunk that lands it writes ONE, in the step it
    lands in, carrying the queue wait stamped when the request was popped —
    so ``admissions`` still counts ``admitted`` step by step."""
    from tpu_voice_agent.utils import get_steplog

    get_steplog().clear()
    res = _run(eng_plain, PROMPTS, chunk_tokens=64, monkeypatch=monkeypatch)
    assert all(r.error is None for r in res)
    steps = get_steplog().steps()
    get_steplog().clear()
    for s in steps:
        assert len(s.get("admissions", [])) == s.get("admitted", 0)
    adm = [a for s in steps for a in s.get("admissions", [])]
    assert len(adm) == len(PROMPTS)
    chunked = [a for a in adm if "tokenize_ms" not in a]  # encoded steps earlier
    assert len(chunked) >= 2
    for a in chunked:
        assert a["prefill_call_ms"] > 0 and a["first_token_call_ms"] > 0
        assert a["prompt_tokens"] > 64 and a["queue_ms"] >= 0.0
    assert sorted(round(a["queue_ms"], 3) for a in adm) == sorted(
        round(r.queue_ms, 3) for r in res)


def test_chunked_prefill_identity_with_radix(eng_off, eng_on, monkeypatch):
    """Chunked admissions against the radix plane: the first (cold) run
    seeds chains, the second admits warm through begin_chunked_prefill's
    chain-match path — all token-identical to the barrier cold engine."""
    cold = _run(eng_off, PROMPTS, monkeypatch=monkeypatch)
    warm1 = _run(eng_on, PROMPTS, chunk_tokens=64, monkeypatch=monkeypatch)
    warm2 = _run(eng_on, PROMPTS, chunk_tokens=64, monkeypatch=monkeypatch)
    for c, w1, w2 in zip(cold, warm1, warm2):
        assert c.error is None and w1.error is None and w2.error is None
        assert c.token_ids == w1.token_ids == w2.token_ids
    # the warm rerun never matched LESS than the static prefix, and the
    # longer prompt matched past it through the inserted chain (the shorter
    # prompt's chain rounds to a block boundary beyond its own length, so
    # it legitimately falls back to the pinned prefix)
    assert all(w.cached_tokens >= len(eng_on.prefix_ids) for w in warm2)
    assert any(w.cached_tokens > len(eng_on.prefix_ids) for w in warm2)
    _leak_check(eng_on)


def test_cancel_mid_chunked_admission_releases_everything(monkeypatch):
    """Cancel lands BETWEEN prefill chunks: the admission dies alone with
    a typed cancelled error, its blocks free through the eviction seam,
    and nothing was half-inserted into the radix tree."""
    monkeypatch.setenv("PREFILL_CHUNK_TOKENS", "32")
    # no pinned prefix -> the full prompt chunks (~28 chunks at C=32), so
    # one step leaves the admission genuinely mid-flight
    eng = _paged(True)
    b = ContinuousBatcher(eng, chunk_steps=4, max_new_tokens=16)
    ids = eng.tokenizer.encode(PROMPTS[0], bos=True)
    rid = b.submit(ids)
    b.step()  # begin + first chunks; prompt >> 32 so still admitting
    assert rid not in b.results
    assert b._admitting, "admission should still be mid-flight"
    b.cancel(rid, reason="ws teardown")
    assert rid in b.results
    assert "cancelled" in (b.results[rid].error or "")
    assert not b._admitting
    _leak_check(eng)
    # the engine still serves after the cancelled admission
    r = _run(eng, [PROMPTS[1]])[0]
    assert r.error is None
    _leak_check(eng)


# ------------------------------------------------------------- prefix feeds


def _feed(b, prompt, tenant=None):
    return b.feed_prefix(prompt, tenant=tenant)


def test_feed_then_final_is_warm_and_token_identical(eng_off, eng_on):
    """A fed prefix (the stabilized partial) leaves a radix chain the
    real parse admits against: cached_tokens covers the fed prompt's full
    blocks, and the output matches the cold engine exactly."""
    text_partial = "filter the results under one hundred"
    text_final = "filter the results under one hundred dollars please"
    p_partial = render_prompt(text_partial, {})
    p_final = render_prompt(text_final, {})
    cold = _run(eng_off, [p_final])[0]
    assert cold.error is None

    b = ContinuousBatcher(eng_on, chunk_steps=16, max_new_tokens=48)
    out = _feed(b, p_partial)
    assert out["ok"] is True and out["prompt_tokens"] > 0
    ids_partial = eng_on.tokenizer.encode(p_partial, bos=True)
    ids_final = eng_on.tokenizer.encode(p_final, bos=True)
    # the rendered partial IS a token prefix of the rendered final here —
    # the fed chain's full blocks are exactly what the final can reuse
    shared = 0
    for a_, b_ in zip(ids_partial, ids_final):
        if a_ != b_:
            break
        shared += 1
    warm = _run(eng_on, [p_final])[0]
    assert warm.error is None
    assert warm.token_ids == cold.token_ids
    bs = eng_on.block_size
    assert warm.cached_tokens >= (shared // bs) * bs - bs  # block-rounded
    _leak_check(eng_on)


def test_feed_retraction_falls_back_token_identically(eng_off, eng_on):
    """STT revises a committed prefix: the final shares only a shorter
    prefix with what was fed. The radix match absorbs exactly the
    still-valid cached part and the parse is token-identical to cold —
    the fed-but-retracted tail is dead cache, never wrong output."""
    fed = render_prompt("recognize speech with this microphone", {})
    final = render_prompt("wreck a nice beach with this microphone", {})
    cold = _run(eng_off, [final])[0]
    assert cold.error is None
    b = ContinuousBatcher(eng_on, chunk_steps=16, max_new_tokens=48)
    out = _feed(b, fed)
    assert out["ok"] is True
    warm = _run(eng_on, [final])[0]
    assert warm.error is None
    assert warm.token_ids == cold.token_ids
    # still warm at least through the static prefix (longest valid prefix)
    assert warm.cached_tokens >= len(eng_on.prefix_ids)
    _leak_check(eng_on)


def test_feed_reextension_is_incremental(eng_on):
    """Feed K then K+delta: the second feed's prefill starts from the
    first feed's chain (cached_tokens grows monotonically) — the O(new
    tokens) re-extension the tentpole is built on."""
    t1 = "sort these results by price from low"
    t2 = "sort these results by price from low to high right now"
    b = ContinuousBatcher(eng_on, chunk_steps=16, max_new_tokens=48)
    o1 = _feed(b, render_prompt(t1, {}))
    o2 = _feed(b, render_prompt(t2, {}))
    assert o1["ok"] and o2["ok"]
    assert o2["cached_tokens"] >= len(eng_on.prefix_ids)
    assert o2["cached_tokens"] >= o1["cached_tokens"]
    _leak_check(eng_on)


def test_feed_sheds_for_live_work(eng_on):
    b = ContinuousBatcher(eng_on, chunk_steps=16, max_new_tokens=48)
    b.pending.append((999, "queued work"))
    out = _feed(b, render_prompt("take a screenshot", {}))
    assert out == {"ok": False, "reason": "busy"}
    b.pending.clear()
    # all slots occupied -> no_slot shed
    for sl in b.slots:
        sl.request_id = 1
    b._active_h[:] = True
    out = _feed(b, render_prompt("take a screenshot", {}))
    assert out == {"ok": False, "reason": "no_slot"}
    for sl in b.slots:
        sl.request_id = -1
    b._active_h[:] = False
    _leak_check(eng_on)


def test_feed_requires_radix(eng_off):
    b = ContinuousBatcher(eng_off, chunk_steps=16, max_new_tokens=48)
    out = _feed(b, render_prompt("take a screenshot", {}))
    assert out == {"ok": False, "reason": "radix_off"}


def test_feed_oversized_prompt_fails_closed(eng_on):
    b = ContinuousBatcher(eng_on, chunk_steps=16, max_new_tokens=48)
    ids = list(range(1, 4000))  # past every bucket and max_len
    out = _feed(b, ids)
    assert out["ok"] is False
    _leak_check(eng_on)


# -------------------------------------------------------- brain HTTP seam


def test_parse_prefix_feed_http_contract():
    """/parse with prefix_feed: backends without a prefill-only admission
    path answer 409 prefix_feed_unsupported (the voice service latches
    feeds off on it); feed-capable backends answer 200 with the feed
    verdict and never run a decode."""
    import httpx

    from tests.http_helper import AppServer
    from tpu_voice_agent.services.brain import RuleBasedParser, build_app

    with AppServer(build_app(RuleBasedParser())) as srv:
        r = httpx.post(srv.url + "/parse",
                       json={"text": "search for hubs", "context": {},
                             "prefix_feed": True})
        assert r.status_code == 409
        assert r.json()["error"] == "prefix_feed_unsupported"

    class _FeedingParser:
        supports_prefix_feed = True
        fed: list[str] = []

        def parse(self, text, context, session_id=None):
            raise AssertionError("a prefix_feed request must never decode")

        def feed_prefix(self, text, context, session_id=None):
            self.fed.append(text)
            return {"ok": True, "prompt_tokens": 9, "cached_tokens": 0}

    with AppServer(build_app(_FeedingParser())) as srv:
        r = httpx.post(srv.url + "/parse",
                       json={"text": "search for hubs", "context": {},
                             "prefix_feed": True})
        assert r.status_code == 200
        body = r.json()
        assert body["prefix_feed"] is True and body["ok"] is True
        assert _FeedingParser.fed == ["search for hubs"]


# ----------------------------------------------------------------- the fuzz


def test_mixed_ok_retracted_cancelled_fuzz_zero_leakage(monkeypatch):
    """The satellite's leak fuzz: random interleavings of committed feeds,
    retracted feeds (revised text), real chunked/barrier parses, and
    mid-admission cancellations on a small pool. Invariant after every
    drain: blocks_in_use == tree-owned blocks (no slot refs leak), and
    every completed parse is error-free."""
    monkeypatch.setenv("PREFILL_CHUNK_TOKENS", "48")
    rng = random.Random(19)
    eng = _paged(True, pool_blocks=48)
    install_prompt_prefix(eng)
    texts = [
        "search for wireless headphones",
        "open the second result",
        "scroll down two pages then go back",
        "take a screenshot of this page",
    ]
    revised = {
        texts[0]: "search for wired headphones",
        texts[1]: "open the second tab",
    }
    for round_ in range(8):
        b = ContinuousBatcher(eng, chunk_steps=4, max_new_tokens=12)
        t = rng.choice(texts)
        op = rng.random()
        if op < 0.4:
            # feed a (possibly soon-retracted) partial, then parse a final
            # that may share only part of it
            _feed(b, render_prompt(t[: max(8, len(t) // 2)], {}))
            final = revised.get(t, t)
            r = b.generate_many([render_prompt(final, {})])[0]
            assert r.error is None, r.error
        elif op < 0.7:
            # cancel mid-chunked-admission
            rid = b.submit(eng.tokenizer.encode(render_prompt(t, {}),
                                                bos=True))
            b.step()
            b.cancel(rid, reason="fuzz")
            assert rid in b.results
        else:
            r = b.generate_many([render_prompt(t, {})])[0]
            assert r.error is None, r.error
        b.run_until_done()
        _leak_check(eng)
    _leak_check(eng)
