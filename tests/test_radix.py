"""Radix KV reuse (serve.radix): session-aware prefix caching over the
paged pool — FAST tier, because the identity contract gates tier-1.

The non-negotiable contract (ISSUE 5, mirroring PR 3/4's differential
style): a radix-hit admission produces TOKEN-IDENTICAL output to a cold
admission; RADIX_ENABLE unset keeps the pre-radix paged path byte-identical;
eviction never frees a block referenced by a live slot or the pinned root
(allocator refcounts are the single source of truth).
"""

import random

import pytest

from tpu_voice_agent.serve import PagedDecodeEngine, RadixCache
from tpu_voice_agent.serve.paged import BlockAllocator, PoolExhausted
from tpu_voice_agent.serve.scheduler import ContinuousBatcher
from tpu_voice_agent.services.brain import (
    SessionTranscripts,
    install_prompt_prefix,
)
from tpu_voice_agent.services.prompts import render_prompt


# ---------------------------------------------------------------- allocator


def test_allocator_ref_unknown_block_raises():
    a = BlockAllocator(8)
    x = a.alloc(2)
    with pytest.raises(ValueError, match="untracked block 6"):
        a.ref([x[0], 6])  # 6 was never handed out
    a.free(x)
    with pytest.raises(ValueError, match=f"untracked block {x[0]}"):
        a.ref([x[0]])  # use-after-free


def test_allocator_double_free_raises():
    a = BlockAllocator(8)
    x = a.alloc(1)
    a.free(x)
    with pytest.raises(ValueError, match=f"double free of block {x[0]}"):
        a.free(x)
    with pytest.raises(ValueError, match="double free of block 3"):
        a.free([3])  # never allocated at all


def test_allocator_fuzz_no_leaks_no_double_handouts():
    """Random alloc/ref/free interleavings against a host model: every
    handout is unique among live blocks, refcounts drain to exactly zero,
    and the pool ends fully reclaimed."""
    rng = random.Random(7)
    a = BlockAllocator(32, n_groups=2)
    live: dict[int, int] = {}  # block -> modeled refcount
    for _ in range(3000):
        op = rng.random()
        if op < 0.45:
            g = rng.randrange(2)
            k = rng.randint(1, 4)
            try:
                blocks = a.alloc(k, group=g)
            except PoolExhausted:
                assert a.free_blocks(g) < k
                continue
            assert len(set(blocks)) == k
            for b in blocks:
                assert b not in live, "double handout of a live block"
                assert b % a.blocks_per_group != 0, "reserved trash block leaked"
                assert g * a.blocks_per_group <= b < (g + 1) * a.blocks_per_group
                live[b] = 1
        elif op < 0.7 and live:
            b = rng.choice(list(live))
            a.ref([b])
            live[b] += 1
        elif live:
            b = rng.choice(list(live))
            a.free([b])
            live[b] -= 1
            if live[b] == 0:
                del live[b]
        assert a.blocks_in_use == len(live)
        for b, r in live.items():
            assert a.refcount(b) == r
    for b, r in list(live.items()):
        a.free([b] * r)
    assert a.blocks_in_use == 0
    assert a.blocks_shared == 0


# ---------------------------------------------------------------- tree unit


def _tree(n_blocks=32, bs=4, max_nodes=64):
    a = BlockAllocator(n_blocks)
    return a, RadixCache(a, bs, max_nodes=max_nodes)


def test_radix_match_is_block_granular_and_refs_for_caller():
    a, t = _tree()
    ids = list(range(1, 11))  # 10 tokens, bs=4 -> 2 full blocks
    blocks = a.alloc(3)
    t.insert(ids, blocks)  # adopts blocks[0:2]; blocks[2] is a partial tail
    assert t.nodes == 2
    assert a.refcount(blocks[0]) == 2 and a.refcount(blocks[1]) == 2
    assert a.refcount(blocks[2]) == 1  # partial tail never enters the tree
    chain, matched = t.match(ids)
    assert chain == blocks[:2] and matched == 8
    assert a.refcount(blocks[0]) == 3  # caller's ref taken by match
    # a match alone is not a HIT: the engine accounts the hit only once it
    # commits to the chain (bucket-fallback admissions reuse nothing)
    assert t.hits == 0 and t.lookups == 1
    t.record_hit(matched)
    assert t.hits == 1 and t.matched_tokens == 8
    a.free(chain)
    # an exactly-chain-length prompt must leave >= 1 token to re-prefill
    chain, matched = t.match(ids[:8])
    assert matched == 4 and chain == blocks[:1]
    a.free(chain)
    # diverging ids match only the common block prefix
    chain, matched = t.match(ids[:4] + [99, 98, 97, 96, 95])
    assert matched == 4
    a.free(chain)


def test_radix_eviction_respects_refs_pins_and_lru():
    a, t = _tree()
    pin = a.alloc(1)
    t.pin_root_chain([1, 2, 3, 4], pin)
    b1 = a.alloc(1)
    t.insert([1, 2, 3, 4] + [5, 6, 7, 8], [pin[0], b1[0]])  # chain A
    b2 = a.alloc(1)
    t.insert([1, 2, 3, 4] + [9, 10, 11, 12], [pin[0], b2[0]])  # chain B (newer)
    a.free(b1)  # the tree is now chain A's tail's sole owner
    a.free(b2)
    assert t.nodes == 3
    # a live caller ref protects chain B from eviction
    chain, matched = t.match([1, 2, 3, 4, 9, 10, 11, 12, 0])
    assert matched == 8
    # evict: only chain A's leaf is unreferenced (B's tail is ref'd by the
    # caller, the pinned root may never go)
    assert t.evict(10) == 1
    assert a.refcount(pin[0]) >= 1 and t.nodes == 2
    a.free(chain[1:])  # drop the caller ref on B's tail
    a.free(chain[:1])
    assert t.evict(10) == 1  # now B's tail goes too; the pin stays
    assert t.nodes == 1
    assert t.evict(10) == 0  # nothing evictable left
    assert a.refcount(pin[0]) == 2  # engine ref + tree ref, untouched


def test_radix_lru_evicts_oldest_leaf_first():
    a, t = _tree()
    x = a.alloc(2)
    t.insert([1, 2, 3, 4], x[:1])  # older chain
    t.insert([9, 9, 9, 9], x[1:])  # newer chain
    a.free(x)
    assert t.evict(1) == 1
    # the OLDER leaf went; the newer one still matches
    chain, matched = t.match([9, 9, 9, 9, 0])
    assert matched == 4
    a.free(chain)
    chain, matched = t.match([1, 2, 3, 4, 0])
    assert matched == 0


def test_radix_max_nodes_cap_holds():
    a, t = _tree(n_blocks=64, bs=2, max_nodes=4)
    for i in range(8):
        b = a.alloc(1)
        t.insert([100 + i, 200 + i], b)
        a.free(b)
    assert t.nodes <= 4


def test_radix_clear_frees_tree_refs():
    a, t = _tree()
    b = a.alloc(2)
    t.insert([1, 2, 3, 4, 5, 6, 7, 8], b)
    a.free(b)
    assert a.blocks_in_use == 2  # tree's refs keep them
    t.clear()
    assert a.blocks_in_use == 0 and t.nodes == 0


# ---------------------------------------------------------------- engines

BUCKETS = (128, 256, 512, 1024, 2048)


def _paged(radix: bool, **kw):
    return PagedDecodeEngine(
        preset="test-tiny", max_len=2048, batch_slots=2,
        prefill_buckets=BUCKETS, radix_enable=radix, **kw)


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
def trained(distilled_intent):
    """Engines carrying the distilled checkpoint, for the tests that go
    through ``BatchedEngineParser``. Its plans take up to ~70 tokens behind
    the brain prompt: PARSE_TOKENS leaves them room."""
    import jax

    cfg, params = distilled_intent

    def build(radix: bool):
        eng = _paged(radix, cfg=cfg, init_weights=False)
        eng.load_params(jax.device_put(params))
        install_prompt_prefix(eng)
        return eng

    return build


PARSE_TOKENS = 160


def _run(eng, prompts, max_new=48):
    return ContinuousBatcher(eng, chunk_steps=16,
                             max_new_tokens=max_new).generate_many(prompts)


def _frame_ids(tok, text, context):
    user = SessionTranscripts.user_frame(text, context)
    return tok.encode(f"\n<|user|>\n{user}\n<|assistant|>\n", bos=False)


TURNS = [
    ("search for wireless headphones", {}),
    ("open the second result", {"last_query": "wireless headphones"}),
    ("sort these by price from low to high", {"last_query": "wireless headphones"}),
]


def _play_session(eng, max_new=48, turns=TURNS):
    """Drive a multi-turn session exactly like the session-aware brain:
    turn 1 is the stateless render, later turns extend prompt ids +
    generated ids (strict token extension — ragged block boundaries arise
    naturally). Returns (per-turn results, per-turn prompt id lists)."""
    tok = eng.tokenizer
    results, prompts = [], []
    hist = None
    for text, ctx in turns:
        ids = (tok.encode(render_prompt(text, ctx), bos=True) if hist is None
               else hist + _frame_ids(tok, text, ctx))
        r = _run(eng, [ids], max_new=max_new)[0]
        assert r.error is None, r.error
        results.append(r)
        prompts.append(ids)
        hist = ids + r.token_ids
    return results, prompts


def test_radix_disabled_by_default(monkeypatch):
    monkeypatch.delenv("RADIX_ENABLE", raising=False)
    eng = _paged(None)  # env decides
    assert eng.radix is None
    monkeypatch.setenv("RADIX_ENABLE", "1")
    monkeypatch.setenv("RADIX_MAX_NODES", "77")
    eng = _paged(None)
    assert eng.radix is not None and eng.radix[0].max_nodes == 77


def test_radix_multi_turn_token_identity(eng_off, eng_on):
    """THE differential: warm radix admissions (turn 2+ reuse turn N-1's
    decoded chain; a repeat session reuses everything) are token-identical
    to the cold engine, across ragged block boundaries."""
    cold, _ = _play_session(eng_off)
    warm, _ = _play_session(eng_on)
    for c, w in zip(cold, warm):
        assert c.token_ids == w.token_ids
        assert eng_on.fsm.walk(w.token_ids) >= 0
    # turn 2+ matched the session chain past the static prefix
    P = len(eng_on.prefix_ids)
    assert warm[0].cached_tokens == P  # turn 1: static prefix only
    assert warm[1].cached_tokens > P
    assert warm[2].cached_tokens >= warm[1].cached_tokens  # block-rounded
    # replaying the same session is a full-history hit, still identical
    warm2, _ = _play_session(eng_on)
    for c, w in zip(cold, warm2):
        assert c.token_ids == w.token_ids
    assert warm2[1].cached_tokens >= warm[1].cached_tokens


def test_radix_concurrent_batch_admissions_identity(eng_off, eng_on):
    """Two requests batched TOGETHER both match tree chains (the pinned
    prefix at least) and share blocks read-only while decoding
    concurrently — still token-identical to the cold engine."""
    tok = eng_on.tokenizer
    prompts = [
        tok.encode(render_prompt("scroll down two pages then go back", {}),
                   bos=True),
        tok.encode(render_prompt("summarize this page for me please", {}),
                   bos=True),
    ]
    cold = _run(eng_off, prompts)
    warm = _run(eng_on, prompts)   # seeds the tree
    warm2 = _run(eng_on, prompts)  # both admissions hit concurrently
    for c, w, w2 in zip(cold, warm, warm2):
        assert c.error is None and w.error is None and w2.error is None
        assert c.token_ids == w.token_ids == w2.token_ids
    assert all(r.cached_tokens > len(eng_on.prefix_ids) for r in warm2)


def test_radix_insert_on_release_and_block_sharing(eng_on):
    """A released request's chain survives in the tree (its blocks stay
    resident under the tree's ref), and a warm admission physically shares
    them: same pool blocks, refcount > 1."""
    base_nodes = sum(t.nodes for t in eng_on.radix)
    ids = eng_on.tokenizer.encode(
        render_prompt("take a screenshot of this page", {}), bos=True)
    r = _run(eng_on, [ids])[0]
    assert r.error is None
    assert sum(t.nodes for t in eng_on.radix) > base_nodes
    # no live slots, but the chain's full blocks are tree-resident
    full = (len(ids) + len(r.token_ids)) // eng_on.block_size
    assert eng_on.allocator.blocks_in_use >= full
    # warm rerun: during admission the matched blocks are multi-owner
    r2 = _run(eng_on, [ids])[0]
    assert r2.token_ids == r.token_ids
    assert r2.cached_tokens >= full * eng_on.block_size


SESSIONS = [
    TURNS,
    [("navigate to example dot com", {}),
     ("take a screenshot of this page", {"last_url": "example.com"})],
    [("filter results under one hundred dollars", {}),
     ("extract the product table", {"last_query": "deals"})],
]


def test_radix_mid_chain_eviction_between_turns_identity(eng_off):
    """A deliberately undersized pool forces eviction of session chains
    between turns (distinct sessions pile divergent branches into the
    tree); admissions just match shorter (or no) chains and re-prefill —
    output stays token-identical and nothing double-frees."""
    eng = _paged(True, pool_blocks=10)
    install_prompt_prefix(eng)
    for turns in SESSIONS:
        cold, _ = _play_session(eng_off, turns=turns)
        warm, _ = _play_session(eng, turns=turns)
        for c, w in zip(cold, warm):
            assert c.token_ids == w.token_ids
    assert sum(t.evictions for t in eng.radix) > 0, \
        "pool was sized to force eviction churn"
    # refcount hygiene: with no slots live, everything resident is owned
    # by the tree (pinned prefix included)
    assert eng.allocator.blocks_in_use == sum(t.nodes for t in eng.radix)


def test_radix_eviction_never_frees_live_or_pinned(eng_on):
    """Direct contract probe on a live engine tree: evict() with a huge
    demand only reclaims unreferenced leaves — the pinned root chain and
    anything a caller still refs survive."""
    tree = eng_on.radix[0]
    alloc = eng_on.allocator
    pin_blocks = eng_on._prefix_blocks[0]
    ids = eng_on.tokenizer.encode(
        render_prompt("scroll down two pages", {}), bos=True)
    chain, matched = tree.match(ids)
    before = {b: alloc.refcount(b) for b in chain + pin_blocks}
    tree.evict(10_000)
    for b in chain + pin_blocks:
        assert alloc.refcount(b) == before[b] >= 1
    if chain:
        alloc.free(chain)


def test_prefill_split_and_metrics(eng_on):
    """cached_tokens + computed-only prefill_ms ride GenerationResult, and
    the radix/paged gauges + counters are exported."""
    from tpu_voice_agent.serve.paged import record_pool_gauges
    from tpu_voice_agent.serve.radix import record_radix_gauges
    from tpu_voice_agent.utils import get_metrics

    ids = eng_on.tokenizer.encode(
        render_prompt("filter results under one hundred dollars", {}), bos=True)
    r1 = _run(eng_on, [ids])[0]
    r2 = _run(eng_on, [ids])[0]
    assert r1.token_ids == r2.token_ids
    assert r2.cached_tokens >= r1.cached_tokens > 0
    assert r2.prefill_ms > 0.0
    record_pool_gauges(eng_on.allocator)
    record_radix_gauges(eng_on.radix)
    snap = get_metrics().snapshot()
    assert snap["gauges"]["radix.nodes"] > 0
    assert 0.0 < snap["gauges"]["radix.hit_rate"] <= 1.0
    assert snap["gauges"]["paged.kv_blocks_shared"] >= 0.0
    assert snap["counters"]["radix.cached_tokens"] > 0


# ---------------------------------------------------------------- sessions


def test_session_transcripts_strict_token_extension(eng_on):
    tok = eng_on.tokenizer
    t = SessionTranscripts(tok, max_sessions=2)
    p1 = t.prompt_for("s1", "search for cats", {})
    assert p1 == render_prompt("search for cats", {})  # turn 1: stateless
    gen = tok.encode('{"version":"1.0"}', bos=False)
    t.record("s1", p1, gen)
    p2 = t.prompt_for("s1", "open the first result", {"last_query": "cats"})
    base = tok.encode(p1, bos=True) + gen
    assert p2[: len(base)] == base  # strict token extension
    # deterministic frame rendering: context key order must not matter
    p2b = t.prompt_for("s1", "open the first result", {"last_query": "cats"})
    assert p2 == p2b
    assert (SessionTranscripts.user_frame("x", {"b": 1, "a": 2})
            == SessionTranscripts.user_frame("x", {"a": 2, "b": 1}))
    # LRU cap: two newer sessions push s1 out -> cold start again
    t.record("s2", "a", [1])
    t.record("s3", "b", [2])
    assert t.prompt_for("s1", "x", {}) == render_prompt("x", {})


def test_session_parser_radix_reuse_and_two_phase(trained):
    """Service integration: the session-aware BatchedEngineParser renders
    strict-extension prompts, warm turns report more cached tokens, and a
    speculative turn commits (cached plan, zero decode) on the matching
    final or is silently superseded."""
    from tpu_voice_agent.services.brain import BatchedEngineParser
    from tpu_voice_agent.utils.tracing import pop_stage_notes

    p = BatchedEngineParser(trained(True), chunk_steps=16, max_new_tokens=PARSE_TOKENS,
                            session_aware=True)
    try:
        pop_stage_notes()
        p.parse("search for cats", {}, session_id="it1")
        n1 = pop_stage_notes()
        p.parse("open the first result", {"last_query": "cats"}, session_id="it1")
        n2 = pop_stage_notes()
        assert n2["cached_tokens"] > n1["cached_tokens"] > 0
        # two-phase: speculative decode, then the matching final commits
        spec = p.parse("sort these by price", {"last_query": "cats"},
                       session_id="it1", speculative=True)
        pop_stage_notes()
        final = p.parse("sort these by price", {"last_query": "cats"},
                        session_id="it1")
        notes = pop_stage_notes()
        assert final.model_dump() == spec.model_dump()
        assert notes.get("cached_tokens", 0) > 0  # replayed from the spec turn
        # a mismatched final supersedes the pending turn instead of
        # delivering it
        spec2 = p.parse("scroll down", {}, session_id="it1", speculative=True)
        other = p.parse("go back", {}, session_id="it1")
        assert "it1" not in p._pending
        assert other is not spec2
    finally:
        p.close()


def test_stateless_parser_contract_unchanged(trained):
    """session_aware off: parse(text, context) works positionally (the
    pre-radix contract build_app relies on when wants_session is False)."""
    from tpu_voice_agent.services.brain import BatchedEngineParser

    p = BatchedEngineParser(trained(False), chunk_steps=16, max_new_tokens=PARSE_TOKENS)
    try:
        assert p.wants_session is False
        r = p.parse("take a screenshot", {})
        assert r.confidence >= 0.0
    finally:
        p.close()
