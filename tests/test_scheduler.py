"""Continuous batching: concurrent slots must be isolated and all outputs
grammar-valid; batch composition must not change a greedy request's tokens."""

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
