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
