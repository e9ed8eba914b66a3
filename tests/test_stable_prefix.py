"""``Tokenizer.stable_prefix``: the ids of a text that no continuation can
change. The engine keeps them for the prompt's head and tokenizes only what
comes behind (``DecodeEngine.encode_prompt``), so the promise is held here as
a property, id for id, over the text a deployment sends — never argued:

    ids, n = tok.stable_prefix(head)
    ids + tok.encode((head + more).encode()[n:]) == tok.encode(head + more)
"""

import os
import random

import pytest

from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
from tpu_voice_agent.services import prompts

TOK = default_tokenizer()
LONGEST = max(len(p) for p in TOK.pieces)
MULTIBYTE = "é ü ß → “quoted” 漢字 かな 😀 👍🏽 naïve café"


def _holds(head: str, more: str) -> int:
    """The promise for one (head, continuation); returns the bytes kept."""
    ids, n = TOK.stable_prefix(head)
    whole = TOK.encode(head + more)
    assert ids + TOK.encode((head + more).encode()[n:]) == whole, (head[-40:], more[:40])
    # what is kept is the head's own encoding as far as it goes, on a token's edge
    assert TOK.encode(head)[:len(ids)] == ids and n <= len(head.encode())
    assert b"".join(TOK.token_bytes(i) for i in ids) == head.encode()[:n]
    assert n > len(head.encode()) - LONGEST  # and no less than can be kept
    return n


def _corpus() -> list[str]:
    from benchmark.lib.corpus import texts

    return texts(64)


def _prompt_cuts(rng):
    """Heads and continuations cut out of rendered prompts, anywhere."""
    for t in rng.sample(_corpus(), 8):
        p = prompts.render_prompt(t, {"last_query": "red shoes"})
        for _ in range(12):
            k = rng.randrange(len(p) + 1)
            yield p[:k], p[k:k + rng.randrange(0, 120)]


def _corpus_behind_the_head(rng):
    """What an admission sees: the head's text, then each text of the cell's
    corpus in its payload."""
    head = os.path.commonprefix([prompts.render_prompt(t, {}) for t in ("sample utterance alpha", "a rather different beta")])
    assert head.endswith('{"text":"')
    for t in _corpus():
        yield head, prompts.render_prompt(t, {})[len(head):]


def _multibyte(rng):
    """Multi-byte characters on both sides of the cut: the kept bytes may end
    inside one, and what is walked behind them is bytes, never decoded."""
    for _ in range(96):
        a = "".join(rng.choice(MULTIBYTE) for _ in range(rng.randrange(0, 50)))
        b = "".join(rng.choice(MULTIBYTE) for _ in range(rng.randrange(0, 30)))
        yield prompts.SYSTEM_PROMPT[:rng.randrange(0, 200)] + a, b + " click the button"


def _empty_suffix(rng):
    for t in rng.sample(_corpus(), 16):
        yield prompts.render_prompt(t, {}), ""
    yield "", ""


def _short_head(rng):
    """A head shorter than the longest piece: nothing of it is decided."""
    p = prompts.prompt_prefix()
    for k in range(LONGEST + 2):
        at = rng.randrange(len(p) - k)
        assert k >= LONGEST or TOK.stable_prefix(p[at:at + k]) == ([], 0)
        yield p[at:at + k], p[at + k:at + k + 60]


def _extends_the_last_piece(rng):
    """The head ends inside a long piece of the vocabulary and the
    continuation completes it: the head's own last tokens must not be kept."""
    long_pieces = [p.decode() for p in TOK.pieces if len(p) >= 4 and p.isascii()]
    for piece in rng.sample(long_pieces, 48):
        k = rng.randrange(1, len(piece))
        yield prompts.SYSTEM_PROMPT[:rng.randrange(20, 300)] + piece[:k], piece[k:] + piece


KINDS = {f.__name__.strip("_"): f for f in (
    _prompt_cuts, _corpus_behind_the_head, _multibyte, _empty_suffix, _short_head,
    _extends_the_last_piece)}


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_kept_ids_and_the_walk_behind_them_are_the_whole_encoding(kind, seed):
    pairs = list(KINDS[kind](random.Random(f"{kind}/{seed}")))
    assert len(pairs) >= 16
    for head, more in pairs:
        _holds(head, more)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_promise_holds_behind_a_site_context(seed):
    """The two long-head cells' shape at a test's size: a site context in the
    head, all of it but the last pieces kept."""
    rng = random.Random(seed)
    words = [w for w in (TOK.decode([i]).strip() for i in range(TOK.vocab_size)) if w.isalpha()]
    prompts.set_site_context(" ".join(rng.choice(words) for _ in range(400)))
    try:
        head = prompts.prompt_prefix() + '{"text":"'
        kept = [_holds(head, prompts.render_prompt(t, {})[len(head):]) for t in _corpus()[:16]]
    finally:
        prompts.set_site_context("")
    assert set(kept) == {kept[0]} and len(head.encode()) - LONGEST < kept[0] <= len(head.encode())


def test_bytes_are_walked_as_the_text_they_spell():
    for t in (MULTIBYTE, prompts.render_prompt("go back", {}), ""):
        assert TOK.encode(t.encode()) == TOK.encode(t)
        assert TOK.encode(t.encode(), bos=True, eos=True) == TOK.encode(t, bos=True, eos=True)
    # a cut inside a character: each half is walked as the bytes it is
    cut = "漢".encode()
    assert b"".join(TOK.token_bytes(i) for i in TOK.encode(cut[:1]) + TOK.encode(cut[1:])) == cut
