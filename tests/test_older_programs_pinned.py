"""The programs of the five model kinds the benchmark held before ISSUE 43 —
dense, routed, hybrid, a chip's share, a latent cache — that no test pinned
yet, held to the text the PARENT of ISSUE 43 (commit 90c46fe) lowers: the
GROUPED admission (``forward_paged_first_tokens`` at (``admit_rows``, 64)) of
all five, the one-row suffix prefill of the latent model and the one-row
1 + 8 block over the engine's pool (what the comparison that decides
``correct`` runs) of all five. ``tests/test_admit_group.py`` pins both chunk
widths of all five and the one-row prefill of the other four
(``tests/test_ffn_pack.py`` a 32-row block of three). ISSUE 43 added a model kind with a forward of its own
(``models/dots3.py``: ``LlamaConfig.index_topk``), planes by layer kind in
``serve/paged.py`` and a site context in ``services/prompts.py``: every older
program's text — what the entry points' compile cache keys on, so a chip run
LOADS the parent's executables — is what it was. A PR that changes one on
purpose re-derives its hash on its parent's tree (``_texts``) and says so.
ISSUE 58 re-derived all eleven, each held on its parent's tree (40ebd89)
first: an admission's covered blocks leave the pool in ONE gather on (plane,
block) (``llama.gather_row_blocks``) where a ``dynamic_slice`` of the whole
plane stood before the gather — and these engines attend through XLA, so
their one-row 1 + 8 block runs that branch too (the chip's goes through the
block kernel). ISSUE 60 re-derived all eleven (each held by the driver's run
of its parent's tree, adb1d6a): the K/V write is ``llama.write_rows`` — the
same pair of scatters where a forward is told no real positions (K's and V's
issued together), a walk over tiles of the real rows in the hybrid's grouped
admission, which is told them."""

import functools
import hashlib
import json

import jax
import jax.numpy as jnp
import pytest

from test_admit_group import TEXTS, _engine, _one_row_prefill_text
from tpu_voice_agent.services.prompts import render_prompt

KINDS = ["dense", "routed", "hybrid", "share", "latent"]


def _pick_logits(logits, state, slots, ns):
    return logits[:, 0, :]


def _lowered(fn, *a, **kw) -> str:
    """Scope names in, Python frames out."""
    frames = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        return fn.__wrapped__.lower(*a, **kw).as_text(debug_info=True)
    finally:
        jax.config.update("jax_traceback_in_locations_limit", frames)


def _texts(eng) -> dict:
    """{"group", "block"}: the lowered text of a group of two's admission and
    of the one-row 1 + W block over the engine's pool."""
    from tpu_voice_agent.models.llama import forward_paged
    from tpu_voice_agent.serve import paged

    texts, first_tokens = {}, paged.forward_paged_first_tokens

    def spy(*a, **kw):
        texts["group"] = _lowered(first_tokens, *a, **kw)
        return first_tokens(*a, **kw)

    paged.forward_paged_first_tokens = spy
    try:
        ids = [eng.tokenizer.encode(render_prompt(t, {}), bos=True) for t in TEXTS[:2]]
        eng.admit_group([eng.prepare_admission(i, s) for s, i in enumerate(ids)], pick=_pick_logits)
    finally:
        paged.forward_paged_first_tokens = first_tokens
        for slot in (0, 1):
            eng.release_slot(slot, ok=False)
    W = eng.fast_forward
    texts["block"] = _lowered(
        forward_paged, eng.params, eng.cfg, jnp.zeros((1, 1 + W), jnp.int32),
        (900 + jnp.arange(1 + W, dtype=jnp.int32))[None], eng.k_pool, eng.v_pool,
        eng.block_tables[0][None], rules=eng.rules, attn_impl=eng.kernels, k_scale=eng.k_scale,
        v_scale=eng.v_scale, kv_quant=eng.kv_quant)
    return texts


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()


GROUP_SHA256 = {
    "dense": "c660f9a40d0d714a91e7beb5617252f950cd9137c1911cacb016d3e1a5201101",
    "routed": "1b2e18edc494b58ef6cdef0e8e25e8b10911d4439765c76cd53291ef3bbd3002",
    "hybrid": "51f724bfea7173126b0134b89c889d01b73f97296cba0a426fef800b99a5ceef",
    "share": "2878bedbdf5f64da0436085de675d8f1e1d78029c07f0c444df92e3707cc9f76",
    "latent": "b34710488aaf21d0cc9a9638ffe07d5deae53fc59bf6853817a490429580eef6",
}
BLOCK_SHA256 = {
    "dense": "7c2107aa05c775a5f7582d54891620c03341f58b8cb357d002e6a2cac79bb4dc",
    "routed": "ba97552f1c33d99614cfe71299bd5a430eff18f61c908edad4c815f2eb17bd25",
    "hybrid": "45fa4e5f3ecf39c2df110c0a00aca72bb35776c91c0c486633e4da5a9125191b",
    "share": "73d794ebad9363ea10114239e70c980dd103084a89a31b8d87f5e761f22aac55",
    "latent": "d2c4f887cbe6ee5f91e5f466aacd017b445e6dde8761f7d5140c2129f7e6b214",
}
ONE_ROW_SHA256 = {"latent": "7cec90a9b8a7060bfa96be805a21d6bfc0fc64cafda675e51fe6354f00462cf5"}


@functools.lru_cache(maxsize=None)
def _built(kind: str):
    eng = _engine(kind)
    return eng, _texts(eng)


@pytest.fixture(scope="module", params=KINDS)
def lowered(request):
    return (request.param, *_built(request.param))


def test_the_grouped_admission_program_is_the_parents(lowered):
    kind, eng, texts = lowered
    assert eng.admit_rows == 4 and "tensor<4x64xi32>" in texts["group"]
    assert _sha(texts["group"]) == GROUP_SHA256[kind]


def test_the_one_row_block_is_the_parents(lowered):
    kind, eng, texts = lowered
    assert "tensor<1x9xi32>" in texts["block"]
    assert _sha(texts["block"]) == BLOCK_SHA256[kind]


def test_the_latent_one_row_prefill_is_the_parents():
    """``tests/test_admit_group.py`` pins the other four's."""
    assert _sha(_one_row_prefill_text(_built("latent")[0])) == ONE_ROW_SHA256["latent"]


def test_the_default_prompt_head_is_879_tokens_and_a_site_context_lengthens_it(lowered):
    """Every older cell's cached head, token for token: 879 tokens with no
    site context; a site context sits behind the system prompt and before the
    exemplars, in every rendered prompt alike."""
    from tpu_voice_agent.services import prompts

    kind, eng, _ = lowered
    assert prompts.site_context() == "" and len(eng.prefix_ids) == 879
    bare = [eng.tokenizer.encode(render_prompt(t, {}), bos=True) for t in TEXTS[:2]]
    prompts.set_site_context("the cart page lists items; the checkout button is below the total")
    try:
        with_site = [eng.tokenizer.encode(render_prompt(t, {}), bos=True) for t in TEXTS[:2]]
        text = render_prompt(TEXTS[0], {})
    finally:
        prompts.set_site_context("")
    first_exemplar = json.dumps(prompts.FEWSHOTS[0][0], separators=(",", ":"))
    assert text.index(prompts.SYSTEM_PROMPT) < text.index("Site context:") < text.index(first_exemplar)
    grown = len(with_site[0]) - len(bare[0])
    assert grown == len(with_site[1]) - len(bare[1]) > 10
    assert [eng.tokenizer.encode(render_prompt(t, {}), bos=True) for t in TEXTS[:2]] == bare
