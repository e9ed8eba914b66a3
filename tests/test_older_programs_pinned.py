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
purpose re-derives its hash on its parent's tree (``_texts``) and says so."""

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
    "dense": "22524698c3b1d71ff728c7215eb149dd0a3d0e94a46f76a5d517727e8f6a78dd",
    "routed": "dafb520f8c61e940d2c3fd99155e60efdf610f50e78d99ef001a0c95c4e485b9",
    "hybrid": "549545176fb0542421bbfdfcac9839965c769cc463b478a5cd58ad3f4577d798",
    "share": "4e8b2a20d506addf970297f60d3b9576be8ce6cbbefb13d3bedb30c4c1887cd9",
    "latent": "ea989309dd32bf9ff8f862c7ab15cbf11e4a540cd6db009b5e4908257635fefd",
}
BLOCK_SHA256 = {
    "dense": "d5e2511ae1f7682872fb2f1bc36a8884ce8fe15c8283a3358a4fe8163c3891a6",
    "routed": "fea6fbfba597ad7171319ba803eda797c1880c522df8937dc5386ccc5108ad1f",
    "hybrid": "7a327bad66726f8026c9369822b58b55ed741bfe6943bfcef0514079bdc254c8",
    "share": "e07fe19a48f64fc9fd8307bcebe9da9fa36beea0b80d04ac4a582102dc3e70c5",
    "latent": "56a77e8468c62c1ce0fb5d3031b31876229e267a2d2718028c9311aa0f1d9e8b",
}
ONE_ROW_SHA256 = {"latent": "7d601f093f6b2931a6512bea9f217c01b62629c049d05e4867ff1017c543303f"}


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
