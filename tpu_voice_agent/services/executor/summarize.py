"""LLM-backed page summarization for the ``summarize`` intent.

The reference never implemented summarize beyond a stub (legacy
apps/executor/src/actions.js:244-251 returned a fixed string; the live
actions.ts dropped the case entirely). This framework has an in-tree decode
engine, so ``summarize`` can actually summarize: an UNCONSTRAINED greedy
decode over a summarization prompt (the grammar FSM only gates constrained
decodes; free text is the right output shape here).

``TPUSummarizer`` mirrors ``grounding.TPUGrounder``: lazily constructed so
the executor stays importable without JAX backend init, injected into
``run_intents`` as a plain callable so tests fake it trivially.
"""

from __future__ import annotations

from typing import Callable

Summarizer = Callable[[str, str], str]  # (title, body) -> summary


def render_summarize_prompt(title: str, body: str, max_body_chars: int = 4000) -> str:
    body = " ".join(body.split())[:max_body_chars]
    title = " ".join(title.split())[:160]  # a title past this is hostile input
    return (
        "<|user|>\nSummarize this web page in 2-3 sentences for a voice "
        f"assistant to read aloud.\nTitle: {title}\nContent: {body}\n<|assistant|>\n"
    )


class TPUSummarizer:
    """serve.DecodeEngine as an executor Summarizer (lazy; own tiny engine
    unless an engine is shared in)."""

    def __init__(self, preset: str | None = None, model_dir: str | None = None,
                 engine=None, max_new_tokens: int = 160):
        import threading

        self.preset = preset or "tinyllama-1.1b"
        self.model_dir = model_dir
        self.max_new_tokens = max_new_tokens
        self._engine = engine
        self._build_lock = threading.Lock()  # warm thread vs request thread

    def _get(self):
        with self._build_lock:
            if self._engine is None:
                from ...serve import DecodeEngine

                if self.model_dir:
                    self._engine = DecodeEngine.from_hf(self.model_dir)
                else:
                    self._engine = DecodeEngine(preset=self.preset)
            return self._engine

    def __call__(self, title: str, body: str) -> str:
        engine = self._get()
        # fit the prompt inside the engine's prefill buckets AND leave decode
        # headroom in the cache: token-measure with the engine's own
        # tokenizer (the in-tree toy tokenizer runs ~1 token/char, so a
        # fixed char cap would overflow every bucket and silently force the
        # truncation fallback — the mode would never summarize)
        limit = min(engine.prefill_buckets[-1],
                    engine.max_len - self.max_new_tokens - 2)
        prompt = None
        for cap in (4000, 2000, 1000, 500, 240, 100, 40):
            prompt = render_summarize_prompt(title, body, max_body_chars=cap)
            if len(engine.encode_prompt(prompt)[0]) <= limit:
                break
        else:
            # even the smallest cap overflows (sub-word-bucket engine):
            # raise — actions falls back to truncation and counts the miss
            raise RuntimeError(
                f"summarize prompt cannot fit engine buckets (limit {limit})")
        res = engine.generate(
            prompt,
            max_new_tokens=self.max_new_tokens,
            constrained=False, greedy=True, byte_budget=800,
        )
        text = res.text.strip()
        if not text:
            raise RuntimeError("summarizer produced empty text")
        return text

    def warm(self) -> None:
        """Build the engine (checkpoint load + compile) off the request
        path — the server calls this from a startup thread so the first
        summarize doesn't stall every session behind exec_lock."""
        self._get()


def make_summarizer_from_env() -> Summarizer | None:
    """EXECUTOR_SUMMARIZE env -> Summarizer | None.

    ``engine[:preset]`` decodes on a random-init preset (shape/latency work);
    ``hf:<dir>`` serves a real checkpoint; unset keeps the truncation
    fallback in actions._run_one."""
    import os

    spec = os.environ.get("EXECUTOR_SUMMARIZE", "").strip()
    if not spec:
        return None
    name, _, arg = spec.partition(":")
    if name == "engine":
        return TPUSummarizer(preset=arg or None)
    if name == "hf":
        if not arg:
            raise ValueError("EXECUTOR_SUMMARIZE=hf:<checkpoint dir> needs a dir")
        return TPUSummarizer(model_dir=arg)
    raise ValueError(f"unknown EXECUTOR_SUMMARIZE {spec!r}")
