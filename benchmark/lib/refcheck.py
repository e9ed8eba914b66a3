"""The comparison that decides ``correct``: the SERVED engines against the
plain references their configuration NAMES (``benchmark/reference/<name>.py``,
a configuration's ``reference`` key), on the run's own weights at the width
it served, after the measured window (outside ``setup_s``).

Logits, not tokens: with random weights the largest logit changes on
rounding. The measure is max|served - reference| over a logits row as a
share of that row's range (max|reference|), the worst row of the sample.

Two halves. The SERVED half is here, one sampler per engine the program has
(``SAMPLERS``): it drives the program's own API and knows nothing of the
block type inside. The REFERENCE half is the named module: its forward, the
sizes it reads from the configuration's own keys, its negative control and
its ``TOLERANCE`` with the readings it was set from (README.md "What a
reference module owes"). A run is correct only if the served rows are within
the tolerance AND the control, in the same run, is above it.
"""

from __future__ import annotations

import time

from .manifest import load_code, references_of


def _rel_err(got, want) -> tuple[float, int]:
    """Worst row's max|got - want| / max|want|, and top-1 agreements."""
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        return float("inf"), 0
    rel = np.max(np.abs(got - want), axis=-1) / np.max(np.abs(want), axis=-1)
    return float(rel.max()), int((got.argmax(-1) == want.argmax(-1)).sum())


def sample_paged_decoder(served, seed: int) -> tuple:
    """Any decoder ``PagedDecodeEngine`` serves: a seeded prompt's prefill
    (cached prefix + suffix through the paged pool), three T=1 decode steps
    (the paged-attention kernel) and one 1+W fast-forward block (the paged
    block kernel), teacher-forced on the served argmax, on the serving
    thread. -> (params, model keys, sample for ``logits``, served rows, what
    was sampled)."""
    import jax.numpy as jnp
    import numpy as np

    from tpu_voice_agent.models.llama import forward_paged
    from tpu_voice_agent.services.prompts import render_prompt

    from .corpus import texts

    eng = served.engine
    corpus = texts(64)
    ids = eng.tokenizer.encode(render_prompt(corpus[seed % len(corpus)], {}), bos=True)
    live = eng.tokenizer.vocab_size
    W = eng.fast_forward
    rows, toks = [], list(ids)

    def paged(tokens: list[int], pos0: int):
        out = forward_paged(
            eng.params, eng.cfg, jnp.asarray([tokens], jnp.int32),
            (pos0 + jnp.arange(len(tokens), dtype=jnp.int32))[None, :],
            eng.k_pool, eng.v_pool, eng.block_tables[0][None], rules=eng.rules,
            attn_impl=eng.kernels, k_scale=eng.k_scale, v_scale=eng.v_scale,
            kv_quant=eng.kv_quant)
        logits, eng.k_pool, eng.v_pool, eng.k_scale, eng.v_scale = out
        return np.asarray(logits[0], np.float32)

    def served_side() -> None:
        first = np.asarray(eng.prefill_slot(ids, 0), np.float32)  # (1, V)
        rows.append(first[0])
        try:
            if len(ids) + 3 + 1 + W > eng._covered[0]:
                raise RuntimeError("comparison would write past the slot's covered blocks")
            for _ in range(3):
                toks.append(int(rows[-1][:live].argmax()))
                rows.append(paged(toks[-1:], len(toks) - 1)[0])
            block = [int(rows[-1][:live].argmax())] + [int(t) for t in ids[1:1 + W]]
            toks.extend(block)
            rows.extend(paged(block, len(toks) - len(block)))
        finally:
            eng.release_slot(0, ok=False)

    served.parser.runtime.submit_call(served_side).result()  # on the serving thread
    return (eng.params, served.dims["model"], {"tokens": toks, "rows": len(rows)}, np.stack(rows),
            f"{len(toks)} tokens ({len(eng.prefix_ids)} from the cached prefix), {len(rows)} "
            f"logit rows (prefill, 3 x T=1, 1 x T={1 + W})")


def sample_speech(served, seed: int) -> tuple:
    """One seeded utterance through ``SpeechEngine``: the served encode (mel
    -> bucketed encoder with the flash kernel -> cross-KV) and four
    teacher-forced T=1 decoder steps (the decode-attention kernel); the
    reference gets the same mel."""
    import jax.numpy as jnp
    import numpy as np

    from tpu_voice_agent.audio.mel import log_mel_spectrogram
    from tpu_voice_agent.models.whisper import decoder_forward, init_self_cache

    from .audio import synth_utterance

    eng = served.stt_engine
    cfg = eng.cfg
    secs = 1.5 + (seed % 7) * 0.25
    audio = synth_utterance(secs)[-eng.frame_buckets[-1] * eng.mel_cfg.hop:]
    cross_kv, valid, n_frames = eng._encode_window(audio)  # what transcribe() runs
    bucket = eng._bucket(n_frames)
    cache = init_self_cache(cfg, 1, dtype=eng._param_dtype)
    live = eng.tokenizer.vocab_size
    toks, rows = list(eng.bos_ids), []
    for step in range(4):
        pos = len(toks) - 1
        logits, cache = decoder_forward(
            eng.params, cfg, jnp.asarray([toks[-1:]], jnp.int32), jnp.asarray([[pos]], jnp.int32),
            cache, cross_kv, valid, attn_impl=eng.kernels)
        rows.append(np.asarray(logits[0, 0], np.float32))
        toks.append(int(rows[-1][:live].argmax()))
    padded = np.zeros(bucket * eng.mel_cfg.hop, np.float32)
    padded[: len(audio)] = audio[: len(padded)]
    mel = log_mel_spectrogram(jnp.asarray(padded), eng.mel_cfg)[:bucket]
    sample = {"mel": mel, "tokens": toks[:-1], "n_valid": max(1, n_frames // 2),
              "first": len(eng.bos_ids) - 1}
    return (eng.params, served.dims["whisper"]["model"], sample, np.stack(rows),
            f"{secs:.2f}s utterance, {n_frames} mel frames in bucket {bucket}, {len(rows)} logit rows")


# the served half, by the name a reference module gives as its SAMPLE
SAMPLERS = {"paged_decoder": sample_paged_decoder, "speech": sample_speech}


def compare(served, config: dict, seed: int, say) -> list[dict]:
    """Every engine the builder served against the reference its
    configuration names; one line a reference, each number beside its limit."""
    import jax

    seen = []
    for name in references_of(config):
        ref = load_code("reference", name)
        t0 = time.perf_counter()
        params, model, sample, rows, what = SAMPLERS[ref.SAMPLE](served, seed)
        want = ref.logits(params, model, sample)
        rel, top1 = _rel_err(rows, want)
        ctrl, _ = _rel_err(ref.logits(params, model, sample, control=True), want)
        jax.block_until_ready(want)
        ok = rel <= ref.TOLERANCE < ctrl
        say(f"reference {name}: {what}; worst max|served-ref|/max|ref| = {rel:.5f} (tolerance "
            f"{ref.TOLERANCE}), top-1 agree {top1}/{len(rows)}; {ref.CONTROL} control {ctrl:.5f} "
            f"(must exceed the tolerance); {time.perf_counter() - t0:.1f}s -> {'ok' if ok else 'FAIL'}")
        seen.append({"reference": name, "ok": ok, "rel_err": rel, "control": ctrl})
    return seen
