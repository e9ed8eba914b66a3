"""The comparison that decides ``correct``: the SERVED engines against the
plain references of ``benchmark/reference/``, on the run's own weights at
the width it served, after the measured window (outside ``setup_s``).

Logits, not tokens: with random weights the largest logit changes on
rounding. The measure is max|served - reference| over a logits row as a
share of that row's range (max|reference|), the worst row of the sample.

Tolerances and why (numbers: my chip runs, PR 23, TPU v5e, full width):

decoder, ``DECODER_TOL`` = 3 %. The served path holds the int8 weights
exactly (the reference dequantises the same q and s) and differs by bf16
activations and bf16 K/V through 32 layers with f32 accumulation, Pallas
attention included. PR 21 measured two served paths (pallas vs xla
attention) 0.9-1.6 % of the range apart; against float32 the served path
measured 1.30-1.51 % in every run. The negative control — the same
reference with its weights re-quantised to int4, which is what "computing
in a lower precision than the configuration states" would be — measured
80-84 %, and has to land ABOVE the tolerance in the same run or the run is
not correct. 3 % is twice what was measured and a twenty-fifth of the
control.

whisper, ``WHISPER_TOL`` = 3 %. bf16 weights AND bf16 activations through
32 + 32 layers against float32, the program's tanh GELU against the
published erf form: measured 1.48-1.69 %. The control rounds the
reference's weights to float8 (e4m3) and measured 10.7-12.6 %.
"""

from __future__ import annotations

import time

DECODER_TOL = 0.03
WHISPER_TOL = 0.03


def _rel_err(got, want) -> tuple[float, int]:
    """Worst row's max|got - want| / max|want|, and top-1 agreements."""
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        return float("inf"), 0
    rel = np.max(np.abs(got - want), axis=-1) / np.max(np.abs(want), axis=-1)
    return float(rel.max()), int((got.argmax(-1) == want.argmax(-1)).sum())


def check_decoder(served, seed: int, say) -> dict:
    """A seeded prompt's prefill (cached prefix + suffix through the paged
    pool), three T=1 decode steps (the paged-attention kernel) and one
    1+W fast-forward block (the paged block kernel), teacher-forced on the
    served argmax, against the reference's full forward."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_voice_agent.models.llama import forward_paged
    from tpu_voice_agent.services.prompts import render_prompt

    from ..reference import decoder as ref
    from .corpus import texts

    eng = served.engine
    m = served.dims["model"]
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

    t0 = time.perf_counter()
    served.parser.runtime.submit_call(served_side).result()  # on the serving thread
    kw = dict(n_layers=eng.cfg.n_layers, nq=eng.cfg.n_heads, nkv=eng.cfg.n_kv_heads,
              eps=float(m["rms_norm_eps"]), theta=float(m["rope_theta"]),
              window=int(m.get("sliding_window", 1 << 30)), last=len(rows),
              pad_to=-(-(len(toks) + 32) // 128) * 128)
    want = ref.forward(eng.params, toks, **kw)
    rel, top1 = _rel_err(np.stack(rows), want)
    ctrl, _ = _rel_err(ref.forward(eng.params, toks, fake_bits=4, **kw), want)
    jax.block_until_ready(want)
    ok = rel <= DECODER_TOL < ctrl
    say(f"reference decoder: {len(toks)} tokens ({len(eng.prefix_ids)} from the cached prefix), "
        f"{len(rows)} logit rows (prefill, 3 x T=1, 1 x T={1 + W}); worst max|served-ref|/max|ref| "
        f"= {rel:.5f} (tolerance {DECODER_TOL}), top-1 agree {top1}/{len(rows)}; int4 control "
        f"{ctrl:.5f} (must exceed the tolerance); {time.perf_counter() - t0:.1f}s -> "
        f"{'ok' if ok else 'FAIL'}")
    return {"ok": ok, "rel_err": rel, "control": ctrl, "rows": len(rows)}


def check_whisper(served, seed: int, say) -> dict:
    """One seeded utterance: the served encode (mel -> bucketed encoder with
    the flash kernel -> cross-KV) and four teacher-forced T=1 decoder steps
    (the decode-attention kernel) against the reference encoder + decoder
    on the same mel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_voice_agent.audio.mel import log_mel_spectrogram
    from tpu_voice_agent.models.whisper import decoder_forward, init_self_cache

    from ..reference import whisper as ref
    from .audio import synth_utterance

    eng = served.stt_engine
    cfg = eng.cfg
    secs = 1.5 + (seed % 7) * 0.25
    audio = synth_utterance(secs)[-eng.frame_buckets[-1] * eng.mel_cfg.hop:]
    t0 = time.perf_counter()
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
    n_valid = max(1, n_frames // 2)
    kw = dict(nh=cfg.n_heads, eps=cfg.norm_eps)

    def reference(via=None):
        enc = ref.encoder(eng.params["encoder"], mel, via=via, **kw)
        return ref.decoder(eng.params["decoder"], jnp.asarray(toks[:-1], jnp.int32), enc,
                           n_valid, via=via, **kw)[len(eng.bos_ids) - 1:]

    want = reference()
    rel, top1 = _rel_err(np.stack(rows), want)
    ctrl, _ = _rel_err(reference(via=jnp.float8_e4m3fn), want)
    jax.block_until_ready(want)
    ok = rel <= WHISPER_TOL < ctrl
    say(f"reference whisper: {secs:.2f}s utterance, {n_frames} mel frames in bucket {bucket}, "
        f"{len(rows)} logit rows; worst max|served-ref|/max|ref| = {rel:.5f} (tolerance "
        f"{WHISPER_TOL}), top-1 agree {top1}/{len(rows)}; float8 control {ctrl:.5f} (must exceed "
        f"the tolerance); {time.perf_counter() - t0:.1f}s -> {'ok' if ok else 'FAIL'}")
    return {"ok": ok, "rel_err": rel, "control": ctrl, "rows": len(rows)}
