"""Benchmark: TRUE voice->intent latency on the in-tree serving stack.

Measures the BASELINE.md primary metric end to end on real hardware: from
the moment the speaker stops talking (first silence sample), through energy
endpointing (350 ms trailing window), the full-window Whisper final
transcription, and the grammar-constrained intent parse (shared-prefix
prefill + 64-token constrained decode) on a TinyLlama-1.1B-class int8
decoder. Both models are resident on the one chip (the colocation the
reference buys from two cloud vendors — apps/voice/src/deepgram.ts +
apps/brain/src/llm.ts).

Round-1's metric (parse-only, named as if it were voice->intent) is kept as
a stderr breakdown row; the ONE stdout JSON line is the honest end-to-end
number. stderr also reports ms/token and the fraction of the weight-read
HBM roofline the decode achieves, so perf regressions are visible
(VERDICT round-1 next #9).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def synth_utterance(seconds: float, sr: int = 16_000) -> np.ndarray:
    """Speech-like audio: modulated tone bursts over a noise floor."""
    rng = np.random.default_rng(0)
    t = np.arange(int(sr * seconds)) / sr
    return (
        0.2 * np.sin(2 * np.pi * 220 * t) * (np.sin(2 * np.pi * 2.5 * t) > -0.3)
        + 0.002 * rng.standard_normal(len(t))
    ).astype(np.float32)


def int8_weight_bytes(cfg) -> float:
    """HBM bytes read PER DECODE TOKEN for the int8 engine: every int8
    matmul weight (incl. the int8 lm_head) is streamed once; the bf16
    embedding contributes only a one-row gather (dim * 2 bytes)."""
    from tpu_voice_agent.models.llama import param_count

    total = param_count(cfg)  # parameter count; embed + lm_head both inside
    embed = cfg.vocab_size * cfg.dim
    matmul_int8 = (total - 2 * embed) + embed  # layers + lm_head, 1 B each
    return float(matmul_int8 + cfg.dim * 2)


def main() -> None:
    from tpu_voice_agent.ops.backend import measurement_devices
    from tpu_voice_agent.utils.compilecache import place_compile_cache

    place_compile_cache()
    devices = measurement_devices()  # exits unless TPU or JAX_PLATFORMS=cpu
    on_tpu = devices[0].platform == "tpu"
    print(f"[bench] devices: {devices}", file=sys.stderr)
    if not on_tpu:
        print("[bench] NOTE: explicit CPU run (JAX_PLATFORMS=cpu) at "
              "test-tiny/whisper-test widths — a count of work, not a "
              "device number", file=sys.stderr)

    from tpu_voice_agent.serve import DecodeEngine
    from tpu_voice_agent.serve.stt import SpeechEngine, StreamingSTT
    from tpu_voice_agent.services.brain import install_prompt_prefix
    from tpu_voice_agent.services.prompts import render_prompt

    # --neural: the zero-egress neural loop (VERDICT round-4 next #5) —
    # every model is an in-tree TRAINED checkpoint (whisper STT + distilled
    # intent parser through the same grammar-constrained engine), driven by
    # acoustic-font renders of the eval utterances instead of the synthetic
    # tone. Same harness, same timing definition, separate metric name.
    neural = "--neural" in sys.argv[1:]
    if neural:
        from tpu_voice_agent.models.llama import LlamaConfig
        from tpu_voice_agent.models.whisper import WhisperConfig
        from tpu_voice_agent.train import distill

        iload = distill.load_ckpt("checkpoints", distill.INTENT_CKPT,
                                  LlamaConfig)
        wload = (distill.load_ckpt("checkpoints", distill.WHISPER_GEN_CKPT,
                                   WhisperConfig)
                 or distill.load_ckpt("checkpoints", distill.WHISPER_CKPT,
                                      WhisperConfig))
        if iload is None or wload is None:
            print("[bench] --neural needs the trained checkpoints under "
                  "checkpoints/ (python -m tpu_voice_agent.train.make_tiny_ckpts)",
                  file=sys.stderr)
            sys.exit(2)
        parser = distill.intent_engine_from(*iload)
        engine = parser.engine  # the underlying constrained DecodeEngine
        stt_engine = distill.whisper_engine_from(*wload)

        def parse_text(text: str) -> None:
            parser.parse(text, {})
    else:
        # ---- intent engine (int8 weight-only: decode is HBM-bound on
        # weights). max_len sized to the workload (prefix ~880 + suffix +
        # 64 generated): the decode loop's cache carry costs HBM traffic
        # proportional to capacity on every step, so capacity the workload
        # can't use is pure tax
        preset = "tinyllama-1.1b" if on_tpu else "test-tiny"
        engine = DecodeEngine(preset=preset, max_len=1024,
                              prefill_buckets=(1024,),
                              quant="int8" if on_tpu else None,
                              fast_forward=8)  # forced-chain tokens ride
        # the memory-bound step free: fewer forwards per intent JSON
        prefix_len = install_prompt_prefix(engine)
        print(f"[bench] prompt prefix cached: {prefix_len} tokens",
              file=sys.stderr)

        # ---- speech engine, colocated on the same chip
        stt_preset = "whisper-large-v3" if on_tpu else "whisper-test"
        # whisper-test (the explicit CPU run) caps at 200 frames; buckets must fit
        stt_buckets = (300, 1000) if on_tpu else (100, 200)
        stt_engine = SpeechEngine(preset=stt_preset,
                                  frame_buckets=stt_buckets,
                                  max_new_tokens=32)

        # random weights never emit EOS, so the decode budget IS the parse
        # cost here. 64 tokens is the metric DEFINITION every round has
        # used — a measured quantity rather than an assumption: real plans for
        # these utterances tokenize to 51-81 tokens, corpus-wide p50 68 /
        # p95 128 (benches/bench_batch.py plan_tokens rows), so 64 sits at
        # the single-intent median. A real checkpoint's EOS behavior is
        # benchmarked for real by --neural (the distilled parser emits
        # genuine EOS at its true plan length); on one CPU core a
        # full-length 81-128-token random decode outlives the endpoint
        # window entirely, which measures core contention, not serving.
        def parse_text(text: str) -> None:
            # random-weight STT transcribes unbounded garbage (json-escaped
            # to \uXXXX, up to ~6 tokens per char) and the prompt prefix
            # alone is ~890 tokens of the 1024 budget: an unlucky transcript
            # overflows prefill and kills the bench. Shrink the tail until
            # the prompt fits; a real utterance fits on the first try.
            for clamp in (100, 50, 20, 8, 0):
                prompt = render_prompt(text[:clamp], {"last_query": None})
                if len(engine.tokenizer.encode(prompt, bos=True)) <= 1024 - 66:
                    break
            engine.generate(prompt, max_new_tokens=64, greedy=True)
    # adaptive endpointing (round-4 next #9: the fixed 350 ms window had
    # become 97% of the measured e2e). Speculate eagerly at 120 ms of
    # silence — wasted transcribes on inter-word gaps cost ~15 ms each on
    # CPU — and let a stable transcript + grammar-complete parse close the
    # utterance once 240 ms of silence AND the parse have both landed,
    # instead of always waiting out 350. The web client ships 60 ms
    # frames, so closes quantize to chunk boundaries: on CPU the measured
    # spec pipeline (15 ms STT + ~150-210 ms for a measured-length plan
    # decode) completes around 290-340 ms, so short-plan utterances close
    # at the 300 ms chunk and long-plan ones ride the full window; on-chip
    # the same knobs floor at 240 ms because the parse is memory-bound
    # fast there.
    from tpu_voice_agent.audio.endpoint import EnergyEndpointer

    endpointer = EnergyEndpointer(spec_silence_ms=120)
    stt = StreamingSTT(stt_engine, endpointer=endpointer, early_close_ms=240.0)

    sr, frame_ms = 16_000, 60  # the web client ships ~60 ms PCM frames
    frame = sr * frame_ms // 1000
    silence = np.zeros(sr, dtype=np.float32)  # 1 s tail; endpoint fires at 350 ms

    if neural:
        # the trained whisper reads the acoustic font; speak the actual
        # eval utterances so the transcripts (and hence the parses) are
        # real model output end to end
        utterances = distill.WHISPER_EVAL_TEXTS[:5]
        speeches = [distill.render_speech(u) for u in utterances]
    else:
        utterances = [
            "search for wireless headphones",
            "sort these by price from low to high",
            "open the second result and take a screenshot",
            "filter results under one hundred dollars",
            "upload my resume and submit the form",
        ]
        speeches = [synth_utterance(2.0)]

    # ---- warmup: every compiled program on both engines (short AND long
    # utterances cover both suffix prefill buckets)
    for u in (utterances[0], utterances[2] + " and also " + utterances[3]):
        parse_text(u)
    stt_engine.warmup()
    stt.feed(speeches[0][:frame])
    stt.reset()

    # frames are fed at their REAL-TIME deadlines, as the mic would deliver
    # them — this is what lets the speculative final transcription AND the
    # speculative parse hide inside the endpoint's wall-clock
    # trailing-silence window (VERDICT round-3 next #3: the voice service
    # starts /parse on the spec_final event; this harness mirrors that)
    from concurrent.futures import ThreadPoolExecutor

    spec_pool = ThreadPoolExecutor(1, thread_name_prefix="spec-parse")
    spec: dict = {"text": None, "fut": None}

    def spec_launch(text: str) -> None:
        if spec["text"] == text and spec["fut"] is not None:
            return
        if spec["fut"] is not None:
            spec["fut"].result()  # single-slot engine: serialize generations
        def run():
            parse_text(text)
            # grammar-complete: arm the adaptive early close (feed-side
            # revalidation makes a stale notification inert)
            stt.parse_complete(text)
            return time.perf_counter()
        spec["text"], spec["fut"] = text, spec_pool.submit(run)

    def feed_paced(audio: np.ndarray, deadline: float) -> tuple[str | None, float]:
        final_text = None
        for j in range(0, len(audio) - frame, frame):
            deadline += frame_ms / 1e3
            now = time.perf_counter()
            if now < deadline:
                time.sleep(deadline - now)
            for kind, text in stt.feed(audio[j:j + frame]):
                if kind == "final":
                    final_text = text
                elif kind == "spec_final":
                    spec_launch(text)
            # an emptied stream buffer means the utterance closed even when
            # the transcript was empty (random weights) — the clock must
            # stop here either way or the metric silently inflates
            if final_text is not None or (j > 0 and len(stt._buf) == 0):
                break
        return final_text, deadline

    e2e_ms, stt_ms, parse_ms = [], [], []
    spec_hits = 0
    for i in range(9):
        stt.reset()
        old = spec["fut"]
        spec["text"], spec["fut"] = None, None
        if old is not None:
            old.result()  # drain any carryover before reusing the engine
        _, t_end_speech = feed_paced(speeches[i % len(speeches)],
                                     time.perf_counter())
        t0 = t_end_speech  # the real-time moment the speaker stopped
        final_text, _ = feed_paced(silence, t_end_speech)
        t1 = time.perf_counter()
        if (final_text and spec["fut"] is not None
                and spec["text"] == final_text):
            # speculation hit: the parse ran inside the endpoint window;
            # e2e ends when BOTH the endpoint confirmed and the parse landed
            t2 = max(t1, spec["fut"].result())
            spec_hits += 1
        else:
            if spec["fut"] is not None:
                spec["fut"].result()  # wasted speculation; drain the slot
            # random weights transcribe garbage; parse cost is what's
            # measured, so fall back to a fixed utterance on an empty final
            text = final_text or utterances[i % len(utterances)]
            parse_text(text)
            t2 = time.perf_counter()
        stt_ms.append((t1 - t0) * 1e3)
        parse_ms.append((t2 - t1) * 1e3)
        e2e_ms.append((t2 - t0) * 1e3)

    print(f"[bench] e2e runs (ms): {[round(x, 1) for x in e2e_ms]}",
          file=sys.stderr)
    p50 = float(np.percentile(e2e_ms, 50))
    p95 = float(np.percentile(e2e_ms, 95))
    stt_p50 = float(np.percentile(stt_ms, 50))
    parse_p50 = float(np.percentile(parse_ms, 50))
    spec_rate = spec_hits / len(e2e_ms)
    early_rate = stt.early_closes / max(1, stt.early_closes + stt.window_closes)
    print(
        f"[bench] e2e p50 {p50:.1f}ms p95 {p95:.1f}ms over {len(e2e_ms)} runs "
        f"(endpoint+final-STT {stt_p50:.1f}ms, post-endpoint parse "
        f"{parse_p50:.1f}ms, speculative-parse hit rate "
        f"{100 * spec_rate:.0f}%, adaptive early close rate "
        f"{100 * early_rate:.0f}% [{stt.early_closes} early / "
        f"{stt.window_closes} full-window]; endpoint closes at 240 ms of "
        f"stable silence when the speculative parse is grammar-complete, "
        f"350 ms otherwise — the reference burned 1000 ms on its debounce "
        f"alone)",
        file=sys.stderr,
    )

    # ---- adaptive-endpoint false-trigger audit: a mid-utterance pause
    # SHORTER than the early-close floor must never close the utterance
    # (the hysteresis guard), and the rate at which pauses at/over the
    # floor do close early is reported, not hidden — that is the
    # latency/turn-taking tradeoff the knob buys. Pauses >= the full
    # window close under the OLD policy too, so only [floor, window) is
    # new exposure.
    def false_trigger_probe(pause_ms: int) -> bool:
        """True if a <pause_ms> mid-utterance pause early-closed before
        the utterance's real end."""
        stt.reset()
        if spec["fut"] is not None:
            spec["fut"].result()  # drain before dropping the handle
        spec["text"], spec["fut"] = None, None
        audio = np.concatenate([
            synth_utterance(1.2),
            np.zeros(sr * pause_ms // 1000, dtype=np.float32),
            synth_utterance(0.8),
        ])
        closes_before = stt.early_closes
        final, deadline = feed_paced(audio, time.perf_counter())
        triggered = final is not None or stt.early_closes > closes_before
        if not triggered:
            feed_paced(silence, deadline)  # normal close afterwards
        return triggered

    guard_ok = not false_trigger_probe(200)   # under the 240 ms floor
    over_floor = false_trigger_probe(280)     # inside [floor, window)
    if spec["fut"] is not None:
        spec["fut"].result()  # single-slot engine: drain before parse-only
        spec["text"], spec["fut"] = None, None
    print(
        f"[bench] adaptive-endpoint audit: 200 ms mid-utterance pause "
        f"early-closed: {not guard_ok} (hysteresis guard must hold -> "
        f"False); 280 ms pause early-closed: {over_floor} (the knob's "
        f"documented exposure window [240, 350) ms — such a pause reads "
        f"as end-of-command once the parse is complete)",
        file=sys.stderr,
    )
    # decode efficiency vs the weight-read HBM roofline. The MARGINAL rate
    # is what matters: every whole-generation dispatch carries fixed costs
    # (prefill, dispatch, the final readback), so decode_ms/steps over a
    # short generation understates the chip. Two unconstrained runs at
    # different lengths; slope over their ACTUAL step counts cancels every
    # fixed cost.
    from tpu_voice_agent.utils.perfdiag import marginal_ms_per_token

    bench_prompt = (parser.render(utterances[0], {}) if neural
                    else render_prompt(utterances[0], {"last_query": None}))
    ms_tok, steps_span = marginal_ms_per_token(engine, bench_prompt,
                                               with_steps=True)
    if ms_tok is not None and on_tpu:
        from tpu_voice_agent.utils.costmodel import device_peak

        peak = device_peak()  # raises for a TPU the peaks table does not know
        floor_ms = int8_weight_bytes(engine.cfg) / peak["bytes_per_s"] * 1e3
        print(
            f"[bench] decode {ms_tok:.2f} ms/token marginal ({1e3 / ms_tok:.0f} tok/s, "
            f"slope over steps {steps_span[0]}->{steps_span[1]}); int8 "
            f"weight-read floor {floor_ms:.2f} ms/token on {peak['device']} -> "
            f"{100 * floor_ms / ms_tok:.0f}% of HBM roofline",
            file=sys.stderr,
        )
    elif ms_tok is not None:
        print(f"[bench] decode {ms_tok:.2f} ms/token marginal (CPU run; "
              "roofline n/a)", file=sys.stderr)

    # parse-only (round-1's metric, for continuity) — measured standalone
    # now that the e2e loop hides the parse inside the endpoint window
    po = []
    for u in utterances[:3]:
        t = time.perf_counter()
        parse_text(u)
        po.append((time.perf_counter() - t) * 1e3)
    print(f"[bench] parse-only p50 {float(np.percentile(po, 50)):.1f}ms "
          f"(round-1's metric, for continuity)", file=sys.stderr)

    print(
        json.dumps(
            {
                "metric": ("voice_to_intent_p50_e2e_neural" if neural
                           else "voice_to_intent_p50_e2e"),
                "value": round(p50, 2),
                "unit": "ms",
                "vs_baseline": round(800.0 / p50, 3),
                # an explicit-CPU row must be distinguishable from a chip
                # row in the JSON itself, not only on stderr
                "backend": "tpu" if on_tpu else "cpu",
                "spec_hit_rate": round(spec_rate, 2),
                "early_close_rate": round(early_rate, 2),
                "false_trigger_under_floor": not guard_ok,
            }
        )
    )


if __name__ == "__main__":
    main()
