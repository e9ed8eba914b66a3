"""Builder ``voice_stack``: voice + brain + fake-page executor from ONE
process through ``services.stack.serve_stack`` — Whisper and the decoder on
the same chip. The STT factory is the program's own
(``voice.stt_factory_from_env``: endpointer, lock, early close, knobs); it
asks for a ``SpeechEngine(preset=...)`` and is handed this configuration's
engine instead, because the factory has no way yet to take widths the
program has no preset for (PERF.md, Open questions)."""

from __future__ import annotations

import os
import time

from . import parse_stack


class PublishedVocabulary:
    """The in-tree tokenizer presented at a published vocabulary's width:
    the rows past its own ids are declared special, which is the engine's
    own way (``SpeechEngine.suppress``, built for a real checkpoint's
    hundreds of control tokens) of never sampling an id that decodes to
    nothing. Without it a random model at 51866 rows picks a dead id 99 %
    of the time and every transcript is empty (my chip run, PR 23)."""

    def __init__(self, tok, vocab_size: int):
        self._tok = tok
        self.special_ids = tuple(getattr(tok, "special_ids", None) or ()) + tuple(
            range(tok.vocab_size, vocab_size))

    def __getattr__(self, name):
        return getattr(self._tok, name)


def build_stt(config: dict, rehearsal: bool, say):
    from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
    from tpu_voice_agent.models.whisper import WhisperConfig
    from tpu_voice_agent.serve import stt as stt_mod
    from tpu_voice_agent.services import voice

    m, s = parse_stack.as_run(config, rehearsal)
    dims = {"model": m, "serving": s}
    if not m["encoder_ffn_dim"] == m["decoder_ffn_dim"] == 4 * config["d_model"]:
        raise ValueError("models/whisper.py fixes the feed-forward width at 4 x d_model")
    wcfg = WhisperConfig(vocab_size=m["vocab_size"], n_mels=m["num_mel_bins"],
                         d_model=m["d_model"], n_heads=m["encoder_attention_heads"],
                         enc_layers=m["encoder_layers"], dec_layers=m["decoder_layers"],
                         max_audio_frames=2 * m["max_source_positions"],
                         max_text_len=m["max_target_positions"])
    t0 = time.perf_counter()
    engine = stt_mod.SpeechEngine(
        cfg=wcfg, tokenizer=PublishedVocabulary(default_tokenizer(), m["vocab_size"]),
        seed=s["weights_seed"],
        frame_buckets=tuple(s["frame_buckets"]), max_new_tokens=s["stt_max_new_tokens"])
    orig = stt_mod.SpeechEngine
    stt_mod.SpeechEngine = lambda preset=None, **kw: engine
    try:
        factory = voice.stt_factory_from_env()
    finally:
        stt_mod.SpeechEngine = orig
    if getattr(factory(), "engine", None) is not engine:  # a rename inside the program shows here
        raise RuntimeError("voice.stt_factory_from_env did not take this configuration's engine")
    say(f"whisper: engine+weights {time.perf_counter() - t0:.1f}s, vocab "
        f"{engine.cfg.vocab_size}, frame buckets {engine.frame_buckets}, kernels {engine.kernels}")
    return engine, factory, dims


def build(config: dict, rehearsal: bool, say) -> parse_stack.Served:
    from tpu_voice_agent.services.executor.server import model_backends_from_env
    from tpu_voice_agent.services.stack import serve_stack

    from ..lib.manifest import ROOT

    os.environ["ARTIFACTS_DIR"] = str(ROOT / ".artifacts")  # inside the checkout, ignored by git
    os.environ["UPLOADS_DIR"] = str(ROOT / ".uploads")
    parser, dims = parse_stack.build_parser(config, rehearsal, say)
    stt_engine, factory, wdims = build_stt(config, rehearsal, say)
    dims["whisper"] = wdims
    t0 = time.perf_counter()
    stack = serve_stack(parser, voice_cfg={"stt_factory": factory},
                        executor_kw=model_backends_from_env())  # warms both engines
    say(f"stack warm-up + sockets {time.perf_counter() - t0:.1f}s: {stack.urls}")
    return parse_stack.Served(stack.urls, parser, dims, [stack.close], stt_engine=stt_engine)
