"""One-shot retrain driver for the in-tree tiny checkpoints on a live TPU.

The round-5 training upgrades (multi-turn dialogs + copy-heavy corpus for
the intent model, the new grounding task, a bigger disjoint bank for the
whisper generalization checkpoint) are hours on a CPU core (~7 h for
grounding alone) but minutes on the chip — each train step is one
dispatch.

The chip belongs to one process: ``python tools/retrain_tpu.py [out_dir]``
must be the only one touching JAX. Each checkpoint saves IMMEDIATELY after
its training so an interrupted run keeps everything already finished;
quality scores print at the end (and are re-checked on CPU by
benches/bench_quality.py either way).
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def log(msg: str) -> None:
    print(f"[retrain {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def main(out: str = "checkpoints") -> None:
    import jax

    devices = jax.devices()
    log(f"devices: {devices}")

    from tpu_voice_agent.evals import score_parser, score_parser_dialogs
    from tpu_voice_agent.evals.wer import normalize_words, wer
    from tpu_voice_agent.train import distill, ground

    results: dict = {}

    # ---- 1. intent (multi-turn dialogs + copy-heavy streaming corpus)
    log("training intent...")
    cfg, params, stats = distill.train_intent_model(log=log)
    parser = distill.intent_engine_from(cfg, params)
    stats["golden"] = results["intent_golden"] = score_parser(parser)
    log(f"golden: {stats['golden']}")
    stats["dialogs"] = results["intent_dialogs_stateless"] = (
        score_parser_dialogs(parser))
    log(f"dialogs stateless: {stats['dialogs']}")
    # scores ride in meta.json so the committed artifact records them
    distill.save_ckpt(out, distill.INTENT_CKPT, cfg, params, stats)
    log("saved intent")

    # ---- 2. grounding
    log("training grounding...")
    gcfg, gparams, gstats = ground.train_grounding(log=log)
    eng = ground.grounding_engine_from(gcfg, gparams)
    gstats["held_out"] = results["grounding"] = ground.score_grounding(eng)
    log(f"grounding held-out: {gstats['held_out']}")
    ground.save_ground_ckpt(out, gcfg, gparams, gstats)
    log("saved grounding")

    # ---- 3. whisper generalization (bigger disjoint bank); only replaces
    # the incumbent when the new held-out WER beats the WER recorded in
    # the incumbent's own meta.json (a hardcoded threshold would let a
    # worse rerun silently replace a better checkpoint)
    import os

    incumbent_wer = 1.0
    meta_path = os.path.join(out, distill.WHISPER_GEN_CKPT, "meta.json")
    if os.path.exists(meta_path):
        with open(meta_path) as f:
            incumbent_wer = float(
                json.load(f)["stats"].get("held_out_wer", 1.0))
    log(f"training whisper-gen (incumbent held-out WER {incumbent_wer})...")
    wcfg, wparams, wstats = distill.train_whisper_generalize(
        steps=9000, n_sentences=640, variants=8, log=log)
    weng = distill.whisper_engine_from(wcfg, wparams)
    te = tw = 0.0
    for t in distill.WHISPER_EVAL_TEXTS:
        hyp = weng.transcribe(distill.render_speech(t)).text
        n = max(len(normalize_words(t)), 1)
        te += wer(t, hyp) * n
        tw += n
        log(f"  ref={t!r} hyp={hyp!r}")
    w2 = te / tw
    wstats["held_out_wer"] = results["whisper_heldout_wer"] = round(w2, 4)
    log(f"held-out WER: {w2:.4f} (incumbent {incumbent_wer})")
    if w2 < incumbent_wer:
        distill.save_ckpt(out, distill.WHISPER_GEN_CKPT, wcfg, wparams, wstats)
        log("beats incumbent -> saved over whisper-tiny-heldout")
    else:
        log("does NOT beat incumbent -> keeping the committed checkpoint")

    print(json.dumps(results, indent=1, default=str))


if __name__ == "__main__":
    main(*(sys.argv[1:2]))
