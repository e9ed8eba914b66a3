"""Train + save the in-tree tiny checkpoints (round-3 VERDICT next #2).

Usage: python -m tpu_voice_agent.train.make_tiny_ckpts [out_dir]

Produces three orbax checkpoints under ``out_dir`` (default ``checkpoints/``):
- ``intent-tiny-distilled``  — test-tiny Llama distilled on the synthetic
  utterance->intent corpus (short-prompt serving, evals.golden scores it)
- ``whisper-tiny-overfit``   — whisper-test overfit on the acoustic-font
  pairs (evals.wer scores it; train-set number, labeled as such)
- ``whisper-tiny-heldout``   — whisper-test trained on a DISJOINT augmented
  sentence bank; WHISPER_EVAL_TEXTS is held out, so its WER generalizes.
  This is the script's long pole (~15 min CPU); skip with CKPT_HELDOUT=0.
- ``grounding-tiny``         — qwen2vl-test trained on synthetic widget
  screenshots (train.ground); scored point-in-bbox on held-out layouts.
  Also slow on one CPU core (~1 h; a TPU window trains it in minutes);
  skip with CKPT_GROUND=0.

Both reload through the real serving stack in benches/bench_quality.py.
"""

from __future__ import annotations

import os
import sys


def main(out_dir: str | None = None) -> None:
    out = out_dir or (sys.argv[1] if len(sys.argv) > 1 else "checkpoints")

    def log(msg: str) -> None:
        print(f"[make_tiny_ckpts] {msg}", file=sys.stderr, flush=True)

    from .distill import (
        INTENT_CKPT,
        WHISPER_CKPT,
        WHISPER_GEN_CKPT,
        save_ckpt,
        train_intent_model,
        train_whisper_generalize,
        train_whisper_overfit,
    )

    log("training intent model (test-tiny distillation)...")
    cfg, params, stats = train_intent_model(log=log)
    path = save_ckpt(out, INTENT_CKPT, cfg, params, stats)
    log(f"saved {path} ({stats})")

    log("training whisper overfit (acoustic font)...")
    wcfg, wparams, wstats = train_whisper_overfit(log=log)
    path = save_ckpt(out, WHISPER_CKPT, wcfg, wparams, wstats)
    log(f"saved {path} ({wstats})")

    # the generalization checkpoint (round-4 VERDICT next #3): trained on a
    # disjoint augmented sentence bank, so WHISPER_EVAL_TEXTS is a true
    # held-out set for it — the honest WER number. Skip with CKPT_HELDOUT=0
    # (it is the long pole of this script, ~15 min CPU).
    if os.environ.get("CKPT_HELDOUT") != "0":
        log("training whisper generalization (held-out eval)...")
        gcfg, gparams, gstats = train_whisper_generalize(log=log)
        path = save_ckpt(out, WHISPER_GEN_CKPT, gcfg, gparams, gstats)
        log(f"saved {path} ({gstats})")

    if os.environ.get("CKPT_GROUND") != "0":
        from .ground import save_ground_ckpt, train_grounding

        log("training grounding (synthetic widget screenshots)...")
        qcfg, qparams, qstats = train_grounding(log=log)
        path = save_ground_ckpt(out, qcfg, qparams, qstats)
        log(f"saved {path} ({qstats})")


if __name__ == "__main__":
    main()
