#!/usr/bin/env python3
"""What the comparison that decides ``correct`` can REFUSE in a cell whose
model routes on the layer's INPUT to ReGLU experts and mixes one full layer
without positions among three windowed ones (``smallthinker``): the cell's
engine built as ``recipe_check.py`` builds it, the sound comparison on
``--seeds`` (row by row, the int4 control beside it), then the served side
again with each fault PLANTED in the served program at the served widths —
the cached head prefilled by the faulty program too — against the reference
on the sound weights, beside the limit that has to refuse it:

- by the program's configuration (``FAULT_CFG``): ``router_on_h2`` (the usual
  placement: the router reads the experts' input, behind attention),
  ``silu_for_relu``, ``no_window`` (the sliding layers see every earlier
  position), ``no_renorm`` (the chosen experts' softmax weights over all 64 as
  they come);
- by rebinding ``llama.layer_kinds``: ``rotate_full`` (rotation on the full
  layers too).

    python3 benchmark/tools/smallthinker_check.py --workload smallthinker_pagemap_flood --seeds 1,2 --faults 1

On the chip through the chip tool; with JAX_PLATFORMS=cpu at the rehearsal's
widths (control flow, never a device number)."""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FAULT_CFG = {"router_on_h2": {"router_input": "ffn"}, "silu_for_relu": {"gate_act": "silu"},
             "no_renorm": {"norm_topk": False}}
FAULTS = (*FAULT_CFG, "no_window", "rotate_full")


@contextlib.contextmanager
def planted(name: str, engine):
    """The engine serving with one fault, its cached head the faulty program's."""
    import jax

    from tpu_voice_agent.models import llama
    from tpu_voice_agent.services.brain import install_prompt_prefix

    sound_cfg, sound_kinds = engine.cfg, llama.layer_kinds
    if name in FAULT_CFG:
        engine.cfg = dataclasses.replace(sound_cfg, **FAULT_CFG[name])
    elif name == "no_window":  # a window no context reaches: ``bound_window`` None
        engine.cfg = dataclasses.replace(sound_cfg, sliding_window=sound_cfg.max_seq_len)
    elif name == "rotate_full":
        llama.layer_kinds = lambda cfg: tuple((True, w) for _, w in sound_kinds(cfg))
    else:
        raise ValueError(name)
    jax.clear_caches()
    try:
        install_prompt_prefix(engine)
        yield
    finally:
        engine.cfg, llama.layer_kinds = sound_cfg, sound_kinds
        jax.clear_caches()
        install_prompt_prefix(engine)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2", help="the comparison's seeds, comma-separated")
    ap.add_argument("--faults", default="", help="the seeds (of --seeds) that also run every fault")
    ap.add_argument("--only", default="", help="comma-separated fault names (default: all)")
    args = ap.parse_args()
    os.chdir(ROOT)
    sys.path.insert(0, ROOT)
    from benchmark.lib import refcheck
    from benchmark.lib.manifest import load_cell, load_code, load_manifest
    from benchmark.run import program_env, say
    from benchmark.tools.recipe_check import _Served, rows_rel

    config = load_cell(load_manifest(), args.workload)["config"]
    program_env(config)
    from tpu_voice_agent.utils.compilecache import place_compile_cache

    place_compile_cache()
    from benchmark.builders import parse_stack
    from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
    from tpu_voice_agent.serve import PagedDecodeEngine
    from tpu_voice_agent.services.brain import install_prompt_prefix

    rehearsal = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    builder = load_code("builders", config["builder"])
    ref = load_code("reference", config["reference"])
    dims = parse_stack.model_dims(config, rehearsal)
    m, s = dims["model"], dims["serving"]
    engine = PagedDecodeEngine(
        cfg=builder.llama_config(m, s), tokenizer=default_tokenizer(), quant=s["quant"],
        batch_slots=s["batch_slots"], block_size=s["block_size"], pool_blocks=s["pool_blocks"],
        max_len=s["max_len"], prefill_buckets=tuple(s["prefill_buckets"]),
        fast_forward=s["fast_forward"], init_weights=False)
    engine.load_params(builder.make_params(engine.cfg, s["weights_seed"]))
    install_prompt_prefix(engine)
    served = _Served(engine, dims)
    faults = [f for f in FAULTS if not args.only or f in args.only.split(",")]
    fault_seeds = {int(x) for x in args.faults.split(",") if x}
    for seed in (int(x) for x in args.seeds.split(",")):
        t0 = time.perf_counter()
        params, model, sample, rows, what = refcheck.SAMPLERS[ref.SAMPLE](served, seed)
        want = ref.logits(params, model, sample)
        rel = rows_rel(rows, want)
        ctrl = rows_rel(ref.logits(params, model, sample, control=True), want)
        say(f"COMPARE seed {seed}: {what}; worst {rel.max():.5f} rows {[round(float(x), 4) for x in rel]}; "
            f"control worst {ctrl.max():.5f} smallest row {ctrl.min():.5f}; {ref.TOLERANCE=}; "
            f"{time.perf_counter() - t0:.1f}s")
        for name in faults if seed in fault_seeds else ():
            t0 = time.perf_counter()
            with planted(name, engine):  # teacher-forced on ITS argmax: the reference follows its tokens
                _, _, sample_f, rows_f, _ = refcheck.SAMPLERS[ref.SAMPLE](served, seed)
            rel_f = rows_rel(rows_f, ref.logits(params, model, sample_f))
            say(f"  FAULT {name} seed {seed}: served worst {rel_f.max():.5f} rows "
                f"{[round(float(x), 4) for x in rel_f]} -> "
                f"{'refused' if rel_f.max() > ref.TOLERANCE else 'PASSES'} at {ref.TOLERANCE}; "
                f"{time.perf_counter() - t0:.1f}s")
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
