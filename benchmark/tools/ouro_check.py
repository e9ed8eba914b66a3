#!/usr/bin/env python3
"""What the comparison that decides ``correct`` can REFUSE in a cell whose
model runs its layers several times a token (``ouro``): the cell's engine built
as ``recipe_check.py`` builds it, the sound comparison on ``--seeds`` (row by
row, the int4 control beside it), then the served side again with each fault
PLANTED in the served program at the served widths — the cached prefix
prefilled by the faulty program too — against the reference on the sound
weights, beside the limit that has to refuse it:

- ``kv_shared_across_passes``: pass u writes and attends pass 0's planes (ONE
  K/V plane a layer for all passes: the cheaper variant the paper discusses,
  NOT this configuration) — by rebinding ``llama.pass_planes``;
- ``three_passes``: one pass fewer than ``total_ut_steps``;
- ``no_pass_norm``: the model's norm does not close a pass (the next pass reads
  the un-normed stream; the gate and the head still read the normed state) — by
  rebinding ``llama._close_pass``;
- ``no_output_norm``: the norms on the sub-layers' outputs dropped (a pre-norm block);
- ``exit_at_pass_0``: the gate's selection forced to the first pass (a threshold of 0).

    python3 benchmark/tools/ouro_check.py --workload ouro_flood --seeds 1,2 --faults 1

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
FAULT_CFG = {"three_passes": lambda cfg: {"ut_steps": cfg.ut_steps - 1},
             "no_output_norm": lambda cfg: {"sandwich_norm": False},
             "exit_at_pass_0": lambda cfg: {"exit_threshold": 0.0}}
FAULTS = ("kv_shared_across_passes", "three_passes", "no_pass_norm", "no_output_norm", "exit_at_pass_0")


@contextlib.contextmanager
def faulty_program(name: str, cfg):
    """-> the configuration to run ``forward_paged`` with while one fault is
    planted in the program (a changed property of the model, or a rebound
    function of ``models.llama``); every compiled program is dropped on the way
    in and on the way out."""
    import jax

    from tpu_voice_agent.models import llama

    planes, close = llama.pass_planes, llama._close_pass
    if name in FAULT_CFG:
        cfg = dataclasses.replace(cfg, **FAULT_CFG[name](cfg))
    elif name == "kv_shared_across_passes":
        llama.pass_planes = lambda u, cfg: 0 * u
    elif name == "no_pass_norm":
        def unnormed(params, cfg, x, ex, u, read):
            return x, close(params, cfg, x, ex, u, read)[1]

        llama._close_pass = unnormed
    else:
        raise ValueError(f"no fault {name!r}: one of {FAULTS}")
    jax.clear_caches()
    try:
        yield cfg
    finally:
        llama.pass_planes, llama._close_pass = planes, close
        jax.clear_caches()


@contextlib.contextmanager
def planted(name: str, engine):
    """The engine serving with one fault, its cached prefix the faulty program's."""
    from tpu_voice_agent.services.brain import install_prompt_prefix

    sound = engine.cfg
    with faulty_program(name, sound) as cfg:
        engine.cfg = cfg
        try:
            install_prompt_prefix(engine)
            yield
        finally:
            engine.cfg = sound
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
