#!/usr/bin/env python3
"""What the comparison that decides ``correct`` can REFUSE in a cell whose
model carries a delta-rule matrix state under a reordered norm
(``olmo_hybrid``): the cell's engine built as ``recipe_check.py`` builds it,
the sound comparison on ``--seeds`` (row by row, the int4 control beside it),
then the served side again with each fault PLANTED in the served program —
the cached prefix prefilled by the faulty program too — against the reference
on the sound weights, beside the limit that has to refuse it:

- the block's own (``models.olmo_hybrid.FAULTS``, through
  ``olmo_hybrid.forward_paged(fault=...)``): ``beta_not_doubled`` (beta in
  (0, 1)), ``no_decay`` (g = 0), ``no_l2norm`` (q and k as the convolution
  leaves them), ``no_q_scale`` (a dropped d_k^-0.5), ``gate_before_norm``,
  ``prenorm_block`` (the norms on the sub-layers' INPUTS), ``rope_on_full``
  (the full layers' q and k rotated), ``bf16_state`` (the state rounded to
  bf16 where a forward reads it);
- by rebinding: ``no_restore`` (an admission leaves the slot the state its
  last request left: ``paged._restore_state``).

    python3 benchmark/tools/gdn_check.py --workload olmohybrid_flood --seeds 1,2 --faults 1

On the chip through the chip tool; with JAX_PLATFORMS=cpu at the rehearsal's
widths (control flow, never a device number)."""

from __future__ import annotations

import argparse
import contextlib
import functools
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REBOUND = ("no_restore",)
# a fault that OVERFLOWS (without the l2 norm the state's eigenvalues leave the unit disc) leaves
# nan in blocks and states that a later request finds stale — a masked key of nan is still nan —
# and the pools do not fit the device twice to be zeroed: such a fault runs LAST, on every seed
OVERFLOWS = ("no_l2norm",)


@contextlib.contextmanager
def planted(name: str, engine):
    """The engine serving with one fault, its cached prefix the faulty program's."""
    import jax

    from tpu_voice_agent.models import olmo_hybrid as oh
    from tpu_voice_agent.serve import paged
    from tpu_voice_agent.services.brain import install_prompt_prefix

    sound = oh.forward_paged, paged._restore_state
    if name in oh.FAULTS:
        oh.forward_paged = functools.partial(sound[0], fault=name)
    elif name == "no_restore":
        paged._restore_state = lambda k_pool, v_pool, k_slot, v_slot, slot: (k_pool, v_pool)
    else:
        raise ValueError(name)
    jax.clear_caches()
    try:
        install_prompt_prefix(engine)
        yield
    finally:
        oh.forward_paged, paged._restore_state = sound
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
    from tpu_voice_agent.models import olmo_hybrid as oh
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
    faults = [f for f in oh.FAULTS + REBOUND if not args.only or f in args.only.split(",")]
    fault_seeds = {int(x) for x in args.faults.split(",") if x}
    def fault(name: str, seed: int, params, model) -> None:
        t0 = time.perf_counter()
        with planted(name, engine):  # teacher-forced on ITS argmax: the reference follows its tokens
            _, _, sample_f, rows_f, _ = refcheck.SAMPLERS[ref.SAMPLE](served, seed)
        rel_f = rows_rel(rows_f, ref.logits(params, model, sample_f))
        say(f"  FAULT {name} seed {seed}: served worst {rel_f.max():.5f} rows "
            f"{[round(float(x), 4) for x in rel_f]} -> "
            f"{'PASSES' if rel_f.max() <= ref.TOLERANCE else 'refused'} at {ref.TOLERANCE}; "
            f"{time.perf_counter() - t0:.1f}s")

    params = model = None
    for seed in (int(x) for x in args.seeds.split(",")):
        t0 = time.perf_counter()
        params, model, sample, rows, what = refcheck.SAMPLERS[ref.SAMPLE](served, seed)
        want = ref.logits(params, model, sample)
        rel = rows_rel(rows, want)
        ctrl = rows_rel(ref.logits(params, model, sample, control=True), want)
        say(f"COMPARE seed {seed}: {what}; worst {rel.max():.5f} rows {[round(float(x), 4) for x in rel]}; "
            f"control worst {ctrl.max():.5f} smallest row {ctrl.min():.5f}; {ref.TOLERANCE=}; "
            f"{time.perf_counter() - t0:.1f}s")
        for name in (f for f in faults if f not in OVERFLOWS) if seed in fault_seeds else ():
            fault(name, seed, params, model)
    for name in (f for f in faults if f in OVERFLOWS):
        for seed in sorted(fault_seeds):
            fault(name, seed, params, model)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
