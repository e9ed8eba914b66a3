#!/usr/bin/env python3
"""What the comparison that decides ``correct`` can REFUSE in a cell whose
model holds a convolution's tail beside its K/V and routes behind a biased
sigmoid router (``lfm2_moe``): the cell's engine built as ``recipe_check.py``
builds it, the sound comparison on ``--seeds`` (row by row, the int4 control
beside it), then the served side again with each fault PLANTED in the served
program — the cached prefix prefilled by the faulty program too — against the
reference on the sound weights, beside the limit that has to refuse it:

- the block's own (``models.lfm2.FAULTS``, through
  ``lfm2.forward_paged(fault=...)``): ``no_in_gate`` (g = u), ``no_out_gate``
  (out = c W_out), ``taps_reversed``, ``tail_of_x`` (the tail holding u where it
  holds B * u), ``tail_at_T`` (the tail taken at the block's end, not at
  ``n_real``), ``bias_in_gates`` (the gates made of s + b), ``no_renorm``,
  ``softmax_router``, ``no_qk_norm``, ``norm_after_rope``, ``dense_everywhere``
  (the selection off: every token through every expert);
- by rebinding: ``no_restore`` (an admission leaves the slot the tails its last
  request left: ``paged._restore_state``).

    python3 benchmark/tools/shortconv_check.py --workload lfm2_flood --seeds 1,2 --faults 1

``--pairs`` first checks, at the cell's shapes and under ``jit``, that attention
over K/V heads in PAIRS (``lfm2.pair_q`` / ``unpair`` around XLA attention over
the paired rows) is attention over heads of 64: the pair written as slices and
stacks came out of the TPU compiler wrong under ``jit`` and right op by op
(PR 64), which no CPU test can see.

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
# a fault that OVERFLOWS would leave nan in blocks and tails that a later request finds stale:
# it would run LAST, on every seed (none of this block's does)
OVERFLOWS = ()


@contextlib.contextmanager
def planted(name: str, engine):
    """The engine serving with one fault, its cached prefix the faulty program's."""
    import jax

    from tpu_voice_agent.models import lfm2 as oh
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


def pairs_under_jit(cfg, say) -> None:
    """Attention of a 1 + 8 block over 1024 cached positions: paired rows, jitted, against heads of 64."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_voice_agent.models import lfm2, sambay

    B, T, S, hd = 1, 9, 1024, cfg.head_dim
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (B, T, cfg.n_heads, hd)).astype(jnp.bfloat16)
    kl, vl = (jax.random.normal(k, (B, S, cfg.n_kv_heads, hd)).astype(jnp.bfloat16) for k in ks[1:])
    pos = (S - T - 1 + jnp.arange(T))[None]
    rows, side = cfg.kv_lanes
    paired = lambda a: a.reshape(B, S, rows, side * hd)
    plain = jax.jit(lambda q, kl, vl: sambay._attend(q, kl, vl, pos, 1 << 30, hd ** -0.5))(q, kl, vl)
    got = jax.jit(lambda q, kl, vl: lfm2.unpair(sambay._attend(
        lfm2.pair_q(q, cfg), paired(kl), paired(vl), pos, 1 << 30, hd ** -0.5), cfg))(q, kl, vl)
    worst = float(np.max(np.abs(np.asarray(got) - np.asarray(plain))) / np.max(np.abs(np.asarray(plain))))
    say(f"PAIRS under jit: heads in pairs against heads of {hd}: {worst:.6f} of the output's range -> "
        f"{'ok' if worst < 1e-3 else 'WRONG'}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2", help="the comparison's seeds, comma-separated")
    ap.add_argument("--faults", default="", help="the seeds (of --seeds) that also run every fault")
    ap.add_argument("--only", default="", help="comma-separated fault names (default: all)")
    ap.add_argument("--pairs", action="store_true", help="first: the paired heads under jit against plain attention")
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
    from tpu_voice_agent.models import lfm2 as oh
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
    if args.pairs:
        pairs_under_jit(engine.cfg, say)
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
