#!/usr/bin/env python3
"""What a configuration's SEEDED WEIGHTS do to the cell, in ONE process and
without traffic: for each recipe (the builder module's constants, overridden
by ``--recipe``; a ``weights_seed``) build the engine as the cell's builder
does, decode the corpus's 64 plans once through the batcher (how many END,
how long they run and how many lie inside ``--band``, the traffic's tokens a
plan; how many experts a layer a forward touches), then the comparison that
decides ``correct`` row by row on ``--seeds`` — under the engine's kernels
and, with ``--xla-too``, with attention through the XLA paths (a kernel's
fault shows as a gap between the two, a rounding's as the same distance from
the reference on both). ``--compare-if N`` keeps the comparison to recipes
with N plans in the band (a sweep of seeds reads lengths alone); ``--faults``
names the comparison seeds on which the served side also runs with each of
``FAULTS`` planted, beside the limit that has to refuse it.

    python3 benchmark/tools/recipe_check.py --workload moonlight_flood \\
        --recipe BIAS_STD=0.2:seed=60 --recipe BIAS_STD=0.1,ROUTED_GAIN=1:seed=38 --seeds 1,2 --faults 1

On the chip through the chip tool; with JAX_PLATFORMS=cpu at the rehearsal's
widths (counts and control flow, never a device number). It keeps an engine
of its own, without ``parse_stack.build``'s serving loop: a recipe is loaded
into it between two decodes."""

from __future__ import annotations

import argparse
import contextlib
import gc
import os
import sys
import time
from concurrent.futures import Future

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class _Inline:
    """``served.parser.runtime`` for a sampler: the call runs here."""

    def submit_call(self, fn):
        out = Future()
        out.set_result(fn())
        return out


class _Served:
    def __init__(self, engine, dims):
        self.engine, self.dims = engine, dims
        self.parser = type("P", (), {"runtime": _Inline(), "engine": engine})()


# faults of the mechanisms a deepseek_v3 block adds, planted on the SERVED side
# (the reference keeps the sound weights): what the comparison must refuse
FAULTS = ("select_by_score", "no_router_scale", "gates_carry_bias", "shared_averaged")


@contextlib.contextmanager
def planted(name: str, engine):
    """The engine serving with one fault: three are a change of its weights
    that equals the fault exactly (a zero bias selects by the score alone; a
    gate without ``router_scale`` is an expert's down scales over it; shared
    experts averaged are their down scales over their number), one a patch of
    the program's selection rule (the chosen carry score + bias)."""
    import jax

    from tpu_voice_agent.models import moe

    cfg, sound, layers = engine.cfg, engine.params, engine.params["layers"]
    scaled = lambda leaf, by: {**leaf, "s": leaf["s"] * by}
    select = moe._select_topk
    if name == "select_by_score":
        layers = {**layers, "router_bias": layers["router_bias"] * 0}
    elif name == "no_router_scale":
        layers = {**layers, "moe_down": scaled(layers["moe_down"], 1 / cfg.router_scale)}
    elif name == "shared_averaged":
        layers = {**layers, "shared_down": scaled(layers["shared_down"], 1 / cfg.n_shared_experts)}
    elif name == "gates_carry_bias":
        def with_bias(router_w, x, n_experts, top_k, score_fn="softmax", bias=None):
            probs, ids, vals = select(router_w, x, n_experts, top_k, score_fn, bias)
            return probs, ids, vals + bias.astype(vals.dtype)[ids]

        moe._select_topk = with_bias
        jax.clear_caches()
    else:
        raise ValueError(name)
    from tpu_voice_agent.services.brain import install_prompt_prefix

    engine.params = {**sound, "layers": layers}
    try:
        install_prompt_prefix(engine)  # the cached prefix is the faulty program's too
        yield
    finally:
        engine.params, moe._select_topk = sound, select
        if name == "gates_carry_bias":
            jax.clear_caches()
        install_prompt_prefix(engine)


def rows_rel(got, want):
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return np.max(np.abs(got - want), axis=-1) / np.max(np.abs(want), axis=-1)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--recipe", action="append", default=[],
                    help="NAME=VALUE[,NAME=VALUE][:seed=N] over the builder module's constants")
    ap.add_argument("--seeds", default="1,2", help="the comparison's seeds, comma-separated")
    ap.add_argument("--plans", type=int, default=64)
    ap.add_argument("--xla-too", action="store_true")
    ap.add_argument("--band", default="100,180", help="the traffic's tokens a plan: LOW,HIGH")
    ap.add_argument("--compare-if", type=int, default=0,
                    help="compare only a recipe whose plans all end with at least this many inside --band")
    ap.add_argument("--faults", default="",
                    help="the comparison seeds (of --seeds) on which the served side also runs "
                         "with each of FAULTS planted")
    args = ap.parse_args()
    os.chdir(ROOT)
    sys.path.insert(0, ROOT)
    from benchmark.lib import refcheck
    from benchmark.lib.corpus import texts
    from benchmark.lib.manifest import load_cell, load_code, load_manifest
    from benchmark.run import program_env, say

    cell = load_cell(load_manifest(), args.workload)
    config = cell["config"]
    program_env(config)
    from tpu_voice_agent.utils.compilecache import place_compile_cache

    place_compile_cache()
    import jax
    import numpy as np

    from benchmark.builders import parse_stack
    from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
    from tpu_voice_agent.serve import ContinuousBatcher, PagedDecodeEngine
    from tpu_voice_agent.services.brain import install_prompt_prefix
    from tpu_voice_agent.services.prompts import render_prompt
    from tpu_voice_agent.utils import get_metrics

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
    prompts = [render_prompt(t, {}) for t in texts(args.plans)]
    low, high = (int(x) for x in args.band.split(","))
    fault_seeds = {int(x) for x in args.faults.split(",") if x}

    def one(recipe: str) -> None:
        consts, _, tail = recipe.partition(":")
        seed = int(tail.split("=")[1]) if tail else s["weights_seed"]
        for pair in filter(None, consts.split(",")):
            name, value = pair.split("=")
            if not hasattr(builder, name):
                raise SystemExit(f"builders/{config['builder']}.py has no constant {name}")
            setattr(builder, name, float(value))
        t0 = time.perf_counter()
        engine.load_params(builder.make_params(engine.cfg, seed))
        install_prompt_prefix(engine)
        batcher = ContinuousBatcher(engine, chunk_steps=int(s["env"].get("BRAIN_CHUNK", 16)),
                                    max_new_tokens=512)
        before = dict(get_metrics().counter_state()[0])
        res = batcher.generate_many(prompts)
        after = get_metrics().counter_state()[0]
        delta = lambda k: after.get(k, 0.0) - before.get(k, 0.0)
        lens = sorted(len(r.token_ids) for r in res)
        ended = sum(bool(r.finished) and r.error is None for r in res)
        inside = sum(low <= n <= high for n in lens)
        routed = engine.cfg.n_layers - getattr(engine.cfg, "first_dense_layers", 0)
        fwds = max(delta("scheduler.forwards"), 1.0)
        say(f"RECIPE {recipe or '(the file)'} seed {seed}: {ended}/{len(res)} plans end; tokens a plan "
            f"min {lens[0]} p10 {lens[len(lens) // 10]} median {lens[len(lens) // 2]} p90 "
            f"{lens[-1 - len(lens) // 10]} max {lens[-1]}, {inside} in {low}-{high}; distinct "
            f"{len({tuple(r.token_ids) for r in res})}; experts a layer a forward touched "
            f"{delta('moe.experts_touched') / fwds / max(routed, 1):.1f}; tokens a forward "
            f"{sum(lens) / fwds:.1f}; {time.perf_counter() - t0:.1f}s")
        for r in res[:2]:
            say(f"  PLAN {len(r.token_ids)} tokens: {r.text[:240]!r}")
        batcher.reset()
        if ended < len(res) or inside < args.compare_if:
            return
        served = _Served(engine, dims)
        for seed_c in (int(x) for x in args.seeds.split(",")):
            for impl in (engine.kernels, *(("xla",) if args.xla_too and engine.kernels != "xla" else ())):
                kept, engine.kernels = engine.kernels, impl
                try:
                    params, model, sample, rows, what = refcheck.SAMPLERS[ref.SAMPLE](served, seed_c)
                finally:
                    engine.kernels = kept
                want = ref.logits(params, model, sample)
                rel = rows_rel(rows, want)
                ctrl = rows_rel(ref.logits(params, model, sample, control=True), want)
                say(f"  COMPARE seed {seed_c} attention {impl}: worst {rel.max():.5f} rows "
                    f"{[round(float(x), 4) for x in rel]}; control worst {ctrl.max():.5f} smallest row "
                    f"{ctrl.min():.5f}; {ref.TOLERANCE=}")
            for name in FAULTS if seed_c in fault_seeds else ():
                with planted(name, engine):  # teacher-forced on ITS argmax: the reference follows its tokens
                    _, _, sample, rows, _ = refcheck.SAMPLERS[ref.SAMPLE](served, seed_c)
                rel, top1 = refcheck._rel_err(rows, ref.logits(params, model, sample))
                say(f"  FAULT {name} seed {seed_c}: served worst {rel:.5f} (top-1 {top1}/{len(rows)}) -> "
                    f"{'refused' if rel > ref.TOLERANCE else 'PASSES'} at {ref.TOLERANCE}")

    for recipe in args.recipe or [""]:
        one(recipe)  # its locals (a sample's parameter tree among them) die with the call ...
        engine.params = None  # ... and the recipe's 10 GB go before the next one's are made
        gc.collect()
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
