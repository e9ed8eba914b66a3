#!/usr/bin/env python3
"""The grouped admission against the per-slot one, on the chip at full width:
the numbers alone, and what a call of each costs.

``correct``'s comparison with the plain references reaches admission only
through ``engine.prefill_slot`` (``benchmark/lib/refcheck.py``), so what the
grouped path of ISSUE 35 computes is compared HERE with what ``correct``
checks: one configuration of the benchmark built as its builder builds it
(published widths, its seeded int8 weights, the 200-block pool, the 879-token
prompt prefix), the same A seeded prompts admitted once a slot at a time
(``prefill_slot``) and once as a group (``prepare_admission`` +
``admit_group``), and printed: the largest difference of the last-position
logits and of the K/V written at the prompts' positions, each as a share of
the per-slot path's largest value (``refcheck._rel_err``'s measure), the
rows whose top-1 token agrees, and BOTH paths' logits against the
configuration's plain float32 reference (two bf16 computations tiled for
other row counts differ from one another by about what each differs from
float32 by). Then, for the engagement rule, the wall of one
call from its first launch to the device's last write: the per-slot
admission, and the grouped one at ``admit_rows`` and at the other widths
asked for (``--rows 4 8``: a width the engine does not derive is put on it
here, for the measurement alone).

    python3 tools/admit_batch_check.py --config mistral-7b-v0.1-int8 [--seed 7] [--rows 4 8]

One configuration a process (each fills most of the chip). A line of JSON a
run, on stdout and appended to ``chiprun_out/admit_batch_check.jsonl``. With
JAX_PLATFORMS=cpu at the configuration's rehearsal widths (no timing is a
device's there)."""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def build_engine(conf: dict, rehearsal: bool, prefix: bool = True):
    """The configuration's engine as its builder makes it, less the server
    (and, for a check that seeds the pool itself, less the cached ``prefix``)."""
    import jax

    from benchmark.builders import (cohere2moe_stack, dots3_stack, glm_dsa_stack, moonlight_stack, olmoe_stack,
                                    ouro_stack, parse_stack, sambay_stack, smallthinker_stack)
    from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
    from tpu_voice_agent.serve import PagedDecodeEngine
    from tpu_voice_agent.services.brain import install_prompt_prefix

    m, s = parse_stack.as_run(conf, rehearsal)
    s["batch_slots"] = conf["serving"]["batch_slots"]  # the rehearsal's 4 slots would group nothing
    llama_config, make_params = {
        "parse_stack": (parse_stack.dense_llama_config, parse_stack.make_decoder_params),
        "olmoe_stack": (olmoe_stack.llama_config, olmoe_stack.make_params),
        "sambay_stack": (sambay_stack.sambay_config, sambay_stack.make_params),
        "cohere2moe_stack": (cohere2moe_stack.llama_config, cohere2moe_stack.make_params),
        "moonlight_stack": (moonlight_stack.llama_config, moonlight_stack.make_params),
        "dots3_stack": (dots3_stack.llama_config, dots3_stack.make_params),
        "glm_dsa_stack": (glm_dsa_stack.llama_config, glm_dsa_stack.make_params),
        "smallthinker_stack": (smallthinker_stack.llama_config, smallthinker_stack.make_params),
        "ouro_stack": (ouro_stack.llama_config, ouro_stack.make_params),
    }[conf["builder"]]
    eng = PagedDecodeEngine(
        cfg=llama_config(m, s), tokenizer=default_tokenizer(), quant=s["quant"],
        batch_slots=s["batch_slots"], block_size=s["block_size"], pool_blocks=s["pool_blocks"],
        max_len=s["max_len"], prefill_buckets=tuple(s["prefill_buckets"]),
        fast_forward=s["fast_forward"], init_weights=False)
    eng.load_params(make_params(eng.cfg, s["weights_seed"]))
    if prefix:
        install_prompt_prefix(eng)
    jax.block_until_ready((eng.params, eng.k_pool))
    return eng, m


def the_logits(logits, state, slots, ns):
    """A group program's ``pick`` that hands its (A, 1, V) logits back (the
    batcher's picks the first tokens from them)."""
    return logits


def written(eng, slot: int, n: int):
    """K and V of the slot's first own block at the prefix's tail and the
    prompt's own positions (a pool by layer kind: every plane it holds),
    float32 on the host."""
    import jax
    import numpy as np

    from tpu_voice_agent.serve.paged import kv_planes

    P = len(eng.prefix_ids)
    first, upto = eng._slot_owned[slot][0], n - P // eng.block_size * eng.block_size
    planes = jax.tree.leaves if eng.sparse else (lambda pool: [kv_planes(pool)])
    return [np.asarray(a[:, first, :upto], np.float32)
            for pool in (eng.k_pool, eng.v_pool) for a in planes(pool)]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True, help="a name under benchmark/configs/")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rows", type=int, nargs="*", default=[], help="further widths to time")
    ap.add_argument("--repeat", type=int, default=7)
    args = ap.parse_args()
    os.chdir(ROOT)
    sys.path.insert(0, ROOT)
    from benchmark.lib import refcheck
    from benchmark.lib.manifest import load_code, load_json
    from benchmark.run import program_env, say

    conf = load_json(f"benchmark/configs/{args.config}.json")
    program_env(conf)
    import jax
    import numpy as np

    from tpu_voice_agent.utils.compilecache import place_compile_cache

    place_compile_cache()
    rehearsal = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    t0 = time.perf_counter()
    eng, model = build_engine(conf, rehearsal)
    A, P = eng.admit_rows, len(eng.prefix_ids)
    dev = jax.devices()[0]
    say(f"{args.config}: engine built in {time.perf_counter() - t0:.1f}s on {dev.platform} "
        f"{dev.device_kind}; kernels {eng.kernels}, prefix {P} tokens, admit_rows {A}")
    if not A:
        print("this engine groups no admissions", file=sys.stderr)
        return 2
    rng = np.random.default_rng(args.seed)
    live = eng.tokenizer.vocab_size
    # the traffic's suffixes: 8-40 tokens, so both buckets; the first one long
    lens = [40] + [int(x) for x in rng.integers(8, 41, size=A - 1)]
    prompts = [list(eng.prefix_ids) + [int(t) for t in rng.integers(3, live, size=n)] for n in lens]

    def release(n):
        for slot in range(n):
            eng.release_slot(slot, ok=False)

    one_logits, one_kv = [], []
    for slot, ids in enumerate(prompts):
        one_logits.append(np.asarray(eng.prefill_slot(ids, slot), np.float32)[0])
        one_kv.append(written(eng, slot, len(ids)))
    release(A)
    out = eng.admit_group([eng.prepare_admission(ids, slot) for slot, ids in enumerate(prompts)],
                          pick=the_logits)
    grp_logits = np.asarray(out.picked, np.float32)[:, 0]
    grp_kv = [written(eng, slot, len(ids)) for slot, ids in enumerate(prompts)]
    release(A)
    rel, top1 = refcheck._rel_err(grp_logits, np.stack(one_logits))
    kv_rel = max(float(np.max(np.abs(g - o)) / np.max(np.abs(o)))
                 for gs, os_ in zip(grp_kv, one_kv) for g, o in zip(gs, os_))
    # both against what ``correct`` compares the per-slot path with: the
    # configuration's plain float32 reference, each prompt's last position
    ref = load_code("reference", conf["reference"])
    want = np.concatenate([np.asarray(ref.logits(eng.params, model, {"tokens": ids, "rows": 1}),
                                      np.float32) for ids in prompts])
    slot_ref, slot_top1 = refcheck._rel_err(np.stack(one_logits), want)
    grp_ref, grp_top1 = refcheck._rel_err(grp_logits, want)
    say(f"GROUP vs PER-SLOT, {A} prompts (suffixes {lens}, bucket {out.records[0].bucket}): "
        f"logits worst max|group-slot|/max|slot| = {rel:.6f}, top-1 agree {top1}/{A}; K/V written "
        f"{kv_rel:.6f}. Against the reference {conf['reference']} (float32; TOLERANCE "
        f"{ref.TOLERANCE}): per-slot {slot_ref:.6f} (top-1 {slot_top1}/{A}), group {grp_ref:.6f} "
        f"(top-1 {grp_top1}/{A})")

    def wall(fn, after) -> float:
        """Median wall of ``fn`` from its first launch to the pools' last write."""
        times = []
        for _ in range(args.repeat):
            jax.block_until_ready((eng.k_pool, eng.v_pool))
            t = time.perf_counter()
            got = fn()
            jax.block_until_ready((got, eng.k_pool, eng.v_pool))
            times.append((time.perf_counter() - t) * 1e3)
            after()
        return statistics.median(times)

    long, short = prompts[0], list(eng.prefix_ids) + prompts[1][P:P + 8]
    timings = {"one_row_bucket64_ms": wall(lambda: eng.prefill_slot(long, 0), lambda: release(1)),
               "one_row_bucket32_ms": wall(lambda: eng.prefill_slot(short, 0), lambda: release(1))}
    derived = type(eng).admit_rows
    try:
        for rows in [A] + [r for r in args.rows if r != A]:
            type(eng).admit_rows = property(lambda self, r=rows: r)
            group = lambda: eng.admit_group(
                [eng.prepare_admission(ids, slot) for slot, ids in enumerate([long, short])],
                pick=the_logits).picked
            group()  # compiled, and the tails made, outside the timing
            release(2)
            timings[f"group_of_{rows}_ms"] = wall(group, lambda: release(2))
    finally:
        type(eng).admit_rows = derived
    say("a call, first launch to last write, ms (median of %d): %s" % (
        args.repeat, ", ".join(f"{k[:-3]} {v:.2f}" for k, v in timings.items())))
    line = {"config": args.config, "seed": args.seed, "admit_rows": A, "suffix_tokens": lens,
            "logits_rel": rel, "top1_agree": top1, "kv_rel": kv_rel, "tolerance": ref.TOLERANCE,
            "per_slot_vs_reference": slot_ref, "group_vs_reference": grp_ref,
            "timings_ms": None if rehearsal else timings,
            "device": {"platform": dev.platform, "kind": dev.device_kind}}
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/admit_batch_check.jsonl", "a") as f:
        f.write(json.dumps(line) + "\n")
    print(json.dumps(line), flush=True)
    return 0 if grp_ref <= ref.TOLERANCE and rel <= ref.TOLERANCE else 1


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
