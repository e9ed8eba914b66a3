#!/usr/bin/env python3
"""The sliding window at its PUBLISHED size, once, on the chip: the
comparison alone. ``command-a-plus-05-2026-int8`` with ``max_len`` 5120 and
two slots (a scratch copy of the configuration, made here; the cell's
``max_len`` 1536 never reaches the 4096 window), a seeded prompt of 4700
tokens admitted through the engine's CHUNKED admission
(``begin_chunked_prefill`` / ``chunked_prefill_step``: ten chunks of 512
behind the pool's gathered blocks, the XLA path with the window mask), then
three T = 1 steps and one 1 + 8 block through the block kernel's windowed
variant — every sliding layer masks 600 positions and more — against the
plain reference's ONE full forward, with its int4 control.

    python3 benchmark/tools/window_check.py [--seed 7] [--prompt 4700]

Until PR 42 ``builders/parse_stack.model_dims`` refused a ``max_len`` that
reaches a window (written when the program had none; since then
``parse_stack.refuse_unserved_window`` asks the program's configuration), so
this builds the engine itself with ``cohere2moe_stack``'s two functions. With JAX_PLATFORMS=cpu at the
rehearsal's widths (window 16, a 200-token prompt)."""

from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--prompt", type=int, default=None, help="tokens (default 4700; rehearsal 200)")
    args = ap.parse_args()
    os.chdir(ROOT)
    sys.path.insert(0, ROOT)
    from benchmark.builders import cohere2moe_stack, parse_stack
    from benchmark.lib import refcheck
    from benchmark.lib.manifest import load_code, load_json
    from benchmark.run import program_env, say

    conf = load_json("benchmark/configs/command-a-plus-05-2026-int8.json")
    program_env(conf)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
    from tpu_voice_agent.models.llama import bound_window, forward_paged
    from tpu_voice_agent.serve import PagedDecodeEngine
    from tpu_voice_agent.utils.compilecache import place_compile_cache

    place_compile_cache()
    rehearsal = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    m, s = parse_stack.as_run(conf, rehearsal)
    n = args.prompt or (200 if rehearsal else 4700)
    chunk = 64 if rehearsal else 512
    max_len = 256 if rehearsal else 5120
    s = {**s, "max_len": max_len, "batch_slots": 2, "pool_blocks": 2 * -(-max_len // s["block_size"]) + 2}
    t0 = time.perf_counter()
    eng = PagedDecodeEngine(
        cfg=cohere2moe_stack.llama_config(m, s), tokenizer=default_tokenizer(), quant=s["quant"],
        batch_slots=2, block_size=s["block_size"], pool_blocks=s["pool_blocks"], max_len=max_len,
        prefill_buckets=(chunk,), fast_forward=s["fast_forward"], init_weights=False)
    eng.load_params(cohere2moe_stack.make_params(eng.cfg, s["weights_seed"]))
    window = bound_window(eng.cfg)
    say(f"engine max_len {max_len}, window {m['sliding_window']} -> the mask is given {window}; kernels "
        f"{eng.kernels}; built in {time.perf_counter() - t0:.1f}s")
    if window is None or n + 13 <= window:
        print("the window does not bind at these sizes", file=sys.stderr)
        return 2
    live, W = eng.tokenizer.vocab_size, eng.fast_forward
    ids = [int(t) for t in np.random.default_rng(args.seed).integers(3, live, size=n)]
    cur = eng.begin_chunked_prefill(ids, 0, chunk)
    first = None
    while first is None:
        first = eng.chunked_prefill_step(cur)
    rows, toks = [np.asarray(first, np.float32).reshape(-1)], list(ids)

    def paged(tokens: list[int], pos0: int):
        out = forward_paged(
            eng.params, eng.cfg, jnp.asarray([tokens], jnp.int32),
            (pos0 + jnp.arange(len(tokens), dtype=jnp.int32))[None, :],
            eng.k_pool, eng.v_pool, eng.block_tables[0][None], attn_impl=eng.kernels)
        logits, eng.k_pool, eng.v_pool = out[:3]
        return np.asarray(logits[0], np.float32)

    for _ in range(3):
        toks.append(int(rows[-1][:live].argmax()))
        rows.append(paged(toks[-1:], len(toks) - 1)[0])
    block = [int(rows[-1][:live].argmax())] + ids[1:1 + W]
    toks.extend(block)
    rows.extend(paged(block, len(toks) - len(block)))
    served = np.stack(rows)
    say(f"served {len(toks)} tokens: {cur.n_chunks} chunks of {chunk}, 3 x T=1, 1 x T={1 + W}; the last "
        f"query masks {len(toks) - window} positions in every sliding layer; {time.perf_counter() - t0:.1f}s")
    ref = load_code("reference", conf["reference"])
    sample = {"tokens": toks, "rows": len(rows)}
    want = ref.logits(eng.params, m, sample)
    rel, top1 = refcheck._rel_err(served, want)
    ctrl, _ = refcheck._rel_err(ref.logits(eng.params, m, sample, control=True), want)
    unbound, _ = refcheck._rel_err(ref.logits(eng.params, dict(m, sliding_window=1 << 30), sample), want)
    jax.block_until_ready(want)
    ok = rel <= ref.TOLERANCE < min(ctrl, unbound)
    say(f"WINDOW reference {conf['reference']}: worst max|served-ref|/max|ref| = {rel:.5f} (tolerance "
        f"{ref.TOLERANCE}), top-1 agree {top1}/{len(rows)}; {ref.CONTROL} control {ctrl:.5f}; the reference "
        f"WITHOUT the window {unbound:.5f} (both must exceed the tolerance); "
        f"{time.perf_counter() - t0:.1f}s -> {'ok' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
