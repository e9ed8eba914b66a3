#!/usr/bin/env python3
"""The packed regions of a fast-forward block against the whole ones, on the
chip at full width: the numbers, what each costs, and how often it would engage.

``correct``'s comparison with the plain references reaches ``forward_paged``
without ``n_real`` (``benchmark/lib/refcheck.py``), so it never samples the
branches ISSUES 37 and 41 add. This does, two ways.

``--config NAME``: one configuration of the benchmark built as its builder
builds it (published widths, its seeded int8 weights, the 200-block pool), and
``--seeds`` (batch_slots, 1 + fast_forward) blocks as the chunk program's
``ff_body`` makes them — a seeded ``n_real`` a row (idle rows, rows of k = 0,
chains up to W), the positions behind it copies of the row's last real one,
over a pool of seeded K/V — through ``forward_paged`` whole and with
``ffn_pack`` = the engine's ``ffn_pack_rows``, which packs BOTH position-wise
regions of every layer (norm, q/k/v; output projection, residuals, MLP:
``llama.FfnPack``; for ``dots3-note-prev-int8`` the walk in tiles of packed
rows, ``llama.RowTiles``, ISSUE 44 — its rows sit behind what the model's OWN
prefill wrote, the cached head all rows share and a seeded suffix admitted a
slot (``admitted``): keys the attention weighs, and a selection that binds;
its planes by layer kind are all compared and its control rounds the
attention planes of both kinds too): the largest difference of the real positions'
logits and of the K/V they wrote, each as a share of the whole path's largest
value (``refcheck._rel_err``'s measure), the rows whose top-1 agrees, held
against ``LIMIT`` (exit code 1 over it). Two readings bracket the limit. BELOW
it, the packed branches themselves: what they differ by is what XLA may fuse
across a region's edge — a projection's rounding with the residual add, a
norm with the layer before it — which the served whole path has and a packed
branch's buffers forbid, and the dots' tiling at another row count (PERF.md
section 6, PRs 37 and 41). ABOVE it, a control the limit must refuse: the whole
path with the layers' planes — q/k/v, the output projection, the MLPs —
rounded to int4.
With ``--rows``, the wall of the forward (first launch to the pools' last
write, median) whole and packed at each of them (every one holds the same real
positions: the differences are the regions'), and of the layers' MLPs ALONE
(``llama._ffn`` scanned over the stacked weights) at each of those row counts
and at the block's: the microbenchmark ``ffn_pack_rows`` was picked from.

``--workload CELL --sweep 0 64 96 128 160``: serves the cell as
``benchmark/run.py`` does, and for each packed width in turn (0 = none) its
traffic for ``--seconds``: tokens a second, ``ffn.forwards_packed`` /
``scheduler.forwards`` — the share of forwards whose real positions number no
more than that width: the cumulative histogram of sum(n_real) at those points —
and ``ffn.rows`` a forward. A width the engine does not derive is put on it
here, for the measurement alone (the batcher's warm-up runs its chunk program first).

``--workload CELL --plans 0 96``: the corpus's 64 plans decoded once through
the batcher at each packed width (0 = the whole block), no traffic: how many
end, how long they run, tokens a forward — and, against the FIRST width's,
token for token: the plans that are the same, and where the others part
(the token index; a plan of random weights follows a near tie that a
rounding turns). The plans' tokens go to ``chiprun_out/plans_<width>.json``,
for two trees to be held against each other.

    python3 tools/ffn_pack_check.py --config mistral-7b-v0.1-int8 [--seed 7 --seeds 4] [--rows 72 128 144]
    python3 tools/ffn_pack_check.py --workload parse_flood --sweep 0 64 96 [--seconds 45]
    python3 tools/ffn_pack_check.py --workload dots3note_sitemap_flood --plans 0 96

One configuration a process (each fills most of the chip). A line of JSON a
run, on stdout and appended to ``chiprun_out/ffn_pack_check.jsonl``. With
JAX_PLATFORMS=cpu at the configuration's rehearsal widths (no timing is a
device's there)."""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from functools import partial

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def seeded_n_real(rng, B: int, T: int, budget: int):
    """A chunk's forward as the flood cells meet it: an eighth of the rows
    idle, three quarters of the others at k = 0, chains of 1..W on the rest,
    one row of k = W; trimmed from the longest chain down to ``budget``."""
    import numpy as np

    n = np.where(rng.random(B) < 0.75, 1, 1 + rng.integers(1, T, size=B))
    n[rng.random(B) < 0.125] = 0
    n[0], n[1] = T, 1
    while n.sum() > budget:
        n[int(np.argmax(n[1:])) + 1] -= 1
    return n.astype(np.int32)


def block(eng, n_real, rng, pool_blocks: int, layout_seed: int, behind=None):
    """``ff_body``'s block for ``n_real``: tokens, positions, a table a row
    (own blocks, ~600 positions behind it in the cells' pool), the write mask.
    Where a row starts comes from ``layout_seed`` alone: blocks that share it
    write the same stretch of each row and read nothing another one wrote.
    ``behind`` (``admitted``): the tables and the starts of rows the engine
    admitted itself."""
    import numpy as np

    B, T, bs = len(n_real), 1 + eng.tables_ff.ff_tokens.shape[1], eng.block_size
    live = n_real > 0
    iw = np.minimum(np.arange(T)[None, :], np.maximum(n_real[:, None] - 1, 0))
    tokens = np.take_along_axis(rng.integers(3, eng.tokenizer.vocab_size, size=(B, T)), iw, axis=1)
    if behind is not None:
        tables, start = behind
    else:
        per_row = min(5, (pool_blocks - 1) // B)  # own blocks a row, block 0 the trash
        assert per_row >= 1, "the pool holds a block a row"
        start = (per_row - 1) * bs + np.random.default_rng(layout_seed).integers(
            0, bs - T, size=B)  # inside each row's last block
        tables = np.zeros((B, eng.max_blocks), np.int32)
        tables[:, :per_row] = 1 + per_row * np.arange(B)[:, None] + np.arange(per_row)[None, :]
    positions = np.where(live[:, None], start[:, None] + iw, 0)
    return tokens.astype(np.int32), positions.astype(np.int32), tables, live


def admitted(eng, layout_seed: int):
    """Every slot admitted by the engine's own prefill behind its cached head:
    a seeded suffix of 20 to 60 tokens a slot, blocks for a block more.
    -> (the slots' tables, where each slot's next position is)."""
    import numpy as np

    rng = np.random.default_rng(layout_seed)
    B, T = eng.batch_slots, 1 + eng.tables_ff.ff_tokens.shape[1]
    start = np.zeros((B,), np.int64)
    for b in range(B):
        ids = eng.prefix_ids + rng.integers(3, eng.tokenizer.vocab_size, size=rng.integers(20, 61)).tolist()
        eng.prefill_slot(ids, b)
        eng._grow(b, len(ids) + T)
        start[b] = len(ids)
    return np.asarray(eng.block_tables)[:, :eng.max_blocks], start


# What the packed branches may differ from the whole regions by, as a share of a
# row's largest logit (``refcheck._rel_err``). Between two readings (PERF.md
# section 6, PRs 37 and 41, have them by configuration): the packed branches
# themselves — the MLP alone read 3.9-4.0 % (Mistral), 0.7-5.3 % (Command A+), 0
# (OLMoE) over four seeds, bit for bit what the whole MLP reads once its output
# is a buffer and not fused into the residual add — and the whole path with its
# planes rounded to int4, the nearest precision below (the MLPs' alone: 75-186 %)
LIMIT = 0.12


# the groups of a parameter tree that hold a layer's planes (a latent model
# keeps its attention's, by layer kind, and its leading dense layers' apart)
LAYER_GROUPS = ("layers", "dense_layers", "attn_full", "attn_swa")


def to_int4(params: dict) -> dict:
    """The layers' int8 planes rounded to 16 levels IN PLACE (donated), their
    scales kept: the control ``LIMIT`` has to refuse."""
    import jax
    import jax.numpy as jnp

    round4 = jax.jit(lambda q: (jnp.clip((q.astype(jnp.int16) + 8) >> 4, -8, 7) << 4).astype(jnp.int8),
                     donate_argnums=0)
    group = lambda layers: {k: {**v, "q": round4(v["q"])} if isinstance(v, dict) and "q" in v else v
                            for k, v in layers.items()}
    return {**params, **{g: group(params[g]) for g in LAYER_GROUPS if g in params}}


def check_config(args) -> int:
    from benchmark.lib import refcheck
    from benchmark.lib.manifest import load_json
    from benchmark.run import program_env, say

    conf = load_json(f"benchmark/configs/{args.config}.json")
    program_env(conf)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tools.admit_batch_check import build_engine
    from tpu_voice_agent.models import llama
    from tpu_voice_agent.utils.compilecache import place_compile_cache

    place_compile_cache()
    rehearsal = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    t0 = time.perf_counter()
    # a pool of seeded K/V — or, where the model weighs and SELECTS its keys (a pool of noise
    # under a residual of the embedding's size would move no logit), what its own prefill wrote
    own_prefill = conf["builder"] == "dots3_stack"
    eng, _ = build_engine(conf, rehearsal, prefix=own_prefill)
    B, T = eng.batch_slots, 1 + eng.tables_ff.ff_tokens.shape[1]
    P = eng.ffn_pack_rows
    dev = jax.devices()[0]
    say(f"{args.config}: engine built in {time.perf_counter() - t0:.1f}s on {dev.platform} "
        f"{dev.device_kind}; kernels {eng.kernels}, block ({B}, {T}), ffn_pack_rows {P}")
    if not 0 < P < B * T:
        print("this engine packs nothing", file=sys.stderr)
        return 2
    widths = sorted({r for r in args.rows if r < B * T} | {P})
    one_head = bool(eng.cfg.layer_types)

    def seeded(pool, k):  # an array (its values what they were: key k itself), or planes by layer kind
        leaves, tree = jax.tree.flatten(pool)
        return jax.tree.unflatten(tree, [
            (jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(k), i) if i else jax.random.PRNGKey(k),
                               a.shape, jnp.bfloat16) * 0.3).astype(a.dtype) for i, a in enumerate(leaves)])

    behind = admitted(eng, args.seed) if own_prefill else None
    pools = [eng.k_pool, eng.v_pool] if own_prefill else [seeded(eng.k_pool, 11), seeded(eng.v_pool, 12)]
    pool_blocks = jax.tree.leaves(pools[0])[0].shape[1]
    eng.k_pool = eng.v_pool = None  # donated below, from ``pools``
    params = eng.params

    class Block:
        """One seeded block: what ``forward_paged`` is handed, and where its real positions are."""

        def __init__(self, seed: int):
            rng = np.random.default_rng(seed)
            self.n_real = seeded_n_real(rng, B, T, min(widths))
            self.tokens, self.positions, self.tables, self.live = block(
                eng, self.n_real, rng, pool_blocks, args.seed, behind)
            self.real = np.arange(T)[None, :] < self.n_real[:, None]
            self.kw = dict(attn_impl=eng.kernels, write_mask=jnp.asarray(self.live), **(
                {"logit_pos": jnp.asarray(np.maximum(self.n_real - 1, 0))} if one_head else {}))
            self.packed_kw = {**self.kw, "n_real": jnp.asarray(self.n_real)}
            rows, _ = np.nonzero(self.real)
            where = self.positions[self.real]
            self.at = (self.tables[rows, where // eng.block_size], where % eng.block_size)

        def forward(self, **more):
            out = llama.forward_paged(params, eng.cfg, jnp.asarray(self.tokens), jnp.asarray(self.positions),
                                      *pools, jnp.asarray(self.tables), **more)
            pools[:] = out[1:3]
            return out

        def left(self, out):
            """The real positions' logits, and the K/V the block wrote."""
            logits = np.asarray(out[0], np.float32)
            logits = logits[self.live, 0] if one_head else logits[self.real]
            return logits, [np.asarray(p[:, self.at[0], self.at[1]], np.float32) for p in jax.tree.leaves(pools)]

    rel_of = lambda got, want: refcheck._rel_err(got, want)[0]
    blocks, readings = [Block(args.seed + i) for i in range(args.seeds)], []
    for blk in blocks:
        blk.want, want_kv = blk.left(blk.forward(**blk.kw))
        out = blk.forward(**blk.packed_kw, ffn_pack=P)
        assert np.asarray(out[-1]).tolist() == [1, P], "the seeded block fits: ONE packed tile held it"
        got, got_kv = blk.left(out)
        rel, top1 = refcheck._rel_err(got, blk.want)
        readings.append({
            "seed": args.seed + len(readings), "real_positions": int(blk.n_real.sum()),
            "packed_vs_whole": rel, "top1_agree": [top1, len(blk.want)],
            "kv_written": max(float(np.max(np.abs(g - w)) / np.max(np.abs(w))) for g, w in zip(got_kv, want_kv))})
        say(f"PACKED vs WHOLE, ({B}, {T}) block, sum(n_real) {int(blk.n_real.sum())} into {P} rows: {readings[-1]}")

    def wall(fn) -> float:
        fn()  # compiled outside the timing
        times = []
        for _ in range(args.repeat):
            jax.block_until_ready(pools)
            t = time.perf_counter()
            jax.block_until_ready(fn())
            times.append((time.perf_counter() - t) * 1e3)
        return statistics.median(times)

    timings, vs_whole, blk = {}, {}, blocks[0]
    if len(widths) > 1:
        # every width holds the same real positions: the differences are the regions'
        timings["forward_whole_ms"] = wall(lambda: blk.forward(**blk.kw))
        for rows in widths:
            timings[f"forward_packed_{rows}_ms"] = wall(lambda: blk.forward(**blk.packed_kw, ffn_pack=rows))
            vs_whole[rows] = rel_of(blk.left(blk.forward(**blk.packed_kw, ffn_pack=rows))[0], blk.want)
        cfg = eng.cfg

        @partial(jax.jit, static_argnames=("rows",))
        def mlps(layers, seed, rows: int):  # the layers' MLPs alone, by rows
            scanned, held = llama._scan_and_whole(layers, cfg)
            scanned = {k: v for k, v in scanned.items() if k in llama._FFN_LEAVES}
            x = jax.random.normal(seed, (1, rows, cfg.dim), jnp.bfloat16)

            def layer(x, xs):
                p, li = xs
                y, _ = llama._ffn({**p, **held, "layer": li} if held else p, x, cfg)
                return (x + 0.01 * y).astype(x.dtype), None

            return jax.lax.scan(layer, x, (scanned, jnp.arange(cfg.n_layers, dtype=jnp.int32)))[0]

        for rows in widths + [B * T]:
            timings[f"mlps_alone_{rows}_rows_ms"] = wall(
                lambda: mlps(params["layers"], jax.random.PRNGKey(rows), rows))
        say("ms, median of %d: %s" % (args.repeat, ", ".join(f"{k[:-3]} {v:.2f}" for k, v in timings.items())))
    # the control, last (it rewrites the weights): the whole path, the layers' planes at int4
    params = eng.params = to_int4(params)
    control = [rel_of(blk.left(blk.forward(**blk.kw))[0], blk.want) for blk in blocks]
    worst = max(r["packed_vs_whole"] for r in readings)
    ok = worst < LIMIT < min(control)
    say(f"packed against whole, worst of {len(readings)} seeds {worst:.6f}; the whole path at int4 against "
        f"itself at int8 {[round(c, 6) for c in control]}; LIMIT {LIMIT}: {'PASS' if ok else 'FAIL'}")
    line = {"config": args.config, "block": [B, T], "ffn_pack_rows": P, "limit": LIMIT, "pass": ok,
            "readings": readings, "int4_control_vs_whole": control, "widths_vs_whole": vs_whole,
            "timings_ms": None if rehearsal else timings,
            "device": {"platform": dev.platform, "kind": dev.device_kind}}
    return report(line, 0 if ok else 1)


def sweep_workload(args) -> int:
    from benchmark.lib.manifest import load_cell, load_code, load_manifest
    from benchmark.run import Client, program_env, say

    cell = load_cell(load_manifest(), args.workload)
    config, traffic = cell["config"], cell["traffic"]
    program_env(config)
    import jax

    from tpu_voice_agent.utils import get_metrics
    from tpu_voice_agent.utils.compilecache import place_compile_cache

    place_compile_cache()
    rehearsal = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    client = Client()
    served = load_code("builders", config["builder"]).build(config, rehearsal, say)
    eng, points = served.engine, []
    derived = eng.ffn_pack_rows
    try:
        for i, rows in enumerate(args.sweep):
            eng.ffn_pack_rows = rows
            served.parser.warmup()  # this width's full chunk program is compiled and RUN here
            gen = {"generator": traffic["generator"], "traffic": traffic, "urls": served.urls,
                   "seed": args.seed, "seconds": args.seconds}
            client.command(dict(gen, cmd="warm"))
            edges: dict = {}
            client.command(dict(gen, cmd="run"), lambda msg: edges.__setitem__(
                msg["ev"], (msg["t"], get_metrics().counter_state()[0])))
            (t0, c0), (t1, c1) = edges["window_start"], edges["window_end"]
            d = lambda k: c1.get(k, 0.0) - c0.get(k, 0.0)
            fwds = d("scheduler.forwards")
            points.append({
                "ffn_pack_rows": rows, "seed": args.seed, "forwards": fwds,
                "tokens_per_s": round(d("scheduler.tokens_generated") / (t1 - t0), 2),
                "tokens_per_forward": round(d("scheduler.tokens_generated") / fwds, 3) if fwds else None,
                "packed_share": round(d("ffn.forwards_packed") / fwds, 4) if fwds else None,
                "ffn_rows_per_forward": round(d("ffn.rows") / fwds, 2) if fwds else None})
            say(f"ffn_pack_rows {rows}: {points[-1]}")
    finally:
        eng.ffn_pack_rows = derived
        client.close()
        served.close()
    dev = jax.devices()[0]
    return report({"workload": args.workload, "seconds": args.seconds, "derived": derived,
                   "sweep": points if not rehearsal else [
                       {**p, "tokens_per_s": None} for p in points],
                   "device": {"platform": dev.platform, "kind": dev.device_kind}}, 0)


def parted(plan: list, other: list) -> int | None:
    """The first token index at which two plans differ, None where they are one."""
    if plan == other:
        return None
    return next((i for i, (a, b) in enumerate(zip(plan, other)) if a != b), min(len(plan), len(other)))


def decode_plans(args) -> int:
    from benchmark.builders import parse_stack
    from benchmark.lib.corpus import texts
    from benchmark.lib.manifest import load_cell, load_manifest
    from benchmark.run import program_env, say

    config = load_cell(load_manifest(), args.workload)["config"]
    program_env(config)
    import jax

    from tools.admit_batch_check import build_engine
    from tpu_voice_agent.serve import ContinuousBatcher
    from tpu_voice_agent.services.prompts import render_prompt
    from tpu_voice_agent.utils import get_metrics
    from tpu_voice_agent.utils.compilecache import place_compile_cache

    place_compile_cache()
    rehearsal = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    eng, _ = build_engine(config, rehearsal)
    chunk = int(parse_stack.as_run(config, rehearsal)[1]["env"].get("BRAIN_CHUNK", 16))
    prompts = [render_prompt(t, {}) for t in texts(64)]
    derived, first, points = eng.ffn_pack_rows, None, []
    os.makedirs("chiprun_out", exist_ok=True)
    for rows in args.plans:
        eng.ffn_pack_rows = rows
        batcher = ContinuousBatcher(eng, chunk_steps=chunk, max_new_tokens=512)
        before = dict(get_metrics().counter_state()[0])
        res = batcher.generate_many(prompts)
        after = get_metrics().counter_state()[0]
        batcher.reset()
        fwds = after.get("scheduler.forwards", 0.0) - before.get("scheduler.forwards", 0.0)
        plans = [list(map(int, r.token_ids)) for r in res]
        with open(f"chiprun_out/plans_{rows}.json", "w") as f:
            json.dump(plans, f)
        first = first if first is not None else plans
        lens = sorted(map(len, plans))
        at = [parted(a, b) for a, b in zip(plans, first)]
        points.append({
            "ffn_pack_rows": rows, "plans": len(plans),
            "ended": sum(bool(r.finished) and r.error is None for r in res),
            "tokens_a_plan": {"min": lens[0], "median": lens[len(lens) // 2], "max": lens[-1],
                              "mean": round(sum(lens) / len(lens), 2)},
            "distinct": len({tuple(p) for p in plans}),
            "tokens_per_forward": round(sum(lens) / fwds, 2) if fwds else None,
            "same_as_first_width": sum(a is None for a in at),
            "parted_at": sorted(a for a in at if a is not None)})
        say(f"ffn_pack_rows {rows}: {points[-1]}")
    eng.ffn_pack_rows = derived
    dev = jax.devices()[0]
    return report({"workload": args.workload, "derived": derived, "plans": points,
                   "device": {"platform": dev.platform, "kind": dev.device_kind}}, 0)


def report(line: dict, code: int) -> int:
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/ffn_pack_check.jsonl", "a") as f:
        f.write(json.dumps(line) + "\n")
    print(json.dumps(line), flush=True)
    return code


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    what = ap.add_mutually_exclusive_group(required=True)
    what.add_argument("--config", help="a name under benchmark/configs/: the block check")
    what.add_argument("--workload", help="a cell of BENCHMARK.json: the sweep under its traffic")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seeds", type=int, default=4, help="blocks checked, seeded --seed, --seed + 1, ...")
    ap.add_argument("--rows", type=int, nargs="*", default=[],
                    help="other packed widths: time the forward and the MLPs alone at each")
    ap.add_argument("--sweep", type=int, nargs="*", default=[0, 64, 96], help="packed widths to serve at")
    ap.add_argument("--plans", type=int, nargs="+", help="with --workload: packed widths to decode the 64 plans at")
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--repeat", type=int, default=9)
    args = ap.parse_args()
    os.chdir(ROOT)
    sys.path.insert(0, ROOT)
    if args.config:
        return check_config(args)
    return decode_plans(args) if args.plans else sweep_workload(args)


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
