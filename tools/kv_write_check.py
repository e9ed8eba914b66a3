#!/usr/bin/env python3
"""A layer's K/V write alone, on the chip, at the cells' shapes: the pair of scatters
that writes ALL B x T rows of the block (``llama.write_rows`` without tiles: the form
every tree has) against the walk over tiles of the packed REAL rows (``write_rows``
with ``llama.write_walk``'s tiles; absent on a tree without it, so the parent runs this
file too), at ``--n`` real rows a forward (ISSUE 60's stop rule).

A zeroed pool of the cell's own shape, a (B, T) block of seeded K and V rows of which
``n`` are real — spread over the rows in order, ``n // B`` or one more each; a row with
none is not live and parks its writes on the trash slot; a position behind a row's
real ones copies the last real one and names its index again, as the chunk program's
``ff_body`` builds them — and one program a form: ``--passes`` scans over the pool's
planes, a write a plane under the scope ``layer/kv_write``, the pools donated and
carried as a forward's layers carry them. ``us_layer`` is the median wall of ``--reps``
launches over passes x planes; ``floor`` the same program writing ONE row a pool (the
scan, the rows' producer and the launch: what is not the write); with ``--trace``
``scope_us_layer`` is the device's self time under ``layer/kv_write`` a write, as the
benchmark's ``scopes`` reader reads a cell, and ``scope_ops`` its largest ops. The
walk's pools are held to the scatters' bit for bit outside the trash slot.

``ops`` lists the ops of the compiled text whose RESULT has the pool's or a plane's
shape, by the stem of their names: the in-place ``scatter`` and the ``fusion`` it is
the root of alone are healthy; a ``copy`` or a slice of that shape is a plane written
out (PRs 34, 58).

    python3 tools/kv_write_check.py [--shape parse_flood ouro_flood] [--n 2 13 44 96 0] [--tile 48]

``--interpret``: the same walk at toy shapes on the CPU (tier-1's smoke test). A line
of JSON a (shape, n, form) on stdout and, on the chip, appended to
``chiprun_out/kv_write_check.jsonl``; exit code 1 where the walk's pools differ from
the scatters'.
"""

from __future__ import annotations

import argparse
import json
import re
import statistics
import sys
import time
from collections import Counter
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from tpu_voice_agent.models import llama  # noqa: E402

# (B, T), the pool (planes, blocks, block size, heads, head_dim) and whether the
# site indexes it by (block, offset) (the unrolled models') or through the flat view
SHAPES = {
    "parse_flood": ((32, 9), (32, 200, 128, 8, 128), False),
    "olmoe_flood": ((32, 9), (16, 200, 128, 16, 128), False),
    "ouro_flood": ((8, 9), (192, 47, 128, 16, 128), False),
    "parse_solo": ((8, 9), (32, 200, 128, 8, 128), False),
    "cmdaplus_flood": ((32, 9), (32, 200, 128, 8, 128), True),
    "toy": ((4, 5), (3, 6, 8, 2, 16), False),
    "toy_by_block": ((4, 5), (3, 6, 8, 2, 16), True),
}
HAS_WALK = hasattr(llama, "write_rows")


def case(rng, B: int, T: int, pool: tuple, n: int):
    """-> (k, v, idx, n_real): the block's rows (padded positions copies of their
    row's last real one), every position's flat pool index (a row that is not live:
    the trash slot 0) and the rows' real positions, ``n`` in all."""
    _, N, bs, H, D = pool
    n_real = np.clip(n // B + (np.arange(B) < n % B), 0, T).astype(np.int32)
    t = np.minimum(np.arange(T)[None, :], np.maximum(n_real[:, None] - 1, 0))
    k, v = (np.take_along_axis(rng.standard_normal((B, T, H, D), dtype=np.float32),
                               t[:, :, None, None], axis=1) for _ in range(2))
    # a block of its own a row behind block 0 (the trash's), positions from a seeded start
    start = rng.integers(0, bs - T, size=B)
    idx = (1 + rng.permutation(N - 1)[:B, None]) * bs + start[:, None] + t
    idx = np.where(n_real[:, None] > 0, idx, 0).astype(np.int32)
    return (jnp.asarray(k, jnp.bfloat16), jnp.asarray(v, jnp.bfloat16), jnp.asarray(idx),
            jnp.asarray(n_real))


def program(form: str, pool: tuple, by_block: bool, passes: int, tile: int):
    """The jitted program of one form: ``scatter`` | ``walk`` | ``floor``."""
    L, N, bs, H, D = pool

    def run(kp, vp, k, v, idx, n_real):
        B, T = idx.shape
        where = (idx // bs, idx % bs) if by_block else (idx,)
        tiles = None
        if form == "walk":  # ``llama.write_walk``, at the tile asked for
            tiles = llama.row_tiles(n_real, T, tile)
            where = tuple(w.reshape(-1)[tiles.idx] for w in where)
        view = (lambda p: p) if by_block else (lambda p: p.reshape(L, N * bs, H, D))

        def layer(pools, li):
            kl, vl = k + li.astype(k.dtype), v - li.astype(v.dtype)  # a layer's own rows
            with jax.named_scope("layer/kv_write"):
                kp, vp = (view(p) for p in pools)
                if form == "floor":
                    at = (li, *(w[0, 0] for w in where))
                    kp, vp = kp.at[at].set(kl[0, 0]), vp.at[at].set(vl[0, 0])
                elif HAS_WALK:
                    kp, vp = llama.write_rows(kp, vp, li, kl, vl, where, tiles)
                else:  # a tree before the helper: the pair at the site
                    kp, vp = kp.at[(li, *where)].set(kl), vp.at[(li, *where)].set(vl)
            return (kp.reshape(pool), vp.reshape(pool)), None

        def one_pass(_, pools):
            return jax.lax.scan(layer, pools, jnp.arange(L, dtype=jnp.int32))[0]

        return jax.lax.fori_loop(0, passes, one_pass, (kp, vp))

    return jax.jit(run, donate_argnums=(0, 1))


def shaped_ops(text: str, pool: tuple) -> dict:
    """Name stem -> count over the compiled text's ops whose result is of the pool's
    shape, its flat view's, or one plane's of either (``fusion`` / ``scatter``: the
    in-place write and the fusion it is the root of; XLA names any other fusion by
    what it holds: ``dynamic-slice_bitcast_fusion``, ``copy_fusion``)."""
    L, N, bs = pool[:3]
    rest = ",".join(str(d) for d in pool[3:])
    dims = {f"{lead}{rows},{rest}" for rows in (f"{N},{bs}", f"{N * bs}") for lead in (f"{L},", "1,", "")}
    found = Counter()
    for m in re.finditer(r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]+)\](?:\{[^}]*\})? ([\w\-]+)\(", text, re.M):
        if m.group(2) in dims and m.group(3) not in ("parameter", "get-tuple-element", "bitcast"):
            found[re.sub(r"[.\d]+$", "", m.group(1))] += 1
    return dict(sorted(found.items()))


def traced(launch, writes: int) -> tuple[float | None, dict]:
    """Profile ``launch()`` -> (device self us a write under ``layer/kv_write``, its ops:
    short name -> [events, us a write]), as the benchmark's ``scopes`` reader reads a cell."""
    import shutil
    import tempfile

    from benchmark.lib import trace as tr
    from benchmark.readers.scopes import in_scope

    where = tempfile.mkdtemp(prefix="kv_write_check_")
    try:
        with jax.profiler.trace(where):
            launch()
        trace = tr.first_plane(tr.load_xplane(tr.find_xplane(where)))
    finally:
        shutil.rmtree(where, ignore_errors=True)
    if trace is None:  # no device plane: the CPU
        return None, {}
    times = Counter(name for name, _, _ in trace["ops"])
    own = {name: ns for name, ns in tr.self_times(trace["ops"]).items()
           if in_scope(trace["scope"].get(name, ""), ["layer/kv_write"])}
    ops = {tr.short_name(name, 60): [times[name], round(ns / writes / 1e3, 2)]
           for name, ns in sorted(own.items(), key=lambda kv: -kv[1])[:12]}
    return round(sum(own.values()) / writes / 1e3, 2), ops


@jax.jit
def digest(pool) -> jax.Array:
    """A position-weighted sum of the pool's bits outside the trash slot (block 0,
    offset 0, of every plane), modulo 2**32, plane by plane (a whole pool's bits as
    uint32 do not fit beside it): equal pools, equal digests."""
    L, N, bs, H, D = pool.shape

    def plane(li, total):
        bits = jax.lax.bitcast_convert_type(jax.lax.dynamic_index_in_dim(pool, li, keepdims=False),
                                            jnp.uint16).astype(jnp.uint32).reshape(N * bs, H * D)
        lin = (li.astype(jnp.uint32) * jnp.uint32(N * bs) + jax.lax.broadcasted_iota(jnp.uint32, bits.shape, 0)) \
            * jnp.uint32(H * D) + jax.lax.broadcasted_iota(jnp.uint32, bits.shape, 1)
        weighted = bits * (lin * jnp.uint32(2654435761) | jnp.uint32(1))
        return total + jnp.sum(weighted) - jnp.sum(weighted[0])

    return jax.lax.fori_loop(0, L, plane, jnp.uint32(0))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", nargs="+", default=["parse_flood", "olmoe_flood", "ouro_flood", "parse_solo"],
                    choices=sorted(SHAPES))
    ap.add_argument("--n", nargs="+", type=int, default=[2, 13, 44, 96, 0],
                    help="real rows a forward (0: all B x T; more than B x T: all)")
    ap.add_argument("--tile", nargs="+", type=int, help="walk at these tiles (default: llama.write_tile's)")
    ap.add_argument("--planes", type=int, help="cut the pool to so many planes")
    ap.add_argument("--passes", type=int, default=4)
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--trace", action="store_true",
                    help="also profile one launch a form: device self time under layer/kv_write, op by op")
    ap.add_argument("--interpret", action="store_true", help="toy shapes, on the CPU: a smoke test")
    args = ap.parse_args()
    if args.interpret:
        args.shape, args.n, args.passes, args.reps = ["toy", "toy_by_block"], [0, 1, 7, 9], 1, 1
    elif jax.default_backend() != "tpu":
        print("kv_write_check: no TPU here (--interpret runs the toy shapes on the CPU)", file=sys.stderr)
        return 2
    bad = 0
    for name in args.shape:
        (B, T), pool, by_block = SHAPES[name]
        if args.planes:
            pool = (args.planes, *pool[1:])
        tiles = (args.tile or [llama.write_tile(B * T)]) if HAS_WALK else []
        forms = [("floor", None), ("scatter", None)] + [("walk", t) for t in tiles]
        for n in args.n:
            n = B * T if n <= 0 else min(n, B * T)
            k, v, idx, n_real = case(np.random.default_rng(args.seed), B, T, pool, n)
            ref = None  # the scatters' digests
            for form, tile in forms:
                fn = program(form, pool, by_block, args.passes, tile)
                kp, vp = (jnp.zeros(pool, jnp.bfloat16) for _ in range(2))
                lowered = fn.lower(kp, vp, k, v, idx, n_real)
                ops = shaped_ops(lowered.compile().as_text(), pool)
                walls = []
                for _ in range(args.reps + 1):  # the first launch compiles
                    t0 = time.perf_counter()
                    kp, vp = jax.block_until_ready(fn(kp, vp, k, v, idx, n_real))
                    walls.append(time.perf_counter() - t0)
                us = statistics.median(walls[1:]) / (args.passes * pool[0]) * 1e6
                line = {"shape": name, "rows": B * T, "row_bytes": pool[3] * pool[4] * 2, "n": n, "form": form,
                        "tile": tile,
                        "us_layer": round(us, 2), "ops": ops, "device": jax.devices()[0].device_kind}
                if args.trace:
                    line["scope_us_layer"], line["scope_ops"] = traced(
                        lambda: jax.block_until_ready(fn(kp, vp, k, v, idx, n_real)), args.passes * pool[0])
                    kp, vp = (jnp.zeros(pool, jnp.bfloat16) for _ in range(2))  # (the launch donated them)
                    kp, vp = fn(kp, vp, k, v, idx, n_real)
                if form != "floor":
                    got = [int(digest(p)) for p in (kp, vp)]
                    if form == "scatter":
                        ref = got
                    else:
                        line["equal"] = got == ref
                        bad += not line["equal"]
                del kp, vp
                print(json.dumps(line), flush=True)
                if not args.interpret:
                    Path("chiprun_out").mkdir(exist_ok=True)
                    with open("chiprun_out/kv_write_check.jsonl", "a") as f:
                        f.write(json.dumps(line) + "\n")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
