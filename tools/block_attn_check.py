#!/usr/bin/env python3
"""``ops.paged_block_attention`` — or, ``--geom latent``, ``ops.paged_latent_attention``
(two pools: a latent of 512 and ONE rotated key of 64 a position, 16 heads) —
alone, on the chip, at a cell's shapes: what its common pass costs at each
number of rows, and — where the kernel takes ``n_real`` (ISSUES 48, 49) — that
the outputs at the real positions are the ``n_real=None`` call's bit for bit.

A seeded pool, ``--rows`` rows behind ``--common`` blocks they all hold and see
whole, each with two blocks of its own, a fast-forward block of 1 + 8 positions
(``--positions``) a row of which ``n_real`` (seeded, ~1.4 of 9 as the flood cells' are) are real
and the rest copies of the row's last real one, as the chunk program's
``ff_body`` builds them. Three walls a row count, each the median of ``--reps``
launches of ``--layers`` calls in one program: ``own`` (the rows' first blocks
differ: nothing rides, and they stand in their own two blocks), ``whole`` (they
ride, every position counted real) and ``packed`` (they ride, ``n_real``
handed down; absent where the kernel takes none, so the parent's tree runs this
file too). ``whole - own`` is the common pass at the block's width,
``packed - own`` on the real positions.

    python3 tools/block_attn_check.py [--geom mistral olmoe cmdaplus latent] [--rows 8 32] [--seed 7]

``--window W`` (ISSUE 51; ``--geom smallthinker phi4flash`` are the two cells whose
window binds, ``--common 64`` the first one's head) times a WINDOWED layer's call,
its split made once a program as a forward makes it: ``own`` then lays the SAME
K/V under block ids of each row's own, so nobody rides and every row walks its
window alone (the walk before ISSUE 51, on any tree); ``whole`` / ``packed``
name the common blocks by the same ids, so the whole blocks inside every row's
window are a common RANGE (``range_blocks``, ``low_items`` of the split; 0 on a
tree without one). The outputs at the real positions are then held, bit for bit,
against the ``own`` call's as well (on the chip; in interpret mode on the CPU the two
round alike only at a power-of-two ``scale``: the CPU's compiler fuses ``dot *
scale - m`` into one multiply-add where no mask stands between them).

A line of JSON a geometry and row count, on stdout and appended to
``chiprun_out/block_attn_check.jsonl``; exit code 1 where a real position's
output differs between the packed and the whole call.
"""

from __future__ import annotations

import argparse
import inspect
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from tpu_voice_agent import ops  # noqa: E402

# (q heads, kv heads, head_dim) of the cells that run the block kernel; "latent":
# (heads, latent width, rotated width) of the one that runs the latent kernel
# (``moonlight_flood``: 7 common blocks a row where the others hold 6)
GEOMS = {"mistral": (32, 8, 128), "olmoe": (16, 16, 128), "cmdaplus": (128, 8, 128),
         "latent": (16, 512, 64),
         # the cells whose window binds: SmallThinker's heads, and the hybrid's as
         # ``models.sambay`` hands them to the kernel (two heads packed into one of 128)
         "smallthinker": (28, 4, 128), "phi4flash": (40, 10, 128)}
BS, POOL, COLUMNS = 128, 200, 12


def kernel(name: str):
    """-> (the op, its keywords, whether it takes ``n_real``)."""
    if name == "latent":
        fn, kw = ops.paged_latent_attention, {"scale": (128 + 64) ** -0.5}
    else:  # the hybrid takes a DIFFERENCE of two heads' outputs: float32 out of the kernel
        fn, kw = ops.paged_block_attention, {"out_dtype": jnp.float32} if name == "phi4flash" else {}
    return fn, kw, "n_real" in inspect.signature(fn.__wrapped__).parameters


def case(rng, B: int, T: int, name: str, common: int, layers: int, rides: bool,
         same_kv: bool = False):
    """-> (queries, pools, tables, positions, n_real): the op's operands in its order.
    ``same_kv`` (a windowed call): rows that do not ride hold the common blocks'
    K/V all the same, under ids of their own."""
    key = jax.random.PRNGKey(int(rng.integers(1 << 30)))
    kq, kk, kv = jax.random.split(key, 3)
    normal = lambda k, *shape: jax.random.normal(k, shape, jnp.bfloat16)
    pool = max(POOL, common * (B + 1) + 2 * B) if same_kv else POOL
    if name == "latent":
        H, C, R = GEOMS[name]
        queries = (normal(kq, B, T, H, C), normal(jax.random.fold_in(kq, 1), B, T, H, R))
        pools = (normal(kk, layers, pool, BS, C), normal(kv, layers, pool, BS, R))
    else:
        nq, nkv, hd = GEOMS[name]
        queries = (normal(kq, B, T, nq, hd),)
        pools = (normal(kk, layers, pool, BS, nkv, hd), normal(kv, layers, pool, BS, nkv, hd))
    tables = np.zeros((B, max(COLUMNS, common + 4)), np.int32)
    first = common if rides or same_kv else 0
    tables[:, :first] = np.arange(first)[None, :]
    tables[:, first:first + 2] = common + 2 * np.arange(B)[:, None] + np.arange(2)[None, :]
    if same_kv:
        mine = common + 2 * B + common * np.arange(B)[:, None] + np.arange(common)[None, :]
        pools = tuple(p.at[:, mine.reshape(-1)].set(p[:, np.tile(np.arange(common), B)])
                      for p in pools)
        if not rides:
            tables[:, :common] = mine
    n_real = np.minimum(rng.geometric(0.7, size=B), T).astype(np.int32)
    base = first * BS + rng.integers(40, 200, size=B)
    # a padded position is a copy of its row's last real one: its query, its position
    t_of = last_real(n_real, T)
    queries = tuple(jnp.take_along_axis(q, jnp.asarray(t_of)[:, :, None, None], axis=1)
                    for q in queries)
    return (queries, pools, jnp.asarray(tables),
            jnp.asarray((base[:, None] + t_of).astype(np.int32)), jnp.asarray(n_real))


def last_real(n_real, T: int) -> np.ndarray:
    """(B, T): t where position t is real, else the row's last real one."""
    return np.minimum(np.arange(T)[None, :], np.asarray(n_real)[:, None] - 1)


def call_kw(tables, positions, n_real, window: int | None, alive=None) -> dict:
    """The op's keywords behind its operands: ``n_real`` where it is handed down, the
    live rows where some are not, and behind a ``window`` the split a forward makes."""
    more = {} if n_real is None else {"n_real": n_real}
    if window is not None:
        more |= {"window": jnp.int32(window), "split": ops.common_block_split(
            tables, positions, alive, BS, window=window, **more)}
    return more if alive is None else more | {"live": alive}


def program(name: str, layers: int, with_n_real: bool, window: int | None = None,
            live: int | None = None):
    """``layers`` calls in one program (the pool holds fewer planes: the index wraps);
    behind a ``window`` with the split a forward makes once for them all. ``live``:
    the first so many rows alone are live."""
    fn, kw, _ = kernel(name)

    def run(queries, pools, tables, positions, n_real):
        alive = None if live is None else jnp.arange(tables.shape[0]) < live
        more = call_kw(tables, positions, n_real if with_n_real else None, window, alive)

        def layer(carry, li):
            out = fn(*queries, *pools, tables, positions, li % pools[0].shape[0], **kw, **more)
            return carry + out.astype(jnp.float32), None
        return jax.lax.scan(layer, jnp.zeros(queries[0].shape, jnp.float32),
                            jnp.arange(layers, dtype=jnp.int32))[0]
    return jax.jit(run)


def wall_ms(fn, args, reps: int) -> float:
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--geom", nargs="+", default=["mistral"], choices=sorted(GEOMS))
    ap.add_argument("--rows", nargs="+", type=int, default=[8, 32])
    ap.add_argument("--common", type=int, help="blocks every row holds (6; latent: 7)")
    ap.add_argument("--window", type=int, help="a windowed layer's call: positions a query sees")
    ap.add_argument("--live", type=int, help="time the programs with the first so many rows alone live")
    ap.add_argument("--positions", type=int, default=9, help="positions a row's block holds (T)")
    ap.add_argument("--layers", type=int, help="calls a program (32; latent: 17)")
    ap.add_argument("--pool-layers", type=int, default=2)
    ap.add_argument("--reps", type=int, default=9)
    ap.add_argument("--seed", type=int, default=7)
    a = ap.parse_args()
    T, W = a.positions, a.window
    if W is not None and "latent" in a.geom:
        ap.error("the latent kernel takes no window")
    dev = jax.devices()[0]
    out = Path("chiprun_out")
    out.mkdir(exist_ok=True)
    same = True
    for name in a.geom:
        fn, kw, takes_n_real = kernel(name)
        common = a.common or (7 if name == "latent" else 6)
        layers = a.layers or (17 if name == "latent" else 32)
        for B in a.rows:
            line = {"geom": name, "rows": B, "positions": T, "common": common, "layers": layers,
                    "device": dev.device_kind, "takes_n_real": takes_n_real,
                    **({} if a.live is None else {"live": a.live})}
            per_call = lambda ms: ms / layers
            made = {}
            for label, rides in (("own", False), ("whole", True)):
                args = made[label] = case(np.random.default_rng(a.seed), B, T, name, common,
                                          a.pool_layers, rides, same_kv=W is not None)
                line[f"{label}_ms"] = per_call(wall_ms(program(name, layers, False, W, a.live), args, a.reps))
            if W is not None:
                split = call_kw(*args[2:5], W, None if a.live is None else jnp.arange(B) < a.live)["split"]
                low = getattr(split, "n_low", None)  # a tree without a range has no such field
                line |= {"window": W, "range_blocks": int(split.n_common), "items": int(split.n_items),
                         "low_items": 0 if low is None else int(low), "riders": int(split.n_riders)}
            if takes_n_real:
                line["packed_ms"] = per_call(wall_ms(program(name, layers, True, W, a.live), args, a.reps))
                line["positions_real"] = int(jnp.sum(args[4]))

                one = lambda args, n_real: np.asarray(
                    fn(*args[0], *args[1], *args[2:4], jnp.int32(0), **kw,
                       **call_kw(*args[2:4], n_real, W)), np.float32)

                ref, got = one(args, None), one(args, args[4])
                real = np.arange(T)[None, :] < np.asarray(args[4])[:, None]
                line["real_positions_bit_equal"] = bool(np.array_equal(ref[real], got[real]))
                last = np.take_along_axis(got, last_real(args[4], T)[:, :, None, None], axis=1)
                line["others_return_the_last_real"] = bool(np.array_equal(last, got))
                same &= line["real_positions_bit_equal"] and line["others_return_the_last_real"]
                if W is not None:  # and the walk in which nobody rides
                    alone = one(made["own"], args[4])
                    line["real_positions_equal_the_own_walks"] = bool(np.array_equal(alone[real], got[real]))
                    line["largest_difference_from_the_own_walks"] = float(np.abs(alone - got)[real].max())
                    same &= line["real_positions_equal_the_own_walks"]
            line["common_pass_ms"] = line["whole_ms"] - line["own_ms"]
            if "packed_ms" in line:
                line["common_pass_packed_ms"] = line["packed_ms"] - line["own_ms"]
            print(json.dumps(line), flush=True)
            with (out / "block_attn_check.jsonl").open("a") as f:
                f.write(json.dumps(line) + "\n")
    return 0 if same else 1


if __name__ == "__main__":
    raise SystemExit(main())
