#!/usr/bin/env python3
"""A selected layer's attention alone, no model, at a cell's shapes: the two
ways ``models.dots3`` fetches a tile's chosen keys (ISSUE 62), timed inside ONE
program over distinct planes —

- ``gather``: one ROW a chosen key out of the pool (``plane[li, sblk, sel % bs]``:
  ``--tile`` x ``--topk`` rows of C + R) and ``ops.sparse_latent.sparse_latent_attention``
  over them, a slot's heads a group;
- ``walk``: ``ops.sparse_latent.walked_latent_attention`` — the tile's table
  columns block by block straight out of the pool under the selection as a
  membership mask, the columns every slot holds in common (``--common``) read once.

A seeded pool; ``--tile`` slots behind ``--common`` blocks they all hold, each
with blocks of its own behind them to ``--keys`` positions of table, standing
8-40 tokens (and a few decoded ones) past the common head as the sitemap cells'
rows do; seeded scores, ``lax.top_k`` of them the selection of BOTH paths (what
is timed is the fetch and the attention, not the indexer). A program is
``--passes`` tile passes (a forward's 8 layers x 3 tiles) over ``--planes``
distinct planes; a reading is the median of ``--reps`` launches over the passes.

    python3 tools/selected_attn_check.py [--heads 64 128] [--keys 8832] [--common 64] [--topk 2048] [--tile 16]

A line of JSON a head count, on stdout and appended to
``chiprun_out/selected_attn_check.jsonl``: us a tile pass of each path, their
ratio, what the tiles' item lists cost a forward (``walk_split``), the slot-ns constants they give ``ops.sparse_latent.walks`` (the rule's
are these readings) and the rule's verdict at these shapes. ``--try COLS:SUB ...``
reads the walk at other key-tile and sub-chunk widths (``_WALK_COLS`` table
columns an item, ``_WALK_SUB`` query rows a step). On the CPU (interpret
mode: pass small shapes) it checks that the two paths agree and prints no time.
Exit code 1 where they differ by more than the pool dtype's rounding, or where the
membership mask (``top_k_members``) is not ``lax.top_k``'s index set on this device.

``--select`` reads the SELECTION alone instead (ISSUE 63; the stop rule's instrument): us a
tile, at ``--tile`` / ``--keys`` / ``--topk``, of the three ways to a walked tile's members'
mask — ``top_k`` (``lax.top_k``, a full sort of every row, + ``top_k_members``), ``select_xla``
(``ops.sparse_latent``'s exact threshold select as XLA alone, its compare-and-count steps
unrolled) and ``select_kernel`` (``threshold_members``: the same steps over the tile's keys
resident in VMEM) — over ``--passes`` distinct score planes cut at ``seq <= position`` as the
cells cut them, in one program each (``program_us``: the same program with no selection in it —
what every reading holds of the scan's own; the ``_net`` ratios are taken without it); and
whether each form's mask IS a scatter of ``top_k``'s indices on this device, on those planes and
on planted rows (ties at the k-th, zeros of both signs, rows that run out, a row all -inf, NaNs
of both signs). One line of JSON, exit code 1 on a difference.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from tpu_voice_agent.ops import sparse_latent as sl  # noqa: E402

BS, C, R = 128, 512, 64
SCALE = (128 + 64) ** -0.5
TOLERANCE = 2e-2  # of the largest output: two bf16 roundings of a softmax's weights


def case(seed: int, tile: int, H: int, keys: int, common: int, topk: int, planes: int):
    """-> (q_c, q_r, plane stack, tables, positions, sel, sblk, chosen, split)."""
    rng = np.random.default_rng(seed)
    nb = -(-keys // BS)
    N = common + tile * (nb - common) + 1
    kq, kr, kp, ks = jax.random.split(jax.random.PRNGKey(seed % (1 << 31)), 4)
    normal = lambda k, *shape: jax.random.normal(k, shape, jnp.bfloat16)
    q_c, q_r = normal(kq, tile, H, C) * 0.3, normal(kr, tile, H, R) * 0.3
    plane = normal(kp, planes, N, BS, C + R)
    tables = np.zeros((tile, nb), np.int32)
    tables[:, :common] = np.arange(common)[None, :]
    tables[:, common:] = common + np.arange(tile * (nb - common)).reshape(tile, nb - common)
    room = nb * BS - common * BS
    pos = common * BS + np.minimum(rng.integers(8, 41, size=tile) + rng.integers(0, 160, size=tile), room) - 1
    pos = jnp.asarray(np.maximum(pos, 0).astype(np.int32))
    seq = jnp.arange(nb * BS, dtype=jnp.int32)[None, :]
    mine = jnp.where(seq <= pos[:, None], jax.random.normal(ks, (tile, nb * BS), jnp.float32), -jnp.inf)
    K = min(topk, nb * BS)
    _, sel = jax.lax.top_k(mine, K)
    tables = jnp.asarray(tables)
    sblk = jnp.take_along_axis(tables, sel // BS, axis=1)
    chosen = sl.chosen_mask(mine, K) & (seq <= pos[:, None])
    split = jax.tree.map(lambda a: a[0], sl.walk_split(tables, pos, tile, BS))
    return q_c, q_r, plane, tables, pos, sel, sblk, chosen, split


def planted(seed: int, tile: int, keys: int) -> jax.Array:
    """(tile, keys) scores with ties at the k-th value (zeros of both signs among them) and rows
    that run out."""
    mine = jnp.round(jax.random.normal(jax.random.PRNGKey(seed % (1 << 31)), (tile, keys), jnp.float32) * 1.5) / 2
    ends = jnp.linspace(1, keys - 1, tile).astype(jnp.int32)[:, None]
    return jnp.where(jnp.arange(keys)[None, :] <= ends, mine, -jnp.inf)


def top_k_s_set(mine: jax.Array, k: int) -> jax.Array:
    """A scatter of ``lax.top_k``'s indices: the yardstick every mask is held to."""
    sel = jax.lax.top_k(mine, k)[1]
    return jnp.zeros(mine.shape, bool).at[jnp.arange(mine.shape[0])[:, None], sel].set(True)


def members_are_top_k_s(seed: int, tile: int, keys: int, topk: int) -> bool:
    """On THIS device: ``top_k_members`` against a scatter of ``lax.top_k``'s indices."""
    mine, k = planted(seed, tile, keys), min(topk, keys)
    return bool(jnp.array_equal(sl.chosen_mask(mine, k), top_k_s_set(mine, k)))


# the three ways to a walked tile's members' mask, (tile, S) float32 scores -> (tile, S) bool
SELECTS = {
    "top_k": sl.chosen_mask,
    "select_xla": lambda mine, k: sl._select_members(
        lambda: sl._total_order(mine), lambda v: (lambda: v), k, unroll=True) != 0,
    "select_kernel": sl.threshold_members,
}


def select_reading(a, timed: bool) -> dict:
    """``--select``: each form's us a tile and whether its mask is ``top_k``'s set."""
    k = min(a.topk, a.keys)
    kp, ks = jax.random.split(jax.random.PRNGKey(a.seed % (1 << 31)))
    pos = jax.random.randint(kp, (a.passes, a.tile, 1), max(a.keys - 700, 0), a.keys)  # 8-40 + a few past the head
    scores = jnp.where(jnp.arange(a.keys)[None, None, :] <= pos,
                       jax.random.normal(ks, (a.passes, a.tile, a.keys), jnp.float32), -jnp.inf)
    rows = planted(a.seed, a.tile, a.keys)
    odd = rows.at[0].set(-jnp.inf).at[1, ::3].set(jnp.nan).at[1, 1::7].set(-jnp.nan).at[2, : a.keys // 2].set(jnp.nan)
    line = {"select": True, "tile": a.tile, "keys": a.keys, "topk": k, "passes": a.passes,
            "device": jax.devices()[0].device_kind}
    time_of = lambda form: jax.jit(lambda planes: jax.lax.scan(
        lambda n, mine: (n + form(mine, k).astype(jnp.int32), None), jnp.zeros(planes.shape[1:], jnp.int32), planes)[0])
    held = [(mine, top_k_s_set(mine, k)) for mine in (*scores, rows, odd)]
    for name, form in SELECTS.items():
        line[f"{name}_is_top_k_s"] = all(bool(jnp.array_equal(form(mine, k), want)) for mine, want in held)
        if timed:
            line[f"{name}_us"] = wall_us(time_of(form), (scores,), a.reps, a.passes)
    if timed:
        # the program's own: the pass with NO selection in it (a plane cut out of the stack, one compare, the sum)
        own = line["program_us"] = wall_us(time_of(lambda mine, k: mine > 0.0), (scores,), a.reps, a.passes)
        for name in list(SELECTS)[1:]:
            line[f"{name}_over_top_k"] = line[f"{name}_us"] / line["top_k_us"]
            line[f"{name}_over_top_k_net"] = (line[f"{name}_us"] - own) / (line["top_k_us"] - own)
    return line


def gather_pass(q_c, q_r, plane, li, pos, sel, sblk):
    tile, H, _ = q_c.shape
    kv = plane[li, sblk, sel % BS]
    return sl.sparse_latent_attention(q_c, q_r, kv, sel, jnp.zeros((tile, H), jnp.int32),
                                      jnp.broadcast_to(pos[:, None], (tile, H)), scale=SCALE)


def walk_pass(q_c, q_r, plane, li, chosen, tables, split):
    return sl.walked_latent_attention(q_c, q_r, plane, li, chosen, tables, split, scale=SCALE)


def program(one, passes: int):
    """``passes`` tile passes in one program, each over another plane (the stack holds fewer: it wraps)."""
    def run(q_c, q_r, plane, *rest):
        def layer(carry, li):
            return carry + one(q_c, q_r, plane, li % plane.shape[0], *rest).astype(jnp.float32), None
        return jax.lax.scan(layer, jnp.zeros(q_c.shape[:2] + (C,), jnp.float32),
                            jnp.arange(passes, dtype=jnp.int32))[0]
    return jax.jit(run)


def wall_us(fn, args, reps: int, passes: int) -> float:
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e6)
    return statistics.median(times) / passes


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--heads", nargs="+", type=int, default=[64, 128])
    ap.add_argument("--keys", type=int, default=8832, help="positions a row's table spans (nb * bs)")
    ap.add_argument("--common", type=int, default=64, help="leading table columns every slot holds")
    ap.add_argument("--topk", type=int, default=2048)
    ap.add_argument("--tile", type=int, default=16)
    ap.add_argument("--passes", type=int, default=24)
    ap.add_argument("--planes", type=int, default=8)
    ap.add_argument("--reps", type=int, default=9)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--select", action="store_true",
                    help="read the selection alone: us a tile of top_k + top_k_members and of each form of "
                         "the threshold select, and whether each mask is top_k's set on this device")
    ap.add_argument("--try", dest="tries", nargs="+", default=[], metavar="COLS:SUB",
                    help="read the walk at these widths too (_WALK_COLS:_WALK_SUB)")
    a = ap.parse_args()
    timed = jax.devices()[0].platform != "cpu"
    out = Path("chiprun_out")
    out.mkdir(exist_ok=True)

    def report(line):
        print(json.dumps(line), flush=True)
        with (out / "selected_attn_check.jsonl").open("a") as f:
            f.write(json.dumps(line) + "\n")

    if a.select:
        line = select_reading(a, timed)
        report(line)
        return 0 if all(line[f"{name}_is_top_k_s"] for name in SELECTS) else 1
    agree = members = members_are_top_k_s(a.seed, a.tile, a.keys, a.topk)
    for H in a.heads:
        q_c, q_r, plane, tables, pos, sel, sblk, chosen, split = case(
            a.seed, a.tile, H, a.keys, a.common, a.topk, a.planes)
        K, keys = sel.shape[1], chosen.shape[1]
        g_args, w_args = (q_c, q_r, plane, pos, sel, sblk), (q_c, q_r, plane, chosen, tables, split)
        f32 = lambda x: np.asarray(x, np.float32)
        got, want = f32(walk_pass(q_c, q_r, plane, jnp.int32(1), chosen, tables, split)), f32(
            gather_pass(q_c, q_r, plane, jnp.int32(1), pos, sel, sblk))
        diff = float(np.abs(got - want).max() / np.abs(want).max())
        twin = f32(sl.walked_latent_attention_reference(q_c, q_r, plane, jnp.int32(1), chosen, tables, scale=SCALE))
        from_twin = float(np.abs(got - twin).max() / np.abs(twin).max())
        agree &= diff < TOLERANCE and from_twin < TOLERANCE
        line = {"heads": H, "tile": a.tile, "keys": keys, "topk": K, "common_items": int(split.n_common),
                "items": int(split.n_items), "device": jax.devices()[0].device_kind,
                "largest_difference": diff, "largest_difference_from_the_twin": from_twin,
                "largest_output": float(np.abs(got).max()), "members_are_top_k_s": members, "rule_walks": bool(sl.walks(keys, K, H))}
        if timed:
            # the gathered kernel alone, over rows gathered before the program (the same every pass)
            kernel_alone = program(lambda q_c, q_r, plane, li, pos, sel, kv: sl.sparse_latent_attention(
                q_c, q_r, kv, sel, jnp.zeros((a.tile, H), jnp.int32),
                jnp.broadcast_to(pos[:, None], (a.tile, H)), scale=SCALE), a.passes)
            line["gathered_kernel_us"] = wall_us(
                kernel_alone, (q_c, q_r, plane, pos, sel, plane[1, sblk, sel % BS]), a.reps, a.passes)
            line["gather_us"] = wall_us(program(gather_pass, a.passes), g_args, a.reps, a.passes)
            line["walk_us"] = wall_us(program(walk_pass, a.passes), w_args, a.reps, a.passes)
            for widths in a.tries:
                was = sl._WALK_COLS, sl._WALK_SUB
                sl._WALK_COLS, sl._WALK_SUB = map(int, widths.split(":"))
                jax.clear_caches()
                other = case(a.seed, a.tile, H, a.keys, a.common, a.topk, a.planes)[-1]  # its items
                line[f"walk_us_{widths}"] = wall_us(
                    program(walk_pass, a.passes), (*w_args[:-1], other), a.reps, a.passes)
                sl._WALK_COLS, sl._WALK_SUB = was
                jax.clear_caches()
            # the items of a forward's 288 positions (18 such tiles), made once a forward for all its layers
            split_all = jax.jit(lambda t, p: sl.walk_split(jnp.tile(t, (18, 1)), jnp.tile(p, 18), a.tile, BS))
            line["split_us_a_forward"] = wall_us(split_all, (tables, pos), a.reps, 1)
            line["walk_over_gather"] = line["walk_us"] / line["gather_us"]
            slot_ns = lambda us: us * 1e3 / a.tile
            line["readings_ns"] = {
                "gather_row": slot_ns(line["gather_us"] - line["gathered_kernel_us"]) / K,
                "gathered_key_head": slot_ns(line["gathered_kernel_us"]) / (K * H),
                "walked_key_head": slot_ns(line["walk_us"]) / (keys * H)}
        report(line)
    return 0 if agree else 1


if __name__ == "__main__":
    raise SystemExit(main())
