#!/usr/bin/env python3
"""A cell's per-layer metrics read from a kept readers' input
(``tools/ctx_dump.py``), by the metric files and readers of ANY tree: this
one's, or a parent's unpacked beside it. No JAX, no chip: what a change of a
reader or of a floor does to a reading is seen on the CPU, on the chip's own
trace, and the parent's and the change's files read ONE trace side by side.

    python3 benchmark/tools/read_ctx.py --ctx chiprun_out/ctx/parse_flood.pkl.gz --workload parse_flood \
        [--tree .parent] [--beside .]

prints one JSON object ``{metric: value}`` (a metric with nothing to read is
left out, as in a run); with ``--beside`` a table of both trees' readings,
joined by ``tests/data/fold_table.json`` where a name was folded."""

from __future__ import annotations

import argparse
import gzip
import inspect
import json
import os
import pickle
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def read_tree(tree: str, ctx_path: str, workload: str) -> dict:
    """In a process of its own, so that ``benchmark`` is THAT tree's package."""
    out = subprocess.run([sys.executable, os.path.abspath(__file__), "--ctx", os.path.abspath(ctx_path),
                          "--workload", workload, "--tree", os.path.abspath(tree)],
                         capture_output=True, text=True, cwd=os.path.abspath(tree))
    if out.returncode:
        raise SystemExit(f"{tree}: {out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def read_here(tree: str, ctx_path: str, workload: str) -> dict:
    sys.path.insert(0, tree)
    os.chdir(tree)
    from benchmark.lib import manifest as mf

    with gzip.open(ctx_path, "rb") as f:
        ctx = pickle.load(f)
    cell = mf.load_cell(mf.load_manifest(), workload)
    by_cell = "cell" in inspect.signature(mf.load_layer_metric).parameters  # a parent of PR 42 has no cells' own files
    values = {}
    for m in cell["per_layer"]:
        spec = mf.load_layer_metric(m["name"], workload) if by_cell else mf.load_layer_metric(m["name"])
        v = mf.load_code("readers", spec["reader"]).read(ctx, **spec.get("args", {}))
        if v is not None:
            values[m["name"]] = float(v)
    return values


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--ctx", required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--tree", default=ROOT)
    ap.add_argument("--beside", default=None, help="a second tree: print both, old name | new name | old | new")
    args = ap.parse_args()
    if args.beside is None:
        print(json.dumps(read_here(os.path.abspath(args.tree), args.ctx, args.workload)))
        return 0
    old, new = (read_tree(t, args.ctx, args.workload) for t in (args.tree, args.beside))
    with open(os.path.join(ROOT, "benchmark", "tests", "data", "fold_table.json")) as f:
        renamed = {(r["old"], r["cell"]): r for r in json.load(f)}
    seen = set()
    for name, v in old.items():
        row = renamed.get((name, args.workload), {"new": name})
        seen.add(row["new"])
        w = new.get(row["new"])
        mark = "" if w is None or v == w else f"  x{w / v:.4f}" if v else "  moved"
        print(f"{name} | {row['new']} | {v:.6g} | {'-' if w is None else format(w, '.6g')}"
              f"{mark}{'  [step 4 ' + row['step4'] + ']' if row.get('step4') else ''}")
    for name, w in new.items():
        if name not in seen:
            print(f"- | {name} | - | {w:.6g}  [new in this cell]")
    return 0


if __name__ == "__main__":
    sys.exit(main())
