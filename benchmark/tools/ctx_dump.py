#!/usr/bin/env python3
"""One ``--trace 1`` run of a cell whose READERS' INPUT is kept: everything a
per-layer reader is handed (``ctx``: the window's records, counter deltas,
step ledger, the reduced trace, the peaks, the model's and the serving keys)
is written to a gzipped pickle, so that two sets of metric files — a parent's
and a change's — read ONE trace (``tools/read_ctx.py``), here or on the CPU.

    chiprun -- python3 benchmark/tools/ctx_dump.py --out chiprun_out/ctx/parse_flood.pkl.gz \
        --workload parse_flood --seed 2147420001 --seconds 45

The run itself is ``benchmark/run.py``'s, unchanged and in this process: the
result line is its own. Nothing the timed window or ``setup_s`` covers knows
of the dump (it is written after ``main`` has returned)."""

from __future__ import annotations

import argparse
import gzip
import os
import pickle
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args, rest = ap.parse_known_args()
    sys.path.insert(0, ROOT)
    from benchmark import run
    from benchmark.lib import manifest as mf

    kept: dict = {}
    load_code = mf.load_code

    class Keeps:
        """A reader that remembers what it was handed."""

        def __init__(self, mod):
            self.read = lambda ctx, **a: (kept.setdefault("ctx", ctx), mod.read(ctx, **a))[1]

    mf.load_code = lambda kind, name: Keeps(load_code(kind, name)) if kind == "readers" \
        else load_code(kind, name)
    sys.argv = [os.path.join(ROOT, "benchmark", "run.py"), *rest, "--trace", "1"]
    code = run.main()
    if "ctx" in kept:
        out = os.path.join(ROOT, args.out)
        os.makedirs(os.path.dirname(out), exist_ok=True)
        with gzip.open(out, "wb", compresslevel=6) as f:
            pickle.dump(kept["ctx"], f, protocol=4)
        print(f"[ctx_dump] {args.out}: {os.path.getsize(out) / 1e6:.1f} MB", file=sys.stderr, flush=True)
    return code


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)  # as run.py: daemon serving threads must not keep the process
