#!/usr/bin/env python3
"""Bounds from runs. Reads result lines (one JSON object per run, as
``run.py`` prints them, each prefixed by ``RUN <cell> <set> <seed> ``) and
prints, per cell and metric: each set's median and spread (interquartile
distance over the median, ``statistics.quantiles(n=4)``), the wider spread,
the second set's median against the first's, and the bound the contract's
rule gives (five times the widest spread over the cells, never under 1 %).

    python3 benchmark/tools/spread.py runs.log [more.log ...]
"""

from __future__ import annotations

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
from benchmark.lib.stats import spread  # noqa: E402


def main(paths: list[str]) -> int:
    runs: dict = {}
    for path in paths:
        for line in open(path, errors="replace"):
            if not line.startswith("RUN "):
                continue
            _, cell, which, seed, payload = line.split(" ", 4)
            try:
                res = json.loads(payload)
            except ValueError:
                continue
            for name, m in res["metrics"].items():
                runs.setdefault((cell, name), {}).setdefault(which, []).append(
                    (int(seed), m["value"], res["correct"]))
    widest: dict = {}
    for (cell, name), sets in sorted(runs.items()):
        row, spreads, medians = [], [], []
        for which, vals in sorted(sets.items()):
            xs = [v for _, v, _ in vals]
            if name == "setup_s" and len(xs) > 1:
                xs = xs[1:]  # the first run of a side compiles; it is recorded apart
            med = statistics.median(xs)
            spreads.append(spread(xs) if len(xs) > 1 else 0.0)
            medians.append(med)
            row.append(f"set {which}: n {len(xs)} median {med:.4f} spread {100 * spreads[-1]:.2f}% "
                       f"min {min(xs):.4f} max {max(xs):.4f} "
                       f"correct {sum(c for _, _, c in vals)}/{len(vals)}")
        drift = (medians[-1] - medians[0]) / medians[0] if len(medians) > 1 else 0.0
        print(f"{cell:16s} {name:28s} " + " | ".join(row) + f" | second vs first {100 * drift:+.2f}%")
        widest[name] = max(widest.get(name, 0.0), max(spreads))
    for name, w in sorted(widest.items()):
        print(f"BOUND {name:28s} widest spread {100 * w:.2f}% -> five times = {100 * 5 * w:.2f}% "
              f"(set {max(0.01, 5 * w):.3f}; refused as loose above {100 * max(0.01, 8 * w):.2f}%)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
