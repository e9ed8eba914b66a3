#!/usr/bin/env python3
"""The comparison that decides ``correct``, on many seeds in ONE process:
builds a cell's configuration as ``run.py`` does (no traffic, no window) and
runs ``lib/refcheck.compare`` once a seed. What a tolerance is set from
(step 4 of "How correct is decided"): the largest reading the served engine
gives and the smallest its control gives, per reference.

    python3 benchmark/tools/compare_seeds.py --workload parse_solo --seeds 1,2,3 [--manifest m.json]

On the chip through the chip tool; with JAX_PLATFORMS=cpu at the rehearsal's
widths (counts and control flow, never a device number)."""

from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--manifest", default=None, help="another manifest than BENCHMARK.json")
    args = ap.parse_args()
    os.chdir(ROOT)
    sys.path.insert(0, ROOT)
    from benchmark.lib import refcheck
    from benchmark.lib.manifest import code_problems, load_cell, load_code, load_manifest
    from benchmark.run import program_env, say

    cell = load_cell(load_manifest(args.manifest), args.workload)
    config = cell["config"]
    program_env(config)
    bad = code_problems(cell)
    if bad:
        print("; ".join(bad), file=sys.stderr)
        return 2
    from tpu_voice_agent.utils.compilecache import place_compile_cache

    place_compile_cache()
    rehearsal = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    served = load_code("builders", config["builder"]).build(config, rehearsal, say)
    try:
        seen = [c for seed in args.seeds.split(",") for c in refcheck.compare(served, config, int(seed), say)]
    finally:
        served.close()
    for name in sorted({c["reference"] for c in seen}):
        mine = [c for c in seen if c["reference"] == name]
        tol = load_code("reference", name).TOLERANCE
        say(f"SEEDS reference {name}: {len(mine)} seeds, served largest {max(c['rel_err'] for c in mine):.5f} "
            f"smallest {min(c['rel_err'] for c in mine):.5f}; control smallest "
            f"{min(c['control'] for c in mine):.5f} largest {max(c['control'] for c in mine):.5f}; "
            f"tolerance {tol}; ok {sum(c['ok'] for c in mine)}/{len(mine)}")
    return 0 if all(c["ok"] for c in seen) else 1


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)  # daemon serving threads must not keep the process
