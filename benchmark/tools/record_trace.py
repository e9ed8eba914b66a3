#!/usr/bin/env python3
"""Record the small device trace the reducer's test reads
(``benchmark/tests/data/small_trace.json``): a few jitted matmuls with idle
gaps between them, under the harness's two anchors. Run on the chip:
``python3 benchmark/tools/record_trace.py <out.json>``; the output is the
reducer's plain-tuple form of the ``.xplane.pb``, so the test needs no
profiler."""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp

    from benchmark.lib import trace as tr

    step = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    step(x).block_until_ready()
    d = os.path.join(os.path.dirname(out) or ".", ".small_trace")
    shutil.rmtree(d, ignore_errors=True)
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation(tr.ANCHOR):
        wall = time.time()
    for _ in range(5):
        step(x).block_until_ready()
        time.sleep(0.004)
    with jax.profiler.TraceAnnotation(tr.ANCHOR_END):
        pass
    jax.profiler.stop_trace()
    data = tr.load_xplane(tr.find_xplane(d))
    data["anchor_wall_s"] = wall
    data["device_kind"] = jax.devices()[0].device_kind
    with open(out, "w") as f:
        json.dump(data, f)
    print({p: {ln: len(evs) for ln, evs in lines.items()} for p, lines in data["device"].items()},
          data["host"])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
