#!/usr/bin/env python3
"""Record the small trace the span and scope readers' test reads
(``benchmark/tests/data/spans_trace.json``): two "admissions" and one
four-forward "chunk" under the program's own span names, with idle gaps
between them, the device programs named and scoped as the decoder's are.
Run on the chip: ``python3 benchmark/tools/record_spans_trace.py
<out.json>``; the output is ``lib.trace.first_plane``'s plain form of the
``.xplane.pb``, so the test needs no profiler."""

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
    from jax.profiler import StepTraceAnnotation, TraceAnnotation

    from benchmark.lib import trace as tr
    from benchmark.readers import host_spans

    def layer(x, w):
        with jax.named_scope("layer/attn"):
            x = jnp.tanh(x @ w)
        with jax.named_scope("layer/ffn"):
            return jax.nn.silu(x @ w) @ w

    @jax.jit
    def forward_paged(x, w):
        return layer(x, w)

    @jax.jit
    def paged_chunk_decode_loop(x, w):
        def body(c):
            i, x = c
            x = layer(x, w)
            with jax.named_scope("lm_head"):
                x = x / (1.0 + jnp.max(jnp.abs(x)))
            return i + 1, x

        return jax.lax.while_loop(lambda c: c[0] < 4, body, (0, x))[1]

    x = jnp.ones((512, 512), jnp.bfloat16)
    w = jnp.full((512, 512), 0.01, jnp.bfloat16)
    forward_paged(x, w).block_until_ready()
    paged_chunk_decode_loop(x, w).block_until_ready()
    d = os.path.join(os.path.dirname(out) or ".", ".spans_trace")
    shutil.rmtree(d, ignore_errors=True)
    jax.profiler.start_trace(d)
    with TraceAnnotation(tr.ANCHOR):
        pass
    with StepTraceAnnotation("sched.step", step_num=0):
        with TraceAnnotation("sched.admit"):
            for rid in (0, 1):
                with TraceAnnotation("sched.admit.request", rid=rid):
                    with TraceAnnotation("sched.admit.request.tokenize"):
                        time.sleep(0.002)
                    with TraceAnnotation("sched.admit.request.prefill_call"):
                        y = forward_paged(x, w)
                    with TraceAnnotation("sched.admit.request.slot_state"):
                        y.block_until_ready()
        with TraceAnnotation("sched.decode_dispatch"):
            y = paged_chunk_decode_loop(x, w)
        with TraceAnnotation("sched.readback"):
            y.block_until_ready()
        with TraceAnnotation("sched.release"):
            time.sleep(0.001)
    time.sleep(0.002)  # no span: the serving loop's own lines
    with TraceAnnotation("sched.wait_for_work"):
        time.sleep(0.003)
    with TraceAnnotation(tr.ANCHOR_END):
        pass
    jax.profiler.stop_trace()
    data = tr.first_plane(tr.load_xplane(tr.find_xplane(d)))
    if data is None:
        print("the trace holds no device plane: record it on the chip", file=sys.stderr)
        return 2
    data["device_kind"] = jax.devices()[0].device_kind
    with open(out, "w") as f:
        json.dump(data, f)
    print({k: len(v) for k, v in data.items() if k != "device_kind"}, host_spans.reduce(data))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
