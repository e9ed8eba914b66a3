"""Automatic decode-perf diagnosis (round-3 VERDICT next #1).

Chip time is budgeted, so every chip run must yield the DIAGNOSIS, not just
the headline number. Two probes (the profiler capture that was the third is
``benchmark/run.py --trace 1``, which also reduces what it captures):

- ``decode_step_hlo`` / ``audit_dequant``: lower the engine's T=1 decode
  forward at its real serving shapes, compile, and scan the optimized HLO's
  ENTRY computation for materialized dequantization — ``convert``/
  ``multiply`` instructions with HBM-sized outputs. A mis-fused int8
  dequant triples that weight's traffic (int8 read + bf16 write + bf16
  read); docs/PERF.md hypothesis 1.
- ``marginal_ms_per_token``: the shared slope measurement ``bench.py`` and
  ``benches/bench_batch.py`` report.
"""

from __future__ import annotations

import re

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16,
}

_INSTR = re.compile(
    r"=\s*(?P<dtype>\w+)\[(?P<shape>[\d,]*)\][^\s]*\s+(?P<op>[\w-]+)\(")
_COMP_HEADER = re.compile(r"^(?:ENTRY\s+)?%?(?P<name>[\w.\-]+)\s*\(")
_CALLS = re.compile(r"calls=%?(?P<name>[\w.\-]+)")
_RESULT_NAME = re.compile(r"^(?:ROOT\s+)?%(?P<name>[\w.\-]+)\s*=")
_OPERAND = re.compile(r"%([\w.\-]+)")


def decode_step_hlo(engine) -> str:
    """Optimized HLO of the single-token decode forward at the engine's
    serving shapes (B=1, its cache capacity, its quantized params)."""
    import jax.numpy as jnp

    from ..models.llama import forward, init_kv_cache

    cache = init_kv_cache(engine.cfg, 1, engine.max_len)
    tok = jnp.zeros((1, 1), jnp.int32)
    pos = jnp.zeros((1, 1), jnp.int32)
    # unwrap the compile sentinel down to a jit object: .lower lives there.
    # Guard on hasattr, not bare __wrapped__ — jit objects expose their own
    # __wrapped__ (the plain Python function), which has no .lower
    fwd = forward
    while not hasattr(fwd, "lower") and hasattr(fwd, "__wrapped__"):
        fwd = fwd.__wrapped__
    lowered = fwd.lower(
        engine.params, engine.cfg, tok, pos, cache, engine.rules,
        attn_impl=engine.kernels, unroll=engine.decode_unroll,
    )
    return lowered.compile().as_text()


def _instr_bytes(m: "re.Match") -> int | None:
    dtype = m.group("dtype")
    if dtype not in _DTYPE_BYTES:
        return None
    size = _DTYPE_BYTES[dtype]
    for d in m.group("shape").split(","):
        if d:
            size *= int(d)
    return size


def audit_dequant(hlo_text: str, min_bytes: int = 8 << 20) -> dict:
    """Find wasteful int8-dequant lowerings anywhere they can hide.

    The decode forward's layer weights are consumed inside the lax.scan-
    lowered while BODY, not ENTRY, and after the fusion pass the dequant
    lives either in an executable computation (truly materialized) or
    inside a fusion body. The scan therefore covers:

    - every instruction in every EXECUTABLE computation (everything that
      is not a fusion body; their results are real buffers): flag
      ``convert``/``multiply`` with outputs >= min_bytes — a materialized
      dequant triples that weight's HBM traffic
    - every FUSION BODY: a dot lowered as a kLoop fusion (the B=1 matvec
      case: the MXU can't fill from a one-row operand, so XLA's
      broadcast-multiply-reduce on the VPU is the intended lowering) owns
      weight-sized multiplies that are DIRECT operands of a ``reduce``/
      ``dot`` — the dot's own x-broadcast product. Any other weight-sized
      multiply is a per-element scale fused into the chain: not extra HBM
      traffic, but ~2 extra VPU ops per weight, which is what held round
      5's pre-fix decode at 1.69 vs the 1.18 ms/token weight-read floor
      (fix: models.llama._qe moves the scale to the dot OUTPUT). A body
      with NO reduce/matmul whose ROOT is weight-sized and carries a big
      convert/multiply is a pure dequant fusion feeding a real buffer —
      flagged for the same reason as the materialized case.

    Round-5 bug fixed here: tuple-rooted fusion instructions
    (``= (f32[..], f32[..]) fusion(...)``) never matched _INSTR, so their
    ``calls=`` bodies were treated as executable computations and the
    dot's own in-fusion convert/multiply chain was reported as
    "materialized" even after the scale fix. ``calls=`` is now collected
    from raw text, and fusion bodies get the multiply>reduce test above.

    Returns {findings: [(op, dtype, shape, mbytes, computation)],
    scanned_instructions: N}."""
    comps: dict[str, list] = {}
    roots: dict[str, str] = {}  # raw ROOT line per computation — tuple
    # roots never match _INSTR, so they must be kept outside the instr scan
    cur: str | None = None
    # fusion bodies from RAW text: calls= appears on fusion instructions
    # regardless of whether their (possibly tuple) result shape parses
    fusion_bodies = {m.group("name") for m in _CALLS.finditer(hlo_text)}
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            m = _COMP_HEADER.match(line)
            cur = m.group("name") if m else None
            if cur is not None and cur not in comps:
                comps[cur] = []
            continue
        if cur is None:
            continue
        if line.lstrip().startswith("ROOT"):
            roots[cur] = line.lstrip()
        m = _INSTR.search(line)
        if m:
            comps[cur].append((m, line))

    findings = []
    n = 0

    def record(tag, m, size, name):
        findings.append((tag, m.group("dtype"),
                         tuple(int(d) for d in m.group("shape").split(",") if d),
                         round(size / 2**20, 1), name))

    for name, instrs in comps.items():
        in_fusion = name in fusion_bodies
        big_multiplies, big_converts = {}, {}
        dot_operands: set[str] = set()
        n_dotlike = 0
        root_big = False
        root_line = None
        for m, line in instrs:
            n += 1
            op = m.group("op")
            size = _instr_bytes(m)
            big = size is not None and size >= min_bytes
            if in_fusion:
                if big and line.lstrip().startswith("ROOT"):
                    root_big = True
                if op in ("reduce", "dot", "dot-general", "convolution"):
                    n_dotlike += 1
                    # first-level operands of the reduce/dot: the multiply
                    # implementing the dot itself shows up here
                    dot_operands.update(_OPERAND.findall(
                        line.split(op + "(", 1)[-1]))
                elif op in ("multiply", "convert") and big:
                    nm = _RESULT_NAME.match(line.lstrip())
                    bucket = big_multiplies if op == "multiply" else big_converts
                    bucket[nm.group("name") if nm else line] = (m, size)
            elif big and op in ("convert", "multiply"):
                record(op, m, size, name)
        if not in_fusion:
            continue
        # tuple ROOTs never parse via _INSTR (their shape is a tuple), so
        # the raw ROOT line is scanned instead: a big convert/multiply
        # feeding the tuple root IS a materialized buffer
        root_raw = roots.get(name, "")
        if not root_big and "tuple(" in root_raw:
            ops = set(_OPERAND.findall(root_raw.split("tuple(", 1)[-1]))
            root_big = bool(ops & (big_multiplies.keys()
                                   | big_converts.keys()))
        if n_dotlike == 0:
            # no dot in the body: a big convert/multiply here is a pure
            # dequant fusion — but only a weight-sized ROOT means a real
            # HBM buffer is written (a small root, e.g. a slice of the
            # converted weight, materializes nothing big)
            if root_big:
                for m, size in (list(big_multiplies.values())
                                + list(big_converts.values()))[:1]:
                    record("fusion:dequant", m, size, name)
        else:
            for nm, (m, size) in big_multiplies.items():
                if nm not in dot_operands:
                    record("fusion:scale-in-dot", m, size, name)
    return {"findings": findings, "scanned_instructions": n}


def marginal_ms_per_token(engine, prompt: str, lengths=(64, 192),
                          tries: int = 3,
                          with_steps: bool = False):
    """Marginal decode ms/token by slope over two generation lengths —
    cancels the fixed prefill/dispatch/readback cost that poisons ms/steps
    at short lengths (the round-2 '14% of roofline' artifact).

    ``with_steps=True`` returns (slope, (steps_lo, steps_hi)) so callers
    report the ACTUAL step counts the slope spans (a run may stop short of
    the requested length at the cache capacity or byte budget)."""
    pts: dict[int, float] = {}
    for n in lengths:
        best = None
        for _ in range(tries):
            r = engine.generate(prompt, max_new_tokens=n, constrained=False,
                                byte_budget=1_000_000, ignore_eos=True)
            best = r if best is None or r.decode_ms < best.decode_ms else best
        if best.steps > 0:
            pts[best.steps] = min(pts.get(best.steps, best.decode_ms),
                                  best.decode_ms)
    ks = sorted(pts)
    slope = None
    if len(ks) >= 2 and ks[-1] > ks[0]:
        s = (pts[ks[-1]] - pts[ks[0]]) / (ks[-1] - ks[0])
        # a non-positive slope means the short run was slower than the long
        # one — host contention noise, not a real rate; report "no reading"
        # rather than a nonsense number
        if s > 0:
            slope = s
    if with_steps:
        return slope, (ks[0], ks[-1]) if len(ks) >= 2 else None
    return slope
