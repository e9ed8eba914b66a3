"""Live HBM ledger: the static plan (utils/hbm_budget.py discipline)
reconciled against measured device memory, continuously.

``hbm_budget`` checks at build time that the flagship config FITS; nothing
ever watched whether the running process still matches that arithmetic.
This module closes the loop:

- ``engine_hbm_plan(engine)`` — shape arithmetic only (no device reads):
  weight bytes from the config's matmul dimensions (int8-aware), KV bytes
  from the engine's actual layout (paged pool blocks / dense slot lines),
  a prefill-activation workspace estimate. The same accounting style as
  ``hbm_budget.pp_tp_hbm_per_chip``, specialized to the dense/paged
  serving engines.
- ``measure_hbm(engine)`` — reality: summed ``nbytes`` over the engine's
  param tree and KV arrays, ``jax.live_arrays()`` for everything alive in
  the process, and the backend's ``memory_stats()`` (bytes_in_use /
  bytes_limit) when the platform exposes them (TPU/GPU; CPU returns none —
  the ledger then reports allocator-tracked bytes only).
- ``record_hbm_gauges(engine)`` — throttled export (``HBM_LEDGER_S``,
  default 1.0 s; the scheduler calls it every chunk) of the
  ``hbm.{weights,kv_pool,workspace,free}_bytes`` gauges plus
  ``hbm.plan_drift`` — (measured − planned) ÷ planned over the accountable
  parts. Drift past ``HBM_DRIFT_WARN`` (default 0.15) is the "your mental
  model of HBM is wrong" alarm: a leaked cache, a double-resident prefix.

Everything degrades gracefully off-TPU: the ledger is exactly as useful on
the CPU harness (allocator-tracked bytes, zero workspace) as the tests
need it to be.
"""

from __future__ import annotations

import os
import time

from . import get_metrics


def _tree_bytes(tree) -> int:
    if tree is None:
        return 0
    import jax

    return sum(int(getattr(x, "nbytes", 0))
               for x in jax.tree_util.tree_leaves(tree))


def engine_hbm_plan(engine) -> dict:
    """Static byte plan for a dense/paged DecodeEngine from config
    arithmetic alone. Mirrors models.llama.init_params' leaf shapes
    (stacked-layer matmuls, bf16 norms, optional MoE experts, int8
    weight-only quantization with f32 per-out-channel scales)."""
    cfg = engine.cfg
    d, f, hd = cfg.dim, cfg.ffn_dim, cfg.head_dim
    nq, nkv, L, V = cfg.n_heads, cfg.n_kv_heads, cfg.n_layers, cfg.vocab_size
    E = getattr(cfg, "n_experts", 0)
    wbytes = 1 if getattr(engine, "quant", None) == "int8" else 2

    attn = d * nq * hd + 2 * d * nkv * hd + nq * hd * d
    ffn = (E * 3 * d * f) if E > 0 else (3 * d * f)
    router = (d * E) if E > 0 else 0
    matmul = L * (attn + ffn) + V * d  # lm_head; embed stays bf16 below
    weights = (matmul + L * router) * wbytes
    if wbytes == 1:
        # f32 per-out-channel scales for every quantized matmul
        out_ch = L * (nq * hd + 2 * nkv * hd + d
                      + ((E * 2 * f + E * d) if E > 0 else (2 * f + d))
                      + (E if E > 0 else 0)) + V
        weights += out_ch * 4
    weights += V * d * 2  # embed: replicated bf16 (a gather — unquantized)
    weights += (L * 2 * d + d) * 2  # attn/mlp norms + final norm, bf16
    if getattr(cfg, "sandwich_norm", False):
        weights += L * 2 * d * 2  # the norms on the two sub-layers' OUTPUTS, bf16
    if getattr(cfg, "ut_steps", 1) > 1:
        weights += (d + 1) * 4  # the exit gate, float32; looped layers are held ONCE

    pool_blocks = getattr(getattr(engine, "allocator", None), "n_blocks", None)
    if pool_blocks is not None:
        # KV_QUANT-aware (ISSUE 12 satellite): bytes-per-block from the
        # stored dtype + scale-plane overhead (ops.kvquant is the single
        # source), so hbm.plan_drift stays ~0 under int8/int4 instead of
        # flagging a phantom 2-4x drift against a bf16-assumed plan
        from ..ops.kvquant import kv_block_bytes

        # the PLANES are the family record's (the layers that write K/V, at the
        # heads they hold — or, where the layers run more than once, one for every
        # (pass, layer)); the weights above are the configuration's n_layers
        fam = engine.family
        if fam.kv_by_head:
            planes, heads, width = fam.cache["planes"]["k"]["kv"]
            kv = pool_blocks * kv_block_bytes(
                planes, engine.block_size, heads, width, getattr(engine, "kv_quant", None))
        else:  # a latent cache: the planes its record names
            kv = pool_blocks * engine.kv_bytes_per_block
    else:
        kv = 2 * L * engine.batch_slots * engine.max_len * nkv * hd * 2  # (no looped model: paged only)
        P = len(getattr(engine, "prefix_ids", ()) or ())
        if P and getattr(engine, "prefix_kv", None):
            kv += 2 * L * P * nkv * hd * 2  # dense prefix KV lives beside

    bucket = max(engine.prefill_buckets) if engine.prefill_buckets else engine.max_len
    workspace = bucket * max(d, f) * 4 * 4  # prefill activation high-water

    return {"weights_bytes": int(weights), "kv_pool_bytes": int(kv),
            "workspace_bytes": int(workspace),
            "total_bytes": int(weights + kv + workspace)}


def measure_hbm(engine) -> dict:
    """Measured bytes: engine-attributed (weights, KV) plus process-wide
    (live arrays, device allocator stats when the platform has them)."""
    import jax

    weights = _tree_bytes(getattr(engine, "params", None))
    if getattr(engine, "allocator", None) is not None:
        kv = _tree_bytes(engine.k_pool) + _tree_bytes(engine.v_pool)  # a hybrid model's are pytrees
        # quantized pools carry their bf16 scale planes beside the values
        for sc in (getattr(engine, "k_scale", None),
                   getattr(engine, "v_scale", None)):
            if sc is not None:
                kv += int(sc.nbytes)
    else:
        cache = getattr(engine, "cache", None)
        kv = _tree_bytes(cache)
        kv += _tree_bytes(getattr(engine, "prefix_kv", None))

    live = None
    try:
        # live_arrays iterates a process-global registry that other threads
        # mutate mid-decode; a rare racing RuntimeError just skips this tick
        live = sum(int(x.nbytes) for x in jax.live_arrays())
    except Exception:
        pass

    out = {"weights_bytes": weights, "kv_pool_bytes": kv}
    if live is not None:
        out["live_bytes"] = live
        out["other_bytes"] = max(0, live - weights - kv)
    stats = jax.devices()[0].memory_stats()  # None on backends that report none (CPU)
    if stats and "bytes_in_use" in stats:
        in_use = int(stats["bytes_in_use"])
        out["bytes_in_use"] = in_use
        # XLA workspace + allocator overhead: what the device holds beyond
        # the arrays the program knows about
        if live is not None:
            out["workspace_bytes"] = max(0, in_use - live)
        if "bytes_limit" in stats:
            out["bytes_limit"] = int(stats["bytes_limit"])
            out["free_bytes"] = max(0, int(stats["bytes_limit"]) - in_use)
    else:
        out["workspace_bytes"] = 0
    return out


# decode_step_bytes moved to utils/costmodel (ISSUE 17): byte accounting
# now lives beside the FLOP model in one source of truth. Re-exported here
# for existing importers; new code should import from costmodel directly.
from .costmodel import decode_step_bytes  # noqa: E402,F401


def hbm_report(engine) -> dict:
    """Plan vs measured vs drift — the /health and bench-artifact body."""
    plan = engine_hbm_plan(engine)
    meas = measure_hbm(engine)
    accounted_plan = plan["weights_bytes"] + plan["kv_pool_bytes"]
    accounted_meas = meas["weights_bytes"] + meas["kv_pool_bytes"]
    drift = ((accounted_meas - accounted_plan) / accounted_plan
             if accounted_plan > 0 else 0.0)
    return {"plan": plan, "measured": meas, "drift": round(drift, 4),
            "t_s": round(time.time(), 3)}


_last_export_s = 0.0


def record_hbm_gauges(engine, min_interval_s: float | None = None,
                      force: bool = False) -> dict | None:
    """Throttled gauge export (the scheduler calls this per chunk; default
    at most once per ``HBM_LEDGER_S`` seconds — ``jax.live_arrays()`` walks
    every live buffer in the process and must not run per chunk)."""
    global _last_export_s
    if min_interval_s is None:
        min_interval_s = float(os.environ.get("HBM_LEDGER_S", "1.0"))
    now = time.monotonic()
    if not force and now - _last_export_s < min_interval_s:
        return None
    _last_export_s = now

    rep = hbm_report(engine)
    meas, plan = rep["measured"], rep["plan"]
    m = get_metrics()
    m.set_gauge("hbm.weights_bytes", float(meas["weights_bytes"]))
    m.set_gauge("hbm.kv_pool_bytes", float(meas["kv_pool_bytes"]))
    m.set_gauge("hbm.workspace_bytes", float(meas.get("workspace_bytes", 0)))
    if "free_bytes" in meas:
        m.set_gauge("hbm.free_bytes", float(meas["free_bytes"]))
    if "live_bytes" in meas:
        m.set_gauge("hbm.live_bytes", float(meas["live_bytes"]))
    m.set_gauge("hbm.plan_total_bytes", float(plan["total_bytes"]))
    m.set_gauge("hbm.plan_drift", rep["drift"])
    if abs(rep["drift"]) > float(os.environ.get("HBM_DRIFT_WARN", "0.15")):
        m.inc("hbm.drift_events")
    return rep
